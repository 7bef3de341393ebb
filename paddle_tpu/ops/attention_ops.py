"""Fused attention op.

Reference analog: operators/fused/multihead_matmul_op.cu (inference-only,
fixed layout). Here a first-class training op that picks the best TPU
execution per context:
  * `sp` mesh axis bound (shard_map)  -> ring attention over ICI
  * TPU backend                       -> pallas flash-attention kernel
  * CPU (tests/virtual mesh)          -> blockwise scan formulation
"""
from __future__ import annotations

import logging

from ..monitor import monitor as _monitor
from ..parallel.mesh import SP_AXIS
from .registry import in_var, register_op, set_out

logger = logging.getLogger("paddle_tpu.ops.attention")

# which implementation each attention op lowered to, counted at trace
# time (per program build, like the collective_* stats)
_LOWERED = {
    "pallas": _monitor.get("attention_lowered_pallas"),
    "blockwise": _monitor.get("attention_lowered_blockwise"),
    "ring": _monitor.get("attention_lowered_ring"),
    "xla": _monitor.get("attention_lowered_xla"),
    # the paged one-token decode step (ops/decode_ops.py): the Pallas
    # kernel over live pages, or the gather + einsum formulation
    "paged_decode": _monitor.get("attention_lowered_paged_decode"),
    "paged_decode_reference":
        _monitor.get("attention_lowered_paged_decode_reference"),
    # the same ops under a sliding window (``window`` attr set): booked
    # beside the plain counters, which they also raise
    "pallas_window": _monitor.get("attention_lowered_pallas_window"),
    "xla_window": _monitor.get("attention_lowered_xla_window"),
    "blockwise_window":
        _monitor.get("attention_lowered_blockwise_window"),
    "paged_decode_window":
        _monitor.get("attention_lowered_paged_decode_window"),
    "paged_decode_reference_window":
        _monitor.get("attention_lowered_paged_decode_reference_window"),
}
_downgrades_logged = set()


def _lowered(path, downgrade_reason=None, window=None):
    """Book the path taken.  On a TPU backend a reference formulation
    (blockwise, or the paged decode step's gather + einsum) is a
    downgrade from the Pallas kernels, not an equivalent: say so, once
    per reason."""
    _LOWERED[path].increase()
    if window is not None:
        _LOWERED[path + "_window"].increase()
    if downgrade_reason and downgrade_reason not in _downgrades_logged:
        _downgrades_logged.add(downgrade_reason)
        logger.warning("attention lowered to its reference formulation "
                       "(%s) on a TPU backend, not the Pallas kernels: %s",
                       path, downgrade_reason)


def _attn_infer(op, block):
    q = in_var(op, block, "Q")
    set_out(op, block, "Out", q.shape, q.dtype)


@register_op("flash_attention", infer=_attn_infer, grad="auto")
def _flash_attention(ctx, op):
    import jax

    from .pallas.flash_attention import (blockwise_attention,
                                         flash_attention,
                                         flash_attention_bias)
    from ..parallel.ring import ring_attention, ulysses_attention

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    bias = ctx.get_input(op, "Bias") if op.single_input("Bias") else None
    if bias is not None and bias.ndim != 2:
        # accept [B,1,1,S]-style additive masks; flatten to rows [B, S]
        bias = bias.reshape(bias.shape[0], bias.shape[-1])
    causal = op.attr("causal", False)
    sm_scale = op.attr("scale", None)
    mode = op.attr("seq_parallel_mode", "ring")
    window = op.attr("window", None)
    if window is not None and (not causal or bias is not None):
        raise NotImplementedError(
            "flash_attention: a sliding window needs causal=True and "
            "no padding bias")

    if op.attr("impl", "auto") == "xla":
        if SP_AXIS in (getattr(ctx, "axis_names", ()) or ()):
            raise NotImplementedError(
                "flash_attention impl='xla' under sequence parallelism "
                "would attend over the local shard only; use impl='auto' "
                "(ring/Ulysses)")
        # einsum formulation: one op for the whole scores/softmax/PV
        # chain; layout "bshd" avoids materializing [B,h,S,d] transposes;
        # supports additive row bias, causal, and in-op probability
        # dropout (stateless key from the op's seed).  On v5e at S=128 it
        # measures within ~4% of the explicit-matmul build (763 vs 792
        # samples/s on the BERT bench) and well above the pallas kernel.
        import jax.numpy as jnp

        layout = op.attr("layout", "bhsd")
        d = q.shape[-1]
        scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
        eq = ("bqhd,bkhd->bhqk" if layout == "bshd"
              else "bhqd,bhkd->bhqk")
        s = jnp.einsum(eq, q, k) * scale
        if bias is not None:
            s = s + bias[:, None, None, :].astype(s.dtype)
        if causal:
            S = s.shape[-1]
            keep = jnp.tril(jnp.ones((S, S), bool))
            if window is not None:
                # i - window < j <= i: the window counts the token itself
                keep = keep & ~jnp.tril(jnp.ones((S, S), bool),
                                        -int(window))
            s = jnp.where(keep[None, None], s,
                          jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        prob = op.attr("dropout_prob", 0.0)
        if prob and not (ctx.is_test or op.attr("is_test", False)):
            keep = jax.random.bernoulli(ctx.rng(op), 1.0 - prob, p.shape)
            p = jnp.where(keep, p / (1.0 - prob), 0.0).astype(p.dtype)
        eo = ("bhqk,bkhd->bqhd" if layout == "bshd"
              else "bhqk,bhkd->bhqd")
        out = jnp.einsum(eo, p, v)
        _lowered("xla", window=window)
        ctx.set_output(op, "Out", out)
        return

    axes = getattr(ctx, "axis_names", ()) or ()
    on_tpu = jax.default_backend() == "tpu"
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    if SP_AXIS in axes:
        if bias is not None or window is not None:
            raise NotImplementedError(
                "flash_attention: padding bias or a sliding window under "
                "sequence parallelism not supported yet — pad-free "
                "bucketing or causal only")
        fn = ring_attention if mode == "ring" else ulysses_attention
        out = fn(q, k, v, SP_AXIS, causal=causal, sm_scale=sm_scale)
        _lowered("ring")
    elif on_tpu and n_mesh == 1:
        if bias is not None:
            out = flash_attention_bias(q, k, v, bias, causal, sm_scale)
        elif window is not None:
            out = flash_attention(q, k, v, causal, sm_scale,
                                  window=int(window))
        else:
            out = flash_attention(q, k, v, causal, sm_scale)
        _lowered("pallas", window=window)
    else:
        # multi-device GSPMD: the einsum formulation lets the partitioner
        # shard batch/head/seq dims freely (pallas_call pins the layout)
        out, _ = blockwise_attention(q, k, v, causal=causal,
                                     sm_scale=sm_scale, bias=bias,
                                     window=window)
        _lowered("blockwise",
                 f"flash_attention under a {n_mesh}-device mesh"
                 if on_tpu else None, window=window)
    ctx.set_output(op, "Out", out)


def _attn_qkv_infer(op, block):
    qkv = in_var(op, block, "QKV")
    shape = list(qkv.shape)
    shape[-1] = shape[-1] // 3
    set_out(op, block, "Out", tuple(shape), qkv.dtype)


@register_op("flash_attention_qkv", infer=_attn_qkv_infer, grad="auto")
def _flash_attention_qkv(ctx, op):
    """Transpose-free fused attention on the packed QKV projection.

    QKV [B, S, 3H] -> Out [B, S, H].  On single-device TPU this lowers to
    the packed pallas kernels (ops/pallas/flash_attention.py:
    flash_attention_packed) whose grid reads 128-lane column chunks of
    the projection directly — none of the [B,S,3H] -> [3,B,h,S,d]
    transpose/slice traffic of the split-tensor path ever reaches HBM
    (measured ~2.4 GB/step of pure layout movement on the seq-512 BERT
    bench).  Elsewhere (CPU meshes, GSPMD) it lowers to an einsum
    formulation the partitioner can shard freely.

    Reference analog: operators/fused/multihead_matmul_op.cu takes the
    same packed [B, S, 3H] input (its "qkv weight" layout) — ours adds
    training (fwd+bwd) and long-sequence O(S) memory.
    """
    import jax
    import jax.numpy as jnp

    from .pallas.flash_attention import (flash_attention_packed,
                                         flash_attention_packed_bias)

    qkv = ctx.get_input(op, "QKV")
    bias = ctx.get_input(op, "Bias") if op.single_input("Bias") else None
    if bias is not None and bias.ndim != 2:
        bias = bias.reshape(bias.shape[0], bias.shape[-1])
    causal = op.attr("causal", False)
    sm_scale = op.attr("scale", None)
    nh = op.attr("num_heads")
    B, S, threeH = qkv.shape
    H = threeH // 3
    D = H // nh

    on_tpu = jax.default_backend() == "tpu"
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    packable = H % 128 == 0 and D in (64, 128)
    if on_tpu and n_mesh == 1 and packable:
        if bias is not None:
            out = flash_attention_packed_bias(qkv, bias, nh, causal,
                                              sm_scale)
        else:
            out = flash_attention_packed(qkv, nh, causal, sm_scale)
        _lowered("pallas")
    else:
        # fallback (CPU / GSPMD meshes): blockwise online-softmax — keeps
        # O(S) attention memory so long-sequence mesh training doesn't
        # regress to an [B,h,S,S] materialization, and the einsum body is
        # layout-free for the partitioner
        from .pallas.flash_attention import blockwise_attention

        x = qkv.reshape(B, S, 3, nh, D)
        q = jnp.moveaxis(x[:, :, 0], 1, 2)               # [B,h,S,d]
        k = jnp.moveaxis(x[:, :, 1], 1, 2)
        v = jnp.moveaxis(x[:, :, 2], 1, 2)
        o, _ = blockwise_attention(q, k, v, causal=causal,
                                   sm_scale=sm_scale, bias=bias)
        out = jnp.moveaxis(o, 1, 2).reshape(B, S, H).astype(qkv.dtype)
        reason = None
        if on_tpu:
            reason = (f"flash_attention_qkv under a {n_mesh}-device mesh"
                      if n_mesh > 1 else
                      f"flash_attention_qkv with hidden {H} / head_dim "
                      f"{D} (kernel needs hidden % 128 == 0 and head_dim "
                      f"64 or 128)")
        _lowered("blockwise", reason)
    ctx.set_output(op, "Out", out)



# ---------------------------------------------------------------------------
# fused inference surfaces (reference operators/fused/) — on TPU these
# are plain compositions XLA fuses; the ops exist for API parity with
# the reference's pass-inserted fused kernels.
# ---------------------------------------------------------------------------
def _mm_infer(op, block):
    x = in_var(op, block, "Input")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("multihead_matmul", infer=_mm_infer)
def _multihead_matmul(ctx, op):
    """Reference fused/multihead_matmul_op.cu: Input [B,S,D] projects to
    packed QKV via W [D,3,N,H] (+ Bias [3,N,H]), scaled dot-product
    attention with optional BiasQK added to the logits, heads merged
    back to [B,S,D]."""
    import jax
    import jax.numpy as jnp
    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "W")
    bias = ctx.get_input(op, "Bias")
    n_head = int(op.attr("head_number"))
    alpha = float(op.attr("alpha", 1.0))
    B, S, D = x.shape
    H = D // n_head
    qkv = jnp.einsum("bsd,dknh->kbnsh", x.astype("float32"),
                     w.reshape(D, 3, n_head, H).astype("float32"))
    qkv = qkv + bias.reshape(3, 1, n_head, 1, H)
    q, k, v = qkv[0], qkv[1], qkv[2]
    logits = jnp.einsum("bnsh,bnth->bnst", q, k) * alpha
    if op.input("BiasQK"):
        logits = logits + ctx.get_input(op, "BiasQK").astype("float32")
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnst,bnth->bsnh", probs, v).reshape(B, S, D)
    ctx.set_output(op, "Out", out.astype(x.dtype))


def _skip_ln_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("skip_layernorm", infer=_skip_ln_infer)
def _skip_layernorm(ctx, op):
    """out = LayerNorm(X + Y) (reference fused/skip_layernorm_op.cc)."""
    import jax.numpy as jnp
    x = ctx.get_input(op, "X")
    y = ctx.get_input(op, "Y")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = float(op.attr("epsilon", 1e-5))
    s = (x + y).astype("float32")
    mu = s.mean(-1, keepdims=True)
    var = ((s - mu) ** 2).mean(-1, keepdims=True)
    out = (s - mu) / jnp.sqrt(var + eps) * scale + bias
    ctx.set_output(op, "Out", out.astype(x.dtype))


def _feel_infer(op, block):
    ids0 = block.var(op.input("Ids")[0])
    emb0 = block.var(op.input("Embs")[0])
    set_out(op, block, "Out",
            (ids0.shape[0], ids0.shape[1], emb0.shape[1]), emb0.dtype)


@register_op("fused_embedding_eltwise_layernorm", infer=_feel_infer)
def _fused_embedding_eltwise_layernorm(ctx, op):
    """out = LayerNorm(sum_i Embs_i[Ids_i]) (reference
    fused/fused_embedding_eltwise_layernorm_op.cc)."""
    import jax.numpy as jnp
    ids = ctx.get_inputs(op, "Ids")
    embs = ctx.get_inputs(op, "Embs")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = float(op.attr("epsilon", 1e-5))
    s = None
    for i, e in zip(ids, embs):
        idx = i.reshape(i.shape[:2]).astype("int32")
        g = e[idx].astype("float32")
        s = g if s is None else s + g
    mu = s.mean(-1, keepdims=True)
    var = ((s - mu) ** 2).mean(-1, keepdims=True)
    out = (s - mu) / jnp.sqrt(var + eps) * scale + bias
    ctx.set_output(op, "Out", out.astype(embs[0].dtype))

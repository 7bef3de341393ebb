"""Plain reference for ``granite-4.0-h-micro``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no state
variable, no chunks, no paging, no batching and no kernel, written from
the configuration's own equations (ISSUE 59; the configuration's
``assumed`` list).  Every norm is an RMSNorm with a learned weight and eps
1e-5, no bias but the convolution's.  One layer, x [n, 2048], h =
rms_norm(x):

    mamba:      z | xBC | dt = h W_in                        [4096 | 4352 | 64]
                xBC = silu(conv4(xBC) + b)      causal, depthwise, 4 taps over
                                                all 4352 channels, zero history
                x | B | C = xBC                 x [64 heads, 64], B, C [128]
                                                shared by every head (one group)
                dt = softplus(dt + dt_bias) [64];  a = exp(dt * A),  A = -exp(A_log)
                per head:  S_t = a_t S_{t-1} + (dt_t x_t) B_t^T     S in R^{64 x 128}, S_0 = 0
                           y_t = S_t C_t + D x_t
                y = rms_norm(y * silu(z)) over all 4096 channels, learned [4096]
                out = y W_out
    attention:  q, k, v = h W_q, h W_k, h W_v   (32 query over 8 KV heads of 64)
                NO rotary embedding; softmax scale attention_multiplier (1/64)
                out = causal softmax(q k^T * 0.015625) v  W_o
    x = x + residual_multiplier * out                        (0.22)
    x = x + residual_multiplier * W_2(silu(W_1 h') * (W_3 h')),  h' = rms_norm(x)   (8192)

under ``x_0 = embedding_multiplier * E[ids]`` (12), then the final norm and
``logits = x_norm E^T / logits_scaling`` (8; the tied table).  The
recurrence is kept as it stands, token by token under ``lax.scan``, the
state a head ``[64, 128]`` as the equations have it; the convolution is an
explicit sum over four shifted copies; attention runs in blocks of query
rows.

Departures from the published description: none in the equations.  What
``config.json`` has no key for is the family's modelling code and listed in
the configuration's ``assumed``: the order z | xBC | dt of the fused input
projection and x | B | C of the convolution's channels, silu after the
convolution, the gate before the norm, the norm over all channels at once.
The layers run are the configuration's cut (two periods).

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512

# Roundings that a control puts in (``benchmark/tests/standins.py``): the
# plain reference leaves ``ROUND`` None, every ``_at`` is then the identity
# and adds nothing to the lowered module.  ``where`` is "residual" (the
# stream after a sublayer), "pages" (K and V as a program writes them to
# its pages) or "product" (an activation that enters a product).
ROUND = None


def _at(where, x):
    return x if ROUND is None else ROUND(where, x)


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def params_from_scope(scope, cfg: dict, name: str = "llama") -> dict:
    """The program's weights, by the names ``models/llama.py`` gives them,
    as they lie in the scope (no copy)."""
    def get(n):
        return scope.find_var(f"{name}.{n}")

    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        p = {"ln1": get(f"blk{i}.ln1"), "ln2": get(f"blk{i}.ln2"),
             "gate_up": get(f"blk{i}.gate_up.w"),
             "down": get(f"blk{i}.ffn_out.w")}
        if kind == "mamba":
            p.update(w_in=get(f"blk{i}.ssd_in.w"),
                     conv=get(f"blk{i}.ssd_conv.w"),
                     conv_b=get(f"blk{i}.ssd_conv.b"),
                     a_log=get(f"blk{i}.ssd_A_log"),
                     dt_bias=get(f"blk{i}.ssd_dt_bias"),
                     d=get(f"blk{i}.ssd_D"), y_norm=get(f"blk{i}.ssd_norm"),
                     wo=get(f"blk{i}.ssd_out.w"))
        else:
            p.update(qkv=get(f"blk{i}.qkv.w"), wo=get(f"blk{i}.attn_out.w"))
        layers.append(p)
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f")}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def _attention(q, k, v, scale):
    """q, k, v [H, n, d], causal, in blocks of queries."""
    n = q.shape[1]
    q, k, v = _at("product", q), _at("pages", k), _at("pages", v)
    j = jnp.arange(n)[None, :]
    out = []
    for start in range(0, n, Q_BLOCK):
        i = jnp.arange(start, min(start + Q_BLOCK, n))[:, None]
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + Q_BLOCK], k) \
            * scale                          # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), -1))
        out.append(jnp.einsum("hqk,hkd->hqd", p, v))
    return jnp.concatenate(out, axis=1)


def _short_conv(z, w, b):
    """c_t = b + sum_j w[:, j] * z_{t-(L-1)+j} with z_{<0} = 0: L shifted
    copies of z [n, C], ``w`` [C, L], ``b`` [C]."""
    n, taps = z.shape[0], w.shape[1]
    zp = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return sum(zp[j:j + n] * w[:, j].astype(z.dtype) for j in range(taps)) \
        + b.astype(z.dtype)


def selective_scan(x, dt, a, bm, cm):
    """The recurrence itself, token by token: x [n, H, P], dt [n, H], a
    [H], bm, cm [n, N] -> y [n, H, P] without the skip.  S_0 = 0."""
    def token(s, row):
        xt, dtt, bt, ct = row
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, ct)

    s0 = jnp.zeros((x.shape[1], x.shape[2], bm.shape[1]), x.dtype)
    return jax.lax.scan(token, s0, (x, dt, bm, cm))[1]


def _swiglu(h, gate_up, down):
    inter = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    return _at("product", jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) \
        @ down.astype(h.dtype)


def _mamba(h, p, cfg, eps):
    dtype = h.dtype
    n = h.shape[0]
    heads, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state, inner = cfg["mamba_d_state"], heads * hp
    zxd = h @ p["w_in"].astype(dtype)
    z, dt = zxd[:, :inner], zxd[:, -heads:]
    xbc = jax.nn.silu(_short_conv(zxd[:, inner:-heads], p["conv"],
                                  p["conv_b"]))
    x = xbc[:, :inner].reshape(n, heads, hp)
    bm, cm = xbc[:, inner:inner + state], xbc[:, inner + state:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(dtype))
    a = -jnp.exp(p["a_log"].astype(dtype))
    y = selective_scan(x, dt, a, bm, cm) \
        + p["d"].astype(dtype)[:, None] * x
    y = _rms_norm(y.reshape(n, inner) * jax.nn.silu(z), p["y_norm"], eps)
    return _at("product", y) @ p["wo"].astype(dtype)


def _full_attention(h, p, cfg, eps):
    dtype = h.dtype
    n, hidden = h.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hidden // heads
    qkv = h @ p["qkv"].astype(dtype)

    def split(t, m):
        return t.reshape(n, m, d).transpose(1, 0, 2)

    q = split(qkv[:, :heads * d], heads)
    k = split(qkv[:, heads * d:(heads + kv) * d], kv)
    v = split(qkv[:, (heads + kv) * d:], kv)
    # query head g reads KV head g // (heads / kv)
    k, v = (jnp.repeat(t, heads // kv, axis=0) for t in (k, v))
    y = _attention(q, k, v, cfg["attention_multiplier"])
    return _at("product", y.transpose(1, 0, 2).reshape(n, heads * d)) \
        @ p["wo"].astype(dtype)


def forward(params: dict, token_ids, cfg: dict, rows=None,
            dtype=jnp.float32):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  ``dtype``: the
    precision of every activation, product and of the recurrent state
    (float32; the bfloat16 control passes the other)."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        table = params["embed"].astype(dtype)
        x = _at("residual", table[ids] * cfg["embedding_multiplier"])
        for p, kind in zip(params["layers"], layer_kinds(cfg)):
            mixer = _mamba if kind == "mamba" else _full_attention
            x = _at("residual", x + res * mixer(
                _at("product", _rms_norm(x, p["ln1"], eps)), p, cfg, eps))
            x = _at("residual", x + res * _swiglu(
                _at("product", _rms_norm(x, p["ln2"], eps)), p["gate_up"],
                p["down"]))
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return (x @ table.T) / cfg["logits_scaling"]

"""Plain reference for ``olmo-hybrid-7b``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no state
variable, no chunks, no paging, no batching and no kernel, written from
the configuration's own equations (ISSUE 41; the configuration's
``assumed`` list).  Every norm is an RMSNorm with a learned weight and eps
1e-6, no bias anywhere.  One layer, x [n, 3840]:

    linear_attention:  q~ | k~ | v~ = x W_qkv              [2880 | 2880 | 5760]
                       q | k | v = silu(conv4(q~ | k~ | v~))   causal, depthwise,
                                   4 taps over all 11520 channels, zero history
                       per head h of 30 (q, k of 96; v of 192):
                         q = q / sqrt(|q|^2 + 1e-6) * 96^-1/2;  k = k / sqrt(|k|^2 + 1e-6)
                         beta_t = 2 sigmoid(x W_b)          (2: linear_allow_neg_eigval)
                         g_t = -exp(A_log) softplus(x W_a + dt_bias);  alpha_t = exp(g_t)
                         S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - (alpha_t S_{t-1})^T k_t))^T
                         o_t = S_t^T q_t                     S in R^{96 x 192}, S_0 = 0
                       y = concat_h(rms_norm_192(o_h) * silu((x W_g)_h)) W_o
    full_attention:    q, k, v = x W_q, x W_k, x W_v        (30 heads of 128)
                       q, k = rms_norm over all 3840, learned [3840]; NO rotary embedding
                       y = causal softmax(q k^T / sqrt(128)) v  W_o
    x = x + rms_norm(y)                    (the norm on the mixer's OUTPUT)
    x = x + rms_norm(W_2(silu(W_1 x) * (W_3 x)))           (11008; on the MLP's output)

then the final norm and ``logits = x_norm W_head`` (untied).  The
recurrence is kept as it stands, token by token under ``lax.scan``; the
convolution is an explicit sum over four shifted copies; attention runs in
blocks of query rows.

Departures from the published description: none in the equations.  What
``config.json`` has no key for is the family's modelling code and listed
in the configuration's ``assumed``: no rotary embedding (``rope_theta``
null), norms on the outputs, QK-norm over the whole projection, the
order q | k | v of the fused projection and a | b of the gates' one, silu
after the convolution, L2-normalised q and k.  The layers run are the
configuration's cut (one period).

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])

# Roundings that a control puts in (``benchmark/tests/standins.py``): the
# plain reference leaves ``ROUND`` None, every ``_at`` is then the identity
# and adds nothing to the lowered module.  ``where`` is "residual" (the
# stream after a sublayer), "pages" (K and V as a program writes them to
# its pages) or "product" (an activation that enters a product).
ROUND = None


def _at(where, x):
    return x if ROUND is None else ROUND(where, x)


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
        if kind == "linear_attention":
            p.update(qkv=get(f"blk{i}.gdn_qkv.w"),
                     conv=get(f"blk{i}.gdn_conv.w"),
                     ab=get(f"blk{i}.gdn_ab.w"),
                     a_log=get(f"blk{i}.gdn_A_log"),
                     dt_bias=get(f"blk{i}.gdn_dt_bias"),
                     o_norm=get(f"blk{i}.gdn_norm"),
                     gate=get(f"blk{i}.gdn_gate.w"),
                     wo=get(f"blk{i}.gdn_out.w"))
        else:
            p.update(qkv=get(f"blk{i}.qkv.w"), q_norm=get(f"blk{i}.q_norm"),
                     k_norm=get(f"blk{i}.k_norm"),
                     wo=get(f"blk{i}.attn_out.w"))
        layers.append(p)
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f"),
            "head": get("head.w")}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def _attention(q, k, v):
    """q, k, v [H, n, d], causal, in blocks of queries."""
    _, n, d = q.shape
    q, k, v = _at("product", q), _at("pages", k), _at("pages", v)
    j = jnp.arange(n)[None, :]
    out = []
    for start in range(0, n, Q_BLOCK):
        i = jnp.arange(start, min(start + Q_BLOCK, n))[:, None]
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + Q_BLOCK], k) \
            / float(np.sqrt(d))              # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), -1))
        out.append(jnp.einsum("hqk,hkd->hqd", p, v))
    return jnp.concatenate(out, axis=1)


def _short_conv(z, w):
    """c_t = sum_j w[:, j] * z_{t-(L-1)+j} with z_{<0} = 0: L shifted
    copies of z [n, C], ``w`` [C, L]."""
    n, taps = z.shape[0], w.shape[1]
    zp = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return sum(zp[j:j + n] * w[:, j].astype(z.dtype) for j in range(taps))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence itself, token by token: q, k [n, H, Dk], v
    [n, H, Dv], g, beta [n, H] -> o [n, H, Dv].  S_0 = 0."""
    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[:, None, None] * s                    # alpha S
        r = v - jnp.einsum("hkv,hk->hv", s, k)
        s = s + k[:, :, None] * (beta[:, None] * r)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


def _swiglu(h, gate_up, down):
    inter = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    return _at("product", jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) \
        @ down.astype(h.dtype)


def _linear_attention(x, p, cfg, eps):
    dtype = x.dtype
    n = x.shape[0]
    heads = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    c = jax.nn.silu(_short_conv(x @ p["qkv"].astype(dtype), p["conv"]))
    q = _l2(c[:, :heads * dk].reshape(n, heads, dk)) * (dk ** -0.5)
    k = _l2(c[:, heads * dk:2 * heads * dk].reshape(n, heads, dk))
    v = c[:, 2 * heads * dk:].reshape(n, heads, dv)
    ab = x @ p["ab"].astype(dtype)
    beta = jax.nn.sigmoid(ab[:, heads:])
    if cfg["linear_allow_neg_eigval"]:
        beta = beta * 2.0
    g = -jnp.exp(p["a_log"].astype(dtype)) \
        * jax.nn.softplus(ab[:, :heads] + p["dt_bias"].astype(dtype))
    o = delta_rule(q, k, v, g, beta)
    gate = (x @ p["gate"].astype(dtype)).reshape(n, heads, dv)
    o = _rms_norm(o, p["o_norm"], eps) * jax.nn.silu(gate)
    return _at("product", o.reshape(n, heads * dv)) @ p["wo"].astype(dtype)


def _full_attention(x, p, cfg, eps):
    dtype = x.dtype
    n, hidden = x.shape
    heads = cfg["num_attention_heads"]
    d = hidden // heads
    qkv = x @ p["qkv"].astype(dtype)
    q = _rms_norm(qkv[:, :hidden], p["q_norm"], eps)
    k = _rms_norm(qkv[:, hidden:2 * hidden], p["k_norm"], eps)

    def split(t):
        return t.reshape(n, heads, d).transpose(1, 0, 2)

    y = _attention(split(q), split(k), split(qkv[:, 2 * hidden:]))
    return _at("product", y.transpose(1, 0, 2).reshape(n, hidden)) \
        @ p["wo"].astype(dtype)


def forward(params: dict, token_ids, cfg: dict, rows=None,
            dtype=jnp.float32):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  ``dtype``: the
    precision of every activation, product and of the recurrent state
    (float32; the bfloat16 control passes the other)."""
    eps = cfg["rms_norm_eps"]
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"].astype(dtype)[ids])
        for p, kind in zip(params["layers"], layer_kinds(cfg)):
            mixer = _linear_attention if kind == "linear_attention" \
                else _full_attention
            x = _at("residual",
                    x + _rms_norm(mixer(x, p, cfg, eps), p["ln1"], eps))
            x = _at("residual",
                    x + _rms_norm(_swiglu(x, p["gate_up"], p["down"]),
                                  p["ln2"], eps))
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ params["head"].astype(dtype)

"""Plain reference for ``solar-open2-250b``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no state
variable, no chunks, no paging, no batching and no kernel, written from
the configuration's own equations (ISSUE 43; the configuration's
``assumed`` list).  Every norm is an RMSNorm with a learned weight and eps
1e-5 on the sublayer's INPUT, no bias anywhere.  One layer, x [n, 4096],
``h = rms_norm(x)``:

    attention (layers in gqa_layers):
        q, k, v = h W_q, h W_k, h W_v      (64 / 8 / 8 heads of 128; NO rotary embedding)
        a = causal softmax(q k^T / sqrt(128)) v        (query head j reads KV head j // 8)
        y = (a * sigmoid(h W_g)) W_o       (use_gqa_gate: W_g [4096, 8192], elementwise)
    KDA (the others):
        q | k | v = silu(conv4(h W_qkv))   causal, depthwise, 4 taps over all
                                           24576 channels, zero history
        per head of 64 (q, k, v of 128):
          q = q / sqrt(|q|^2 + 1e-6) * 128^-1/2;  k = k / sqrt(|k|^2 + 1e-6)
          beta_t = 2 sigmoid(h W_b)                     (2: kda_allow_neg_eigval)
          g_t = -exp(A_log) softplus((h W_f_down W_f_up)_t + dt_bias)  in R^128:
                a log decay A KEY CHANNEL (low rank: kda_use_full_proj false)
          S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
          o_t = S_t^T q_t                  S in R^{128 x 128}, S_0 = 0
        y = concat_h(rms_norm_128(o_h) * sigmoid((h W_g_down W_g_up)_h)) W_o
    x = x + y;  h = rms_norm(x)
    FFN (every layer):
        s = sigmoid(h W_r)  [E_router];  sel = top8(s + b), ties to the lower index
        w_e = s_e / (sum_{sel} s + 1e-6) * routed_scaling_factor     (all 8 chosen)
        x = x + sum_{e in sel, e HELD} w_e SwiGLU_e(h) + SwiGLU_shared(h)

then the final norm and ``logits = x_norm W_head`` (untied, over the
vocabulary slice).  ``held = (first, count)`` says which experts this
chip holds: the sum runs over the chosen experts that are held, the
weights are normalised over all eight chosen, and what the absent
experts would add is left out (``held = (0, E_router)`` is the uncut
layer).  The recurrence is kept as it stands, token by token under
``lax.scan``; the convolution is an explicit sum over four shifted copies;
experts are a plain loop over the held ones with a mask; attention runs in
blocks of query rows.

**How the decay is carried.**  ``Diag(exp(g_t))`` is applied as ``1 +
expm1(g_t)``: the same number, but a slow channel's factor (g near 0) is
then 1 plus a small term computed to its own precision, where the chip's
float32 ``exp`` is off by up to 5e-6 relative and the recurrence
compounds that once a token (PERF.md section 6, PR 41).

Departures from the published description: none in the equations.  What
``config.json`` has no key for is listed in the configuration's
``assumed``.  The layers run, the experts held and the vocabulary are the
configuration's cut.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them.

Routing is discrete.  Handed the program's PRE-sigmoid router logits of
the compared ``rows`` (``program_router`` [R, L, E]), a compared row whose
own 8th-9th margin of ``s + b`` is under the configuration's
``near_tie_margin_share_of_router_range`` of the row's range of ``s + b``
takes the program's eight experts, if each of them is within that margin
of the reference's 8th; ``forward`` then also returns what it saw, layer
by layer.  Without them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def layer_kinds(cfg: dict) -> list:
    return ["attention" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


def held_range(cfg: dict) -> tuple:
    """``(first, count)`` of the experts this chip holds, of the router's
    ``cfg["expert_share"]["router_experts"]``."""
    return int(cfg["expert_share"]["first"]), int(cfg["n_routed_experts"])

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
        b = f"blk{i}."
        p = {"ln1": get(b + "ln1"), "ln2": get(b + "ln2"),
             "router": get(b + "moe.router.w"),
             "bias": get(b + "moe.expert_bias"),
             "gate_up": get(b + "moe.gate_up.w"),
             "down": get(b + "moe.down.w"),
             "shared_gate_up": get(b + "moe.shared_gate_up.w"),
             "shared_down": get(b + "moe.shared_down.w")}
        if kind == "kda":
            p.update(qkv=get(b + "gdn_qkv.w"), conv=get(b + "gdn_conv.w"),
                     wb=get(b + "gdn_b.w"), f_down=get(b + "gdn_f_down.w"),
                     f_up=get(b + "gdn_f_up.w"), a_log=get(b + "gdn_A_log"),
                     dt_bias=get(b + "gdn_dt_bias"),
                     o_norm=get(b + "gdn_norm"),
                     g_down=get(b + "gdn_g_down.w"),
                     g_up=get(b + "gdn_g_up.w"), wo=get(b + "gdn_out.w"))
        else:
            p.update(qkv=get(b + "qkv.w"), gate=get(b + "attn_gate.w"),
                     wo=get(b + "attn_out.w"))
        layers.append(p)
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f"),
            "head": get("head.w")}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def _attention(q, k, v):
    """q [H, n, d] over k, v [Hkv, n, d], causal, query head j reading KV
    head j // (H // Hkv).  In blocks of queries."""
    heads, n, d = q.shape
    rep = heads // k.shape[0]
    q, k, v = _at("product", q), _at("pages", k), _at("pages", v)
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
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
    [n, H, Dv], g [n, H, Dk] (a log decay a key channel), beta [n, H] ->
    o [n, H, Dv].  S_0 = 0."""
    def token(s, x):
        q, k, v, g, beta = x
        s = (1 + jnp.expm1(g))[:, :, None] * s              # Diag(alpha) S
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


def _kda(h, p, cfg, eps):
    dtype = h.dtype
    n = h.shape[0]
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    # (beta and the decay read the normed rows whole: kept products)
    whole, h = h, _at("product", h)
    c = jax.nn.silu(_short_conv(h @ p["qkv"].astype(dtype), p["conv"]))
    q = _l2(c[:, :heads * d].reshape(n, heads, d)) * (d ** -0.5)
    k = _l2(c[:, heads * d:2 * heads * d].reshape(n, heads, d))
    v = c[:, 2 * heads * d:].reshape(n, heads, d)
    beta = jax.nn.sigmoid(whole @ p["wb"].astype(dtype))
    if cfg["kda_allow_neg_eigval"]:
        beta = beta * 2.0
    f = (whole @ p["f_down"].astype(dtype)) @ p["f_up"].astype(dtype)
    g = -jnp.exp(p["a_log"].astype(dtype))[None, :, None] \
        * jax.nn.softplus(f + p["dt_bias"].astype(dtype)) \
        .reshape(n, heads, d)
    o = delta_rule(q, k, v, g, beta)
    gate = (_at("product", h @ p["g_down"].astype(dtype))
            @ p["g_up"].astype(dtype)).reshape(n, heads, d)
    o = _rms_norm(o, p["o_norm"], eps) * jax.nn.sigmoid(gate)
    return _at("product", o.reshape(n, heads * d)) @ p["wo"].astype(dtype)


def _gated_attention(h, p, cfg):
    dtype = h.dtype
    n = h.shape[0]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    h = _at("product", h)
    qkv = h @ p["qkv"].astype(dtype)

    def split(t, m):
        return t.reshape(n, m, d).transpose(1, 0, 2)

    a = _attention(split(qkv[:, :heads * d], heads),
                   split(qkv[:, heads * d:(heads + kv) * d], kv),
                   split(qkv[:, (heads + kv) * d:], kv))
    a = a.transpose(1, 0, 2).reshape(n, heads * d)
    if cfg["use_gqa_gate"]:
        a = a * jax.nn.sigmoid(h @ p["gate"].astype(dtype))
    return _at("product", a) @ p["wo"].astype(dtype)


def _choose(score, top_k, rows, prog_score, margin_share):
    """Each token's experts as a mask [n, E], chosen on ``score`` = ``s +
    b``.  ``prog_score`` [R, E]: the program's ``s + b`` of the compared
    ``rows`` (or None).  Returns the mask and a report ``[deviation,
    least margin, near ties, taken]`` of the compared rows."""
    top, idx = jax.lax.top_k(score, top_k + 1)
    chosen = jax.nn.one_hot(idx[:, :top_k], score.shape[-1],
                            dtype=bool).any(axis=1)
    if prog_score is None:
        return chosen, None
    mine = score[rows]                                       # [R, E]
    span = mine.max(-1) - mine.min(-1)
    margin = top[rows, top_k - 1] - top[rows, top_k]        # 8th - 9th
    limit = margin_share * span
    theirs = jax.nn.one_hot(jax.lax.top_k(prog_score, top_k)[1],
                            score.shape[-1], dtype=bool).any(axis=1)
    # the program's eight are all within the margin of my 8th
    sound = jnp.all(jnp.where(
        theirs, mine >= (top[rows, top_k - 1] - limit)[:, None], True), -1)
    near = margin < limit
    take = near & sound & jnp.any(theirs != chosen[rows], -1)
    report = jnp.stack([
        jnp.max(jnp.abs(prog_score - mine) / span[:, None]),
        jnp.min(margin / span), near.sum().astype(jnp.float32),
        take.sum().astype(jnp.float32)])
    return chosen.at[rows].set(jnp.where(take[:, None], theirs,
                                         chosen[rows])), report


def route(logits, bias, cfg, rows=None, program_logits=None):
    """Sigmoid routing on pre-sigmoid ``logits`` [n, E] over ALL the
    router's experts: the weights [n, E] (zero off the chosen eight,
    normalised over the eight) and the near-tie report."""
    top_k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    b = bias.astype(jnp.float32)
    prog = None if program_logits is None \
        else jax.nn.sigmoid(program_logits.astype(jnp.float32)) + b
    share = cfg["check_tolerance"]["near_tie_margin_share_of_router_range"] \
        if prog is not None else 0.0
    chosen, report = _choose(s + b, top_k, rows, prog, share)
    a = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        a = a / (a.sum(-1, keepdims=True) + 1e-6)
    return a * float(cfg["routed_scaling_factor"]), report


def held_experts(h, weights, gate_up, down, first):
    """sum over the HELD experts e = first .. first + len(gate_up) - 1 of
    a_e W_2,e(silu(W_1,e h) * (W_3,e h)), as a loop over them;
    ``weights`` [n, E_router] is zero where a token did not choose an
    expert."""
    def one(e, acc):
        y = _swiglu(h, jax.lax.dynamic_index_in_dim(gate_up, e, 0, False),
                    jax.lax.dynamic_index_in_dim(down, e, 0, False))
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return acc + w[:, None].astype(h.dtype) * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def ffn(h, p, cfg, held, rows=None, program_logits=None, shared=True):
    """One layer's FFN on normed rows h [n, hidden] for the chip that
    holds experts ``held = (first, count)`` (``p["gate_up"]`` [count, ..]):
    ``(y, router logits [n, E_router], near-tie report)``.  ``shared``
    False leaves the shared expert out (the shares of a layer count it
    once)."""
    dtype = h.dtype
    logits = h @ p["router"].astype(dtype)
    h = _at("product", h)               # (the router read it whole)
    weights, report = route(logits, p["bias"], cfg, rows, program_logits)
    first, count = held
    if p["gate_up"].shape[0] != count:
        raise ValueError(f"{p['gate_up'].shape[0]} expert matrices for a "
                         f"share of {count}")
    y = held_experts(h, weights, p["gate_up"], p["down"], first)
    if shared and cfg["n_shared_experts"]:
        y = y + _swiglu(h, p["shared_gate_up"], p["shared_down"])
    return y, logits, report


def forward(params: dict, token_ids, cfg: dict, rows=None,
            program_router=None, dtype=jnp.float32, keep_router=False,
            held=None):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  With
    ``program_router`` [R, L, E] (the program's pre-sigmoid router logits
    of ``rows``) also the near-tie report ``[L, 4]``; with ``keep_router``
    instead its own pre-sigmoid router logits of ``rows``, [R, L, E].
    ``held``: the experts held (default: the configuration's).  ``dtype``:
    the precision of every activation, product and of the recurrent state
    (float32; the bfloat16 control passes the other)."""
    eps = cfg["rms_norm_eps"]
    held = held_range(cfg) if held is None else held
    ids = jnp.asarray(token_ids, jnp.int32)
    if rows is not None:
        rows = jnp.asarray(rows)
    reports, routers = [], []
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"].astype(dtype)[ids])
        for i, (p, kind) in enumerate(zip(params["layers"],
                                          layer_kinds(cfg))):
            h = _rms_norm(x, p["ln1"], eps)
            x = _at("residual", x + (_kda(h, p, cfg, eps) if kind == "kda"
                                     else _gated_attention(h, p, cfg)))
            y, logits, report = ffn(
                _rms_norm(x, p["ln2"], eps), p, cfg, held, rows,
                None if program_router is None else program_router[:, i])
            x = _at("residual", x + y)
            if keep_router:
                routers.append(logits[rows])
            if report is not None:
                reports.append(report)
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[rows]
        out = x @ params["head"].astype(dtype)
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

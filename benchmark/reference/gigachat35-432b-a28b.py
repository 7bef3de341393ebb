"""Plain reference for ``gigachat35-432b-a28b``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no state
variable, no chunks, no paging, no batching and no kernel, written from
the configuration's own equations (ISSUE 47; the configuration's
``assumed`` list).  ``N(x; w) = x / sqrt(mean(x^2) + 1e-6) * w``, ``w`` the
scale a channel that the zero-centred norm gives (``2 sigmoid(0) = 1 + 0
= 1`` at the seeded weights).  One layer, x [n, 7168], in the sandwich
form (``layernorm_type`` pre_post, four norm vectors):

    x = x + N(mixer(N(x)));  x = x + N(ffn(N(x)))

    latent attention (layers in full_attention_layers), h = N(x), in the
    EXPANDED form only (the program's decode step runs the absorbed form:
    different arithmetic for the same function):
        c_q = N(h W_qa) [1536];  [q_nope | q_rope] = c_q W_qb   (64 x (128 | 64))
        [c_kv | k_r] = h W_kva  (512 | 64);  c_kv = N(c_kv)
        q_rope, k_r rotated at their positions: pairs (2i, 2i + 1), base
            100,000, YaRN's frequency table (``yarn_frequencies``)
        [k_nope | v] = c_kv W_kvb  (64 x (128 | 128))
        a = causal softmax((q_nope . k_nope + q_rope . k_r) * scale) v
            scale = 192^-1/2 * (0.1 ln 8 + 1)^2   (use_mla_scaling_factor)
        y = (a * sigmoid(h W_g)) W_o        (gated_attention: W_g [7168, 8192])
    gated delta rule (the others), h = N(x):
        q | k | v = silu(conv4(h W_qkv))    32 x 128 | 32 x 128 | 64 x 128
        q = q / sqrt(|q|^2 + 1e-6) * 128^-1/2;  k = k / sqrt(|k|^2 + 1e-6)
        key head j serves VALUE heads 2j and 2j + 1; a value head:
          g_t = -exp(A_log) softplus(h W_a + dt_bias),  beta_t = sigmoid(h W_b)
          S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
          o_t = S_t^T q_t                  S in R^{128 x 128}, S_0 = 0
        y = concat_h(N_128(o_h) * 2 sigmoid((h W_z)_h)) W_o
    FFN, h = N(x), SwiGLU_L(h; W) = W_2(silu(min(W_1 h, L)) * clip(W_3 h, -L, L)), L = 10:
        leading dense layers:  SwiGLU_L of 18,432
        the others: s = sigmoid(h W_r) [E_router]; sel = top8(s + b), ties
            to the lower index; w_e = 2.5 s_e / (sum_{sel} s + 1e-6)
            sum_{e in sel, e HELD} w_e SwiGLU_L,e(h) + SwiGLU_L,shared(h)

then the final norm and ``logits = x_norm W_head`` (untied, over the
vocabulary slice).  ``held = (first, count)`` says which experts this chip
holds: the sum runs over the chosen experts that are held, the weights are
normalised over all eight chosen, and what the absent experts would add is
left out (``held = (0, E_router)`` is the uncut layer).  The recurrence is
kept as it stands, token by token under ``lax.scan``, with the decay
carried as ``1 + expm1(g)`` (PERF.md section 6, PR 41: the chip's float32
``exp`` is off by up to 5e-6 relative, and the recurrence compounds it
once a token); the convolution is an explicit sum over four shifted
copies; experts are a plain loop over the held ones; attention runs in
blocks of query rows.

Departures from the published description: none in the equations as this
file's head gives them.  What ``config.json`` has no key for (the norm's
two readings, the sandwich, the gates' forms, the router's score and bias,
the clamp's form) is in the configuration's ``assumed``, a reason each.
The multi-token heads (``num_nextn_predict_layers``) draft tokens and
never change what the model answers: cut with the depth.  The layers run,
the experts held and the vocabulary are the configuration's cut.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them:
``params["dense"]`` are the leading dense layers, ``params["layers"]`` the
expert layers behind them, in order.

Routing is discrete.  Handed the program's PRE-sigmoid router logits of
the compared ``rows`` (``program_router`` [R, L_moe, E]), a compared row
whose own 8th-9th margin of ``s + b`` is under the configuration's
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
    return ["mla" if i in cfg["full_attention_layers"] else "delta"
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

    dense, layers = [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"blk{i}."
        p = {k: get(b + k) for k in ("ln1", "ln1_post", "ln2", "ln2_post")}
        if kind == "delta":
            p.update(qkv=get(b + "gdn_qkv.w"), conv=get(b + "gdn_conv.w"),
                     ab=get(b + "gdn_ab.w"), a_log=get(b + "gdn_A_log"),
                     dt_bias=get(b + "gdn_dt_bias"),
                     o_norm=get(b + "gdn_norm"), z=get(b + "gdn_gate.w"),
                     wo=get(b + "gdn_out.w"))
        else:
            p.update(q_a=get(b + "q_a.w"), q_a_norm=get(b + "q_a_norm"),
                     q_b=get(b + "q_b.w"), kv_a=get(b + "kv_a.w"),
                     kv_a_norm=get(b + "kv_a_norm"), kv_b=get(b + "kv_b.w"),
                     gate=get(b + "attn_gate.w"), wo=get(b + "attn_out.w"))
        if i < cfg["first_k_dense_replace"]:
            p.update(gate_up=get(b + "gate_up.w"), down=get(b + "ffn_out.w"))
            dense.append(p)
        else:
            p.update(router=get(b + "moe.router.w"),
                     bias=get(b + "moe.expert_bias"),
                     gate_up=get(b + "moe.gate_up.w"),
                     down=get(b + "moe.down.w"),
                     shared_gate_up=get(b + "moe.shared_gate_up.w"),
                     shared_down=get(b + "moe.shared_down.w"))
            layers.append(p)
    return {"embed": get("embed"), "dense": dense, "layers": layers,
            "ln_f": get("ln_f"), "head": get("head.w")}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


# ---------------------------------------------------------------------------
# latent attention, the expanded form
# ---------------------------------------------------------------------------

def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The 32 rotation frequencies of the 64 rotary dimensions, written
    out: ``f_i = base^(-2i / 64)``; the correction dimensions of
    ``beta_fast`` and ``beta_slow`` turns over the original 32,768
    positions are ``d(r) = 64 ln(32768 / (2 pi r)) / (2 ln base)``, low =
    floor(d(beta_fast)), high = ceil(d(beta_slow)); ``m_i`` is 1 up to
    low, 0 from high on, linear between; ``f_i <- (1 - m_i) f_i / factor +
    m_i f_i``.  The cos / sin factor is 1 (``mscale`` = ``mscale_all_dim``)."""
    rs = cfg["rope_scaling"]
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):
        return d * np.log(rs["original_max_position_embeddings"]
                          / (turns * 2 * np.pi)) / (2 * np.log(base))

    low = max(np.floor(dim_of(rs["beta_fast"])), 0)
    high = min(np.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    m = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return (1 - m) * f / rs["factor"] + m * f


def _rotate_pairs(x, cos, sin):
    """x [n, ..., d] at positions 0 .. n - 1: pair (2i, 2i + 1) turned by
    the angle ``pos * f_i``; cos, sin [n, d / 2]."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    cos = cos.reshape((shape[0],) + (1,) * (len(shape) - 2) + cos.shape[1:])
    sin = sin.reshape(cos.shape)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(shape)


def mla_scale(cfg: dict) -> float:
    scale = float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if cfg["use_mla_scaling_factor"]:
        scale *= (0.1 * np.log(cfg["rope_scaling"]["factor"]) + 1.0) ** 2
    return float(scale)


def _attention(q, k, v, scale):
    """q, k [H, n, dk] and v [H, n, dv], causal, in blocks of queries."""
    n = q.shape[1]
    # (the expanded K and V are products' results, not page rows)
    q, k, v = _at("product", q), _at("product", k), _at("product", v)
    j = jnp.arange(n)[None, :]
    out = []
    for start in range(0, n, Q_BLOCK):
        i = jnp.arange(start, min(start + Q_BLOCK, n))[:, None]
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + Q_BLOCK], k) \
            * scale                           # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), -1))
        out.append(jnp.einsum("hqk,hkd->hqd", p, v))
    return jnp.concatenate(out, axis=1)


def _mla(h, p, cfg, eps):
    dtype = h.dtype
    n = h.shape[0]
    heads, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    # (float32 angles, as the family's modelling code takes them)
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(cfg), jnp.float32)[None, :]
    cos, sin = jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)
    h = _at("product", h)
    c_q = _at("product", _norm(h @ p["q_a"].astype(dtype), p["q_a_norm"],
                               eps))
    q = (c_q @ p["q_b"].astype(dtype)).reshape(n, heads, dn + dr)
    kv_a = h @ p["kv_a"].astype(dtype)
    # the latent row as a program writes it to its pages: [c_kv | k_r]
    c_kv = _at("pages", _norm(kv_a[:, :c], p["kv_a_norm"], eps))
    k_r = _at("pages", _rotate_pairs(kv_a[:, c:], cos, sin))  # [n, dr]
    q = jnp.concatenate([q[..., :dn], _rotate_pairs(q[..., dn:], cos, sin)],
                        axis=-1)
    kv = (c_kv @ p["kv_b"].astype(dtype)).reshape(n, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (n, heads, dr))],
        axis=-1)
    a = _attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                   kv[..., dn:].transpose(1, 0, 2), mla_scale(cfg))
    a = a.transpose(1, 0, 2).reshape(n, heads * dv)
    if cfg["gated_attention"]:
        a = a * jax.nn.sigmoid(h @ p["gate"].astype(dtype))
    return _at("product", a) @ p["wo"].astype(dtype)


# ---------------------------------------------------------------------------
# the gated delta rule, two value heads a key head
# ---------------------------------------------------------------------------

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
    [n, H, Dv], g (a log decay a head) and beta [n, H] -> o [n, H, Dv].
    S_0 = 0."""
    def token(s, x):
        q, k, v, g, beta = x
        s = (1 + jnp.expm1(g))[:, None, None] * s
        r = v - jnp.einsum("hkv,hk->hv", s, k)
        s = s + k[:, :, None] * (beta[:, None] * r)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


def _delta(h, p, cfg, eps):
    dtype = h.dtype
    n = h.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    # (the decay and beta read the normed rows whole: kept products)
    whole, h = h, _at("product", h)
    c = jax.nn.silu(_short_conv(h @ p["qkv"].astype(dtype), p["conv"]))
    q = _l2(c[:, :hk * dk].reshape(n, hk, dk)) * (dk ** -0.5)
    k = _l2(c[:, hk * dk:2 * hk * dk].reshape(n, hk, dk))
    v = c[:, 2 * hk * dk:].reshape(n, hv, dv)
    # key head j serves value heads (hv / hk) j ..
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    ab = whole @ p["ab"].astype(dtype)
    g = -jnp.exp(p["a_log"].astype(dtype)) \
        * jax.nn.softplus(ab[:, :hv] + p["dt_bias"].astype(dtype))
    beta = jax.nn.sigmoid(ab[:, hv:])
    o = delta_rule(q, k, v, g, beta)
    gate = (h @ p["z"].astype(dtype)).reshape(n, hv, dv)
    o = _norm(o, p["o_norm"], cfg["linear_attn_o_norm_eps"]) \
        * (float(cfg["linear_sigmoid_gate_scale"]) * jax.nn.sigmoid(gate))
    return _at("product", o.reshape(n, hv * dv)) @ p["wo"].astype(dtype)


# ---------------------------------------------------------------------------
# FFN: the clamped SwiGLU, dense or routed over a held share
# ---------------------------------------------------------------------------

def _swiglu(h, gate_up, down, limit):
    inter = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    gate, up = gu[:, :inter], gu[:, inter:]
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _at("product", jax.nn.silu(gate) * up) @ down.astype(h.dtype)


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
    normalised over the eight, times ``routed_scaling_factor``) and the
    near-tie report."""
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


def held_experts(h, weights, gate_up, down, first, limit):
    """sum over the HELD experts e = first .. first + len(gate_up) - 1 of
    w_e SwiGLU_L,e(h), as a loop over them; ``weights`` [n, E_router] is
    zero where a token did not choose an expert."""
    def one(e, acc):
        y = _swiglu(h, jax.lax.dynamic_index_in_dim(gate_up, e, 0, False),
                    jax.lax.dynamic_index_in_dim(down, e, 0, False), limit)
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return acc + w[:, None].astype(h.dtype) * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def ffn(h, p, cfg, held, rows=None, program_logits=None, shared=True):
    """One expert layer's FFN on normed rows h [n, hidden] for the chip
    that holds experts ``held = (first, count)`` (``p["gate_up"]`` [count,
    ..]): ``(y, router logits [n, E_router], near-tie report)``.
    ``shared`` False leaves the shared expert out (the shares of a layer
    count it once)."""
    dtype = h.dtype
    limit = cfg["swiglu_limit"]
    logits = h @ p["router"].astype(dtype)
    h = _at("product", h)               # (the router read it whole)
    weights, report = route(logits, p["bias"], cfg, rows, program_logits)
    first, count = held
    if p["gate_up"].shape[0] != count:
        raise ValueError(f"{p['gate_up'].shape[0]} expert matrices for a "
                         f"share of {count}")
    y = held_experts(h, weights, p["gate_up"], p["down"], first, limit)
    if shared and cfg["n_shared_experts"]:
        y = y + _swiglu(h, p["shared_gate_up"], p["shared_down"], limit)
    return y, logits, report


def forward(params: dict, token_ids, cfg: dict, rows=None,
            program_router=None, dtype=jnp.float32, keep_router=False,
            held=None):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  With
    ``program_router`` [R, L_moe, E] (the program's pre-sigmoid router
    logits of ``rows``, one entry an EXPERT layer) also the near-tie
    report ``[L_moe, 4]``; with ``keep_router`` instead its own pre-sigmoid
    router logits of ``rows``, [R, L_moe, E].  ``held``: the experts held
    (default: the configuration's).  ``dtype``: the precision of every
    activation, product and of the recurrent state (float32; the bfloat16
    control passes the other)."""
    eps = cfg["rms_norm_eps"]
    held = held_range(cfg) if held is None else held
    ids = jnp.asarray(token_ids, jnp.int32)
    if rows is not None:
        rows = jnp.asarray(rows)
    reports, routers = [], []
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"].astype(dtype)[ids])
        moe_at = -len(params["dense"])
        for p in params["dense"] + params["layers"]:
            h = _norm(x, p["ln1"], eps)
            y = _mla(h, p, cfg, eps) if "kv_a" in p \
                else _delta(h, p, cfg, eps)
            x = _at("residual", x + _norm(y, p["ln1_post"], eps))
            h = _norm(x, p["ln2"], eps)
            if moe_at < 0:
                y = _swiglu(_at("product", h), p["gate_up"], p["down"],
                            cfg["swiglu_limit"])
            else:
                y, logits, report = ffn(
                    h, p, cfg, held, rows,
                    None if program_router is None
                    else program_router[:, moe_at])
                if keep_router:
                    routers.append(logits[rows])
                if report is not None:
                    reports.append(report)
            moe_at += 1
            x = _at("residual", x + _norm(y, p["ln2_post"], eps))
        x = _at("product", _norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[rows]
        out = x @ params["head"].astype(dtype)
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

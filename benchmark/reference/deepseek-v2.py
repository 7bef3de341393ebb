"""Plain reference for ``deepseek-v2``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no pages, no
chunks, no batching and no kernel: the whole prompt in one forward,
written from the configuration's own equations (ISSUE 56; the
configuration's ``assumed`` list).  ``N(x; w) = x / sqrt(mean(x^2) + 1e-6)
* w``.  One layer, x [n, 5120], pre-norm:

    x = x + MLA(N(x));  x = x + FFN(N(x))

    latent attention (every layer), h = N(x), in the EXPANDED form only
    (the program's decode step runs the absorbed form, its chunks expand
    cached rows block by block: different arithmetic for the same
    function):
        c_q = N(h W_qa) [1536];  [q_nope | q_rope] = c_q W_qb   (128 x (128 | 64))
        [c_kv | k_r] = h W_kva  (512 | 64);  c_kv = N(c_kv)
        q_rope, k_r rotated at their positions: pairs (2i, 2i + 1), base
            10,000, YaRN's frequency table (``yarn_frequencies``); cos and
            sin carry mscale / mscale_all_dim = 1
        [k_nope | v] = c_kv W_kvb  (128 x (128 | 128))
        a = causal softmax((q_nope . k_nope + q_rope . k_r) * scale) v
            scale = 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2 = 0.11472
        y = a W_o                          [16384 -> 5120], no bias, no gate
    FFN, h = N(x), SwiGLU(h; W) = W_2(silu(W_1 h) * (W_3 h)):
        leading dense layers:  SwiGLU of 12,288
        the others: s = softmax(h W_r) [E_router = 160]; the experts lie in
            8 groups of 20 consecutive indices; a group's score is the MAX
            of its experts' s; the 3 best groups are kept (ties to the
            lower index), s elsewhere set to 0; sel = the 6 largest of what
            is left (ties to the lower index); w_e = 16 s_e (not
            renormalised: norm_topk_prob false)
            sum_{e in sel, e HELD} w_e SwiGLU_e(h)   experts of 1536
              + S_1(h) + S_2(h)                      two shared experts of
                                                     1536, computed ONE BY
                                                     ONE and summed

then the final norm and ``logits = x_norm W_head`` (untied, over the
vocabulary slice).  ``held = (first, count)`` says which experts this chip
holds: the sum runs over the chosen experts that are held, and what the
absent experts would add is left out (``held = (0, 160)`` is the uncut
layer).

So that a 9,000-token prompt fits beside 12.6 GB of weights (128 heads'
keys and values of 9,000 rows are 1.5 GB a layer, a row block's scores
over all heads 2.4 GB), the rows go through a layer in blocks of
``ROW_BLOCK`` (``lax.map``) and a block's attention is formed
``HEAD_GROUP`` heads at a time: the group's keys and values are expanded
from the latent for the whole sequence, the block's scores are [16,
ROW_BLOCK, n].  Nothing else is blocked: a block's attention sees every
earlier row's latent, which is made for the whole sequence first.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them:
``params["dense"]`` are the leading dense layers, ``params["layers"]`` the
expert layers behind them, in order.  The program keeps the two shared
experts as one fused SwiGLU of width 3072 (gate | up columns and down rows
of expert j at ``j * 1536``); the reference takes the two apart.

Routing is discrete, twice: groups, then experts.  Handed the program's
router logits of the compared ``rows`` (``program_router`` [R, L_moe, E]),
a compared row whose 3rd-4th margin of GROUP scores or whose 6th-7th
margin of kept scores is under the configuration's
``near_tie_margin_share_of_router_range`` of the row's range of ``s``
takes the program's six experts, if each of their groups is within that
margin of the reference's 3rd group and each of them within it of the
reference's 6th; ``forward`` then also returns what it saw, layer by
layer.  Without them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512
HEAD_GROUP = 16


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
    for i in range(cfg["num_hidden_layers"]):
        b = f"blk{i}."
        p = {"ln1": get(b + "ln1"), "ln2": get(b + "ln2"),
             "q_a": get(b + "q_a.w"), "q_a_norm": get(b + "q_a_norm"),
             "q_b": get(b + "q_b.w"), "kv_a": get(b + "kv_a.w"),
             "kv_a_norm": get(b + "kv_a_norm"), "kv_b": get(b + "kv_b.w"),
             "wo": get(b + "attn_out.w")}
        if i < cfg["first_k_dense_replace"]:
            p.update(gate_up=get(b + "gate_up.w"), down=get(b + "ffn_out.w"))
            dense.append(p)
        else:
            p.update(router=get(b + "moe.router.w"),
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
    ``beta_fast`` and ``beta_slow`` turns over the original 4,096
    positions are ``d(r) = 64 ln(4096 / (2 pi r)) / (2 ln base)``, low =
    floor(d(beta_fast)), high = ceil(d(beta_slow)); ``m_i`` is 1 up to
    low, 0 from high on, linear between; ``f_i <- (1 - m_i) f_i / factor +
    m_i f_i``."""
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


def yarn_attention_factor(factor, mscale) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def mla_scale(cfg: dict) -> float:
    """``(nope + rope)^-1/2`` times the attention factor at
    ``mscale_all_dim``, squared: 0.11472 as published."""
    rs = cfg["rope_scaling"]
    return float((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
                 * yarn_attention_factor(rs["factor"],
                                         rs["mscale_all_dim"]) ** 2)


def _rotate_pairs(x, cos, sin):
    """x [n, ..., d] at the positions of cos, sin [n, d / 2]: pair (2i,
    2i + 1) turned by the angle ``pos * f_i``."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    cos = cos.reshape((shape[0],) + (1,) * (len(shape) - 2) + cos.shape[1:])
    sin = sin.reshape(cos.shape)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(shape)


def _tables(cfg, first, n, dtype):
    """cos, sin [n, 32] at positions ``first ..``, times mscale /
    mscale_all_dim (1 as published); float32 angles, as the family's
    modelling code takes them, the tables in the activations' precision."""
    rs = cfg["rope_scaling"]
    factor = yarn_attention_factor(rs["factor"], rs["mscale"]) \
        / yarn_attention_factor(rs["factor"], rs["mscale_all_dim"])
    angles = (first + jnp.arange(n, dtype=jnp.float32))[:, None] \
        * jnp.asarray(yarn_frequencies(cfg), jnp.float32)[None, :]
    return (jnp.cos(angles) * factor).astype(dtype), \
        (jnp.sin(angles) * factor).astype(dtype)


def attention_rows(q, c_kv, k_r, w_kvb, first, cfg):
    """q [r, H, nope + rope] (rotated), rows ``first .. first + r - 1``,
    over the latent ``c_kv`` [n, 512] and the rotated shared key ``k_r``
    [n, 64] of the whole sequence, causal; ``HEAD_GROUP`` heads at a time,
    their keys and values expanded through ``w_kvb``.  Returns [r, H * v]."""
    heads = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    r, n = q.shape[0], c_kv.shape[0]
    group = min(HEAD_GROUP, heads)
    keep = jnp.arange(n)[None, :] <= first + jnp.arange(r)[:, None]
    scale = mla_scale(cfg)
    w = w_kvb.astype(q.dtype).reshape(c_kv.shape[1], heads, dn + dv)

    def heads_of(g):
        wg = jax.lax.dynamic_slice_in_dim(w, g * group, group, 1)
        kv = _at("product",
                 jnp.einsum("nc,chd->hnd", c_kv, wg))     # [g, n, dn + dv]
        qg = _at("product",
                 jax.lax.dynamic_slice_in_dim(q, g * group, group, 1))
        s = (jnp.einsum("qhd,hkd->hqk", qg[..., :dn], kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", qg[..., dn:], k_r)) \
            * scale                          # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1))
        return jnp.einsum("hqk,hkd->hqd", p, kv[..., dn:])

    out = jax.lax.map(heads_of, jnp.arange(heads // group))
    return out.reshape(heads, r, dv).transpose(1, 0, 2).reshape(r, heads * dv)


# ---------------------------------------------------------------------------
# FFN: SwiGLU, dense or routed over a held share
# ---------------------------------------------------------------------------

def swiglu(h, gate_up, down):
    """W_2(silu(W_1 h) * (W_3 h)) with gate | up side by side."""
    width = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    return _at("product", jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
        @ down.astype(h.dtype)


def shared_sum(h, gate_up, down, n_shared):
    """The SUM of ``n_shared`` shared experts, each computed on its own:
    expert j's gate and up columns and down rows lie at ``j * width`` of
    the fused matrices."""
    width = down.shape[0] // n_shared
    total = gate_up.shape[1] // 2
    acc = jnp.zeros_like(h)
    for j in range(n_shared):
        cols = slice(j * width, (j + 1) * width)
        gu = jnp.concatenate([gate_up[:, cols],
                              gate_up[:, total:][:, cols]], axis=1)
        acc = acc + swiglu(h, gu, down[cols])
    return acc


def grouped_choice(s, cfg):
    """Group-limited greedy selection on scores ``s`` [n, E]: ``(chosen
    mask [n, E], group scores [n, G], kept-group mask [n, G], kept scores
    [n, E])``; ties to the lower index (``lax.top_k``)."""
    n, e = s.shape
    groups, keep_n = cfg["n_group"], cfg["topk_group"]
    gs = s.reshape(n, groups, e // groups).max(-1)
    kept = jax.nn.one_hot(jax.lax.top_k(gs, keep_n)[1], groups,
                          dtype=bool).any(axis=1)
    left = jnp.where(jnp.repeat(kept, e // groups, axis=1), s, 0.0)
    chosen = jax.nn.one_hot(
        jax.lax.top_k(left, cfg["num_experts_per_tok"])[1], e,
        dtype=bool).any(axis=1)
    return chosen, gs, kept, left


def _choose(s, cfg, rows, prog_s, margin_share):
    """Each token's experts as a mask [n, E].  ``prog_s`` [R, E]: the
    program's scores of the compared ``rows`` (or None).  Returns the mask
    and a report ``[deviation, least margin, near ties, taken]`` of the
    compared rows; a margin is the smaller of the row's 3rd-4th group
    margin and its 6th-7th margin of kept scores."""
    top_k, keep_n = cfg["num_experts_per_tok"], cfg["topk_group"]
    chosen, gs, kept, left = grouped_choice(s, cfg)
    if prog_s is None:
        return chosen, None
    mine, gs, left = s[rows], gs[rows], left[rows]
    span = mine.max(-1) - mine.min(-1)
    g_top = jax.lax.top_k(gs, keep_n + 1)[0]
    e_top = jax.lax.top_k(left, top_k + 1)[0]
    margin = jnp.minimum(g_top[:, keep_n - 1] - g_top[:, keep_n],
                         e_top[:, top_k - 1] - e_top[:, top_k])
    limit = margin_share * span
    theirs, _, their_groups, _ = grouped_choice(prog_s, cfg)
    # the program's groups are within the margin of my 3rd, its six
    # experts within it of my 6th
    sound = jnp.all(jnp.where(
        their_groups, gs >= (g_top[:, keep_n - 1] - limit)[:, None],
        True), -1) & jnp.all(jnp.where(
            theirs, mine >= (e_top[:, top_k - 1] - limit)[:, None],
            True), -1)
    near = margin < limit
    take = near & sound & jnp.any(theirs != chosen[rows], -1)
    report = jnp.stack([
        jnp.max(jnp.abs(prog_s - mine) / span[:, None]),
        jnp.min(margin / span), near.sum().astype(jnp.float32),
        take.sum().astype(jnp.float32)])
    return chosen.at[rows].set(jnp.where(take[:, None], theirs,
                                         chosen[rows])), report


def route(logits, cfg, rows=None, program_logits=None, grouped=True):
    """Softmax routing on ``logits`` [n, E] over ALL the router's experts:
    the weights [n, E] (zero off the chosen six, their softmax scores as
    they are, times ``routed_scaling_factor``) and the near-tie report.
    ``grouped`` False leaves the group step out (the 6 largest of all 160:
    a planted fault's reading, not the model's)."""
    if not grouped:
        cfg = dict(cfg, n_group=1, topk_group=1)
    s = jax.nn.softmax(logits.astype(jnp.float32), -1)
    prog = None if program_logits is None \
        else jax.nn.softmax(program_logits.astype(jnp.float32), -1)
    share = cfg["check_tolerance"]["near_tie_margin_share_of_router_range"] \
        if prog is not None else 0.0
    chosen, report = _choose(s, cfg, rows, prog, share)
    a = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        a = a / (a.sum(-1, keepdims=True) + 1e-20)
    return a * float(cfg["routed_scaling_factor"]), report


def held_experts(h, weights, gate_up, down, first):
    """sum over the HELD experts e = first .. first + len(gate_up) - 1 of
    w_e SwiGLU_e(h), as a loop over them; ``weights`` [n, E_router] is
    zero where a token did not choose an expert."""
    def one(e, acc):
        y = swiglu(h, jax.lax.dynamic_index_in_dim(gate_up, e, 0, False),
                   jax.lax.dynamic_index_in_dim(down, e, 0, False))
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return acc + w[:, None].astype(h.dtype) * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def ffn(h, p, cfg, held, rows=None, program_logits=None, shared=True,
        weights=None, grouped=True):
    """One expert layer's FFN on normed rows h [n, hidden] for the chip
    that holds experts ``held = (first, count)`` (``p["gate_up"]`` [count,
    ..]): ``(y, router logits [n, E_router], near-tie report)``.
    ``shared`` False leaves the shared experts out (the shares of a layer
    count them once).  ``weights``: the routing already done (a block of
    rows)."""
    logits = report = None
    if weights is None:
        logits = h @ p["router"].astype(h.dtype)
        weights, report = route(logits, cfg, rows, program_logits, grouped)
        h = _at("product", h)           # (the router read it whole)
    first, count = held
    if p["gate_up"].shape[0] != count:
        raise ValueError(f"{p['gate_up'].shape[0]} expert matrices for a "
                         f"share of {count}")
    y = held_experts(h, weights, p["gate_up"], p["down"], first)
    if shared and cfg["n_shared_experts"]:
        y = y + shared_sum(h, p["shared_gate_up"], p["shared_down"],
                           int(cfg["n_shared_experts"]))
    return y, logits, report


def forward(params: dict, token_ids, cfg: dict, rows=None,
            program_router=None, dtype=jnp.float32, keep_router=False,
            held=None, grouped=True):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  With
    ``program_router`` [R, L_moe, E] (the program's router logits of
    ``rows``, one entry an EXPERT layer) also the near-tie report ``[L_moe,
    4]``; with ``keep_router`` instead its own router logits of ``rows``,
    [R, L_moe, E].  ``held``: the experts held (default: the
    configuration's).  ``dtype``: the precision of every activation and
    product (float32; the bfloat16 control passes the other)."""
    eps = cfg["rms_norm_eps"]
    held = held_range(cfg) if held is None else held
    heads, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    ids = jnp.asarray(token_ids, jnp.int32)
    n = ids.shape[0]
    block = min(ROW_BLOCK, n)
    pad = -n % block
    if rows is not None:
        rows = jnp.asarray(rows)
    reports, routers = [], []
    with jax.default_matmul_precision("highest"):
        x = _at("residual",
                params["embed"].astype(dtype)[jnp.pad(ids, (0, pad))])
        cos, sin = _tables(cfg, 0, n + pad, dtype)
        moe_at = -len(params["dense"])
        for p in params["dense"] + params["layers"]:
            h = _at("product", _norm(x, p["ln1"], eps))
            kv_a = h @ p["kv_a"].astype(dtype)
            # the latent row as a program writes it to its pages
            c_kv = _at("pages", _norm(kv_a[:, :c], p["kv_a_norm"], eps))
            k_r = _at("pages",
                      _rotate_pairs(kv_a[:, c:], cos, sin))  # [n, dr]

            def attend(b, h=h, p=p, c_kv=c_kv, k_r=k_r):
                first = b * block
                hb = jax.lax.dynamic_slice_in_dim(h, first, block, 0)
                c_q = _at("product", _norm(hb @ p["q_a"].astype(dtype),
                                           p["q_a_norm"], eps))
                q = (c_q @ p["q_b"].astype(dtype)).reshape(
                    block, heads, dn + dr)
                cb, sb = (jax.lax.dynamic_slice_in_dim(t, first, block, 0)
                          for t in (cos, sin))
                q = jnp.concatenate(
                    [q[..., :dn], _rotate_pairs(q[..., dn:], cb, sb)], -1)
                a = attention_rows(q, c_kv, k_r, p["kv_b"], first, cfg)
                return _at("product", a) @ p["wo"].astype(dtype)

            y = jax.lax.map(attend, jnp.arange((n + pad) // block))
            x = _at("residual", x + y.reshape(n + pad, -1))
            h = _norm(x, p["ln2"], eps)
            if moe_at < 0:
                def dense(b, h=_at("product", h), p=p):
                    hb = jax.lax.dynamic_slice_in_dim(h, b * block, block, 0)
                    return swiglu(hb, p["gate_up"], p["down"])

                y = jax.lax.map(dense, jnp.arange((n + pad) // block))
            else:
                logits = h @ p["router"].astype(dtype)
                weights, report = route(
                    logits, cfg, rows,
                    None if program_router is None
                    else program_router[:, moe_at], grouped)

                def experts(b, h=_at("product", h), p=p, weights=weights):
                    first = b * block
                    hb = jax.lax.dynamic_slice_in_dim(h, first, block, 0)
                    return ffn(hb, p, cfg, held, weights=jax.lax.
                               dynamic_slice_in_dim(weights, first, block,
                                                    0))[0]

                y = jax.lax.map(experts, jnp.arange((n + pad) // block))
                if keep_router:
                    routers.append(logits[rows])
                if report is not None:
                    reports.append(report)
            moe_at += 1
            x = _at("residual", x + y.reshape(n + pad, -1))
        x = _at("product", _norm(x, params["ln_f"], eps)[:n])
        if rows is not None:
            x = x[rows]
        out = x @ params["head"].astype(dtype)
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

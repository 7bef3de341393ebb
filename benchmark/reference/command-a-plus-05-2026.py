"""Plain reference for ``command-a-plus-05-2026``: the forward pass in
float32 ``jax.numpy`` at "highest" matmul precision, with no cache, no
pages, no chunks, no batching and no kernel: the whole prompt in one
forward, written from the configuration's own equations (ISSUE 51; the
configuration's ``assumed`` list).  The only norm is a LayerNorm with a
learned weight and NO bias, eps 1e-5.  One layer, x [n, 4096]:

    h = (x - mean(x)) / sqrt(var(x) + 1e-5) * w_ln           ONE norm a layer
    attention:
        q, k, v = h W_q, h W_k, h W_v      (128 / 8 / 8 heads of 128, no bias, no QK-norm)
        sliding layers: q, k rotated, pairs (2i, 2i + 1) of all 128 dims
                        (rope_gptj), base 50,000; token i attends j with
                        i - 4096 < j <= i
        full layers:    NO positional encoding; token i attends every j <= i
        a = softmax(q k^T / sqrt(128)) v   (query head g reads KV head g // 16)
        A = a W_o                          [16384 -> 4096]
    FFN, on the SAME h:
        s = sigmoid(h W_r)  [128];  sel = top8(s), ties to the lower index,
                                    no selection bias
        w_e = s_e / (sum_{sel} s + 1e-6)
        F = sum_{e in sel, e HELD} w_e E_e(h) + (1/4) sum_{j<4} S_j(h)
        E(h) = W_2(silu(W_1 h) * (W_3 h)), width 4096, no bias; S_j four
        shared experts of the same form, computed ONE BY ONE and averaged
    x = x + A + F                          the parallel block: both halves
                                           read h, both are added to raw x

then the final LayerNorm and ``logits = logit_scale * x_norm W_E^T`` over
the embedding table itself (tied; over the vocabulary slice).  ``held =
(first, count)`` says which experts this chip holds: the sum runs over
the chosen experts that are held, the weights are normalised over all
eight chosen, and what the absent experts would add is left out (``held =
(0, 128)`` is the uncut layer).

Departures from the published description: the vision tower is not in
``config.json`` and is not run (prompt rows are token ids);
``first_k_dense_replace`` is 0, so the prefix-dense keys are read by no
layer.  What ``config.json`` has no key for is listed in the
configuration's ``assumed``.  The layers run, the experts held and the
vocabulary are the configuration's cut.

So that a 9,000-token prompt fits beside 12.5 GB of weights, the rows go
through a layer in blocks of ``ROW_BLOCK`` (``lax.map``) and a block's
scores are formed one KV head's group of 16 query heads at a time: [16,
ROW_BLOCK, n].  Nothing else is blocked: a block's attention sees every
earlier row's K and V, which are made for the whole sequence first.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them.
The program keeps the four shared experts as one fused SwiGLU of width
16384 (gate | up columns and down rows of expert j at ``j * 4096``) and
multiplies its output by 0.25; the reference takes the four apart.

Routing is discrete.  Handed the program's PRE-sigmoid router logits of
the compared ``rows`` (``program_router`` [R, L, E]), a compared row whose
own 8th-9th margin of ``s`` is under the configuration's
``near_tie_margin_share_of_router_range`` of the row's range of ``s``
takes the program's eight experts, if each of them is within that margin
of the reference's 8th; ``forward`` then also returns what it saw, layer
by layer.  Without them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512


def held_range(cfg: dict) -> tuple:
    """``(first, count)`` of the experts this chip holds, of the router's
    ``cfg["expert_share"]["router_experts"]``."""
    return int(cfg["expert_share"]["first"]), int(cfg["num_experts"])

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
    for i in range(cfg["num_hidden_layers"]):
        b = f"blk{i}."
        layers.append({
            "ln": get(b + "ln1"), "qkv": get(b + "qkv.w"),
            "wo": get(b + "attn_out.w"), "router": get(b + "moe.router.w"),
            "gate_up": get(b + "moe.gate_up.w"),
            "down": get(b + "moe.down.w"),
            "shared_gate_up": get(b + "moe.shared_gate_up.w"),
            "shared_down": get(b + "moe.shared_down.w")})
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f")}


def layer_norm(x, w, eps):
    """``(x - mean) / sqrt(var + eps) * w``: no bias."""
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(x.dtype)


def rope_interleaved(x, theta, first=0):
    """x: [heads, n, d] at positions ``first ..``.  Pairs (x[2i], x[2i +
    1]) rotated where they lie (rope_gptj), all ``d`` dimensions."""
    n, d = x.shape[1], x.shape[2]
    inv_freq = 1.0 / (theta ** (np.arange(0, d // 2) / (d // 2)))
    ang = (first + jnp.arange(n, dtype=jnp.float32))[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    # the tables in x's own precision, so that a lower one stays lower
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention_rows(q, k, v, first, window):
    """q [H, r, d], rows ``first .. first + r - 1``, over k, v [Hkv, n, d],
    causal, the window as a mask: keys j with i - window < j <= i.  Query
    head g reads KV head g // (H // Hkv); one KV head's group at a time."""
    heads, r, d = q.shape
    kv_heads, n, _ = k.shape
    q = _at("product", q)
    rep = heads // kv_heads
    i = first + jnp.arange(r)[:, None]
    j = jnp.arange(n)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (j > i - window)

    def group(g):
        qg = jax.lax.dynamic_slice_in_dim(q, g * rep, rep, 0)
        kg = jax.lax.dynamic_index_in_dim(k, g, 0, False)
        vg = jax.lax.dynamic_index_in_dim(v, g, 0, False)
        s = jnp.einsum("hqd,kd->hqk", qg, kg) \
            / float(np.sqrt(d))              # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1))
        return jnp.einsum("hqk,kd->hqd", p, vg)

    out = jax.lax.map(group, jnp.arange(kv_heads))     # [Hkv, rep, r, d]
    return out.reshape(heads, r, d)


def swiglu(h, gate_up, down):
    """W_2(silu(W_1 h) * (W_3 h)) with gate | up side by side."""
    width = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    return _at("product", jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
        @ down.astype(h.dtype)


def shared_mean(h, gate_up, down, n_shared):
    """The mean of ``n_shared`` shared experts, each computed on its own:
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
    return acc / float(n_shared)


def _choose(score, top_k, rows, prog_score, margin_share):
    """Each token's experts as a mask [n, E], chosen on ``score``; ties go
    to the lower index (``lax.top_k``).  ``prog_score`` [R, E]: the
    program's scores of the compared ``rows`` (or None).  Returns the mask
    and a report ``[deviation, least margin, near ties, taken]``."""
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


def route(logits, cfg, rows=None, program_logits=None):
    """Sigmoid routing on pre-sigmoid ``logits`` [n, E] over ALL the
    router's experts, no selection bias: the weights [n, E] (zero off the
    chosen eight, normalised over the eight) and the near-tie report."""
    top_k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    prog = None if program_logits is None \
        else jax.nn.sigmoid(program_logits.astype(jnp.float32))
    share = cfg["check_tolerance"]["near_tie_margin_share_of_router_range"] \
        if prog is not None else 0.0
    chosen, report = _choose(s, top_k, rows, prog, share)
    a = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        a = a / (a.sum(-1, keepdims=True) + 1e-6)
    return a, report


def held_experts(h, weights, gate_up, down, first):
    """sum over the HELD experts e = first .. first + len(gate_up) - 1 of
    w_e E_e(h), as a loop over them; ``weights`` [n, E_router] is zero
    where a token did not choose an expert."""
    def one(e, acc):
        y = swiglu(h, jax.lax.dynamic_index_in_dim(gate_up, e, 0, False),
                   jax.lax.dynamic_index_in_dim(down, e, 0, False))
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return acc + w[:, None].astype(h.dtype) * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def ffn(h, p, cfg, held, rows=None, program_logits=None, shared=True,
        weights=None):
    """One layer's FFN on normed rows h [n, hidden] for the chip that holds
    experts ``held = (first, count)`` (``p["gate_up"]`` [count, ..]):
    ``(y, router logits [n, E_router], near-tie report)``.  ``shared``
    False leaves the shared mean out (the shares of a layer count it
    once).  ``weights``: the routing already done (a block of rows)."""
    logits = report = None
    if weights is None:
        logits = h @ p["router"].astype(h.dtype)
        weights, report = route(logits, cfg, rows, program_logits)
        h = _at("product", h)           # (the router read it whole)
    first, count = held
    if p["gate_up"].shape[0] != count:
        raise ValueError(f"{p['gate_up'].shape[0]} expert matrices for a "
                         f"share of {count}")
    y = held_experts(h, weights, p["gate_up"], p["down"], first)
    if shared and cfg["num_shared_experts"]:
        y = y + shared_mean(h, p["shared_gate_up"], p["shared_down"],
                            int(cfg["num_shared_experts"]))
    return y, logits, report


def forward(params: dict, token_ids, cfg: dict, rows=None,
            program_router=None, dtype=jnp.float32, keep_router=False,
            held=None):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  With
    ``program_router`` [R, L, E] (the program's pre-sigmoid router logits
    of ``rows``) also the near-tie report ``[L, 4]``; with ``keep_router``
    instead its own pre-sigmoid router logits of ``rows``, [R, L, E].
    ``held``: the experts held (default: the configuration's).  ``dtype``:
    the precision of every activation and product (float32; the bfloat16
    control passes the other)."""
    eps = cfg["layer_norm_eps"]
    held = held_range(cfg) if held is None else held
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
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
        for i, p in enumerate(params["layers"]):
            sliding = cfg["layer_types"][i] == "sliding_attention"
            window = int(cfg["sliding_window"]) if sliding else None
            h = layer_norm(x, p["ln"], eps)
            logits = h @ p["router"].astype(dtype)
            h = _at("product", h)       # (the router read it whole)
            wq = heads * d
            kv = (h @ p["qkv"][:, wq:].astype(dtype)).reshape(
                n + pad, 2 * kv_heads, d).transpose(1, 0, 2)
            k, v = kv[:kv_heads], kv[kv_heads:]
            if sliding:
                k = rope_interleaved(k, float(cfg["rope_theta"]))
            k, v = _at("pages", k), _at("pages", v)
            weights, report = route(
                logits, cfg, rows,
                None if program_router is None else program_router[:, i])

            def rows_of(b, h=h, k=k, v=v, p=p, weights=weights,
                        sliding=sliding, window=window):
                first = b * block
                hb = jax.lax.dynamic_slice_in_dim(h, first, block, 0)
                q = (hb @ p["qkv"][:, :wq].astype(dtype)).reshape(
                    block, heads, d).transpose(1, 0, 2)
                if sliding:
                    q = rope_interleaved(q, float(cfg["rope_theta"]), first)
                a = attention_rows(q, k, v, first, window)
                a = _at("product", a.transpose(1, 0, 2).reshape(block, wq)) \
                    @ p["wo"].astype(dtype)
                y, _, _ = ffn(hb, p, cfg, held, weights=jax.lax.
                              dynamic_slice_in_dim(weights, first, block, 0))
                return a + y

            y = jax.lax.map(rows_of, jnp.arange((n + pad) // block))
            x = _at("residual", x + y.reshape(n + pad, -1))
            if keep_router:
                routers.append(logits[rows])
            if report is not None:
                reports.append(report)
        x = _at("product", layer_norm(x, params["ln_f"], eps)[:n])
        if rows is not None:
            x = x[rows]
        out = x @ params["embed"].astype(dtype).T
        if float(cfg["logit_scale"]) != 1.0:
            out = out * float(cfg["logit_scale"])
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

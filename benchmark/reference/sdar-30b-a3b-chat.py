"""Plain reference for ``sdar-30b-a3b-chat``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no paging, no
batching and no kernel, written from the configuration's own equations
(ISSUE 32; the configuration's ``assumed`` list).  One layer, x [n, 2048],
block length B:

    h   = rms_norm(x);  q, k, v = h W_q, h W_k, h W_v          (no bias)
    q, k = rms_norm over head_dim of each head, learned [128] weight
    q, k = rope(q, k; theta 1e6) at the absolute position
    row i attends column j  iff  j // B <= i // B       (block-causal)
    x   = x + attention W_o
    h2  = rms_norm(x);  l = h2 W_r                      # router, [128]
    S   = top-8 of l;  w = softmax(l[S])
    x   = x + sum_{e in S} w_e W_down,e(silu(W_gate,e h2) * (W_up,e h2))

and, after the last layer, the final norm and the untied head.  The logits
of position i predict the token AT position i (no shift).

Experts are a plain loop over all 128 with a mask; attention runs in
blocks of query rows so that a 1k prompt fits beside 12.5 GB of weights.

``generate`` is the generation loop, for the CPU tests: blocks aligned at
multiples of B from position 0; the prompt's ``n mod B`` tail sits, fixed,
at the head of the first generated block; a denoising pass proposes
``argmax`` at every undecided position and fixes the ``ceil(undecided /
passes_left)`` of highest ``softmax(logits)[argmax]`` (ties to the lower
index); the block's tokens are final when none is undecided.  It runs the
whole sequence through ``forward`` every pass: no state is kept, so a
commit pass has nothing to do here (the program's writes K/V).

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them:
the fused matrices are taken apart inside the jitted ``forward``.

Routing is discrete.  Handed the program's router logits
(``forward``'s ``program_router``, of the rows ``router_covers`` marks), a
covered row whose own 8th-9th logit margin is under the configuration's
``near_tie_margin_share_of_router_range`` takes the program's eight
experts, if each of them is within that margin of the reference's 8th
logit, and ``forward`` also returns what it saw, layer by layer.  Without
them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512

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

    layers = [{"ln1": get(f"blk{i}.ln1"), "qkv": get(f"blk{i}.qkv.w"),
               "q_norm": get(f"blk{i}.q_norm"),
               "k_norm": get(f"blk{i}.k_norm"),
               "wo": get(f"blk{i}.attn_out.w"), "ln2": get(f"blk{i}.ln2"),
               "router": get(f"blk{i}.moe.router.w"),
               "gate_up": get(f"blk{i}.moe.gate_up.w"),
               "down": get(f"blk{i}.moe.down.w")}
              for i in range(cfg["num_hidden_layers"])]
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f"),
            "head": get("head.w")}


def generation(cfg: dict) -> dict:
    """The generation loop's settings (``assumed`` in the file)."""
    return cfg["assumed"]["generation"]


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [heads, n, d].  Rotate-half: pairs (x[i], x[i + d/2])."""
    n, d = x.shape[1], x.shape[2]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = jnp.asarray(np.outer(np.arange(n), inv_freq), jnp.float32)
    # the tables in x's own precision, so that a lower one stays lower
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1).astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1).astype(x.dtype)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, block):
    """q [H, n, d] over k, v [Hkv, n, d], query head g reading KV head
    g // (H // Hkv), row i admitting column j iff j // block <= i // block.
    In blocks of queries: a block's scores are [H, Q_BLOCK, n]."""
    heads, n, d = q.shape
    rep = heads // k.shape[0]
    q, k, v = _at("product", q), _at("pages", k), _at("pages", v)
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    j = jnp.arange(n)[None, :]
    out = []
    for start in range(0, n, Q_BLOCK):
        i = jnp.arange(start, min(start + Q_BLOCK, n))[:, None]
        keep = j // block <= i // block
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + Q_BLOCK], k) \
            / float(np.sqrt(d))              # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1))
        out.append(jnp.einsum("hqk,hkd->hqd", p, v))
    return jnp.concatenate(out, axis=1)


def _choose(logits, top_k, prog, covered, margin_share):
    """Each token's experts as a mask [n, E], and what the near-tie rule
    saw.  ``prog`` [n, E]: the program's router logits, ``covered`` [n]
    bool the rows it holds them for (or both None: the reference's own
    choice everywhere, no report).  The report: ``[largest |prog - mine|
    of a row's range, least 8th-9th margin of a row's range, rows at a
    near tie, rows that took the program's eight]``, over the covered
    rows."""
    top, idx = jax.lax.top_k(logits, top_k + 1)
    chosen = jax.nn.one_hot(idx[:, :top_k], logits.shape[-1],
                            dtype=bool).any(axis=1)
    if prog is None:
        return chosen, None
    span = logits.max(-1) - logits.min(-1)
    margin = top[:, top_k - 1] - top[:, top_k]              # 8th - 9th
    limit = margin_share * span
    theirs = jax.nn.one_hot(jax.lax.top_k(prog, top_k)[1],
                            logits.shape[-1], dtype=bool).any(axis=1)
    # the program's eight are all within the margin of my 8th logit
    sound = jnp.all(jnp.where(
        theirs, logits >= (top[:, top_k - 1] - limit)[:, None], True), -1)
    near = covered & (margin < limit)
    take = near & sound & jnp.any(theirs != chosen, -1)
    off = jnp.abs(prog - logits).max(-1) / span
    report = jnp.stack([jnp.max(jnp.where(covered, off, 0.0)),
                        jnp.min(jnp.where(covered, margin / span, jnp.inf)),
                        near.sum().astype(jnp.float32),
                        take.sum().astype(jnp.float32)])
    return jnp.where(take[:, None], theirs, chosen), report


def _experts(h, chosen, weights, gate_up, down):
    """sum_e w_e W_down,e(silu(W_gate,e h) * (W_up,e h)) as a loop over
    every expert, each masked to the tokens that chose it."""
    inter = down.shape[1]

    def one(e, acc):
        gu = h @ jax.lax.dynamic_index_in_dim(gate_up, e, 0, False)
        y = _at("product", jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) \
            @ jax.lax.dynamic_index_in_dim(down, e, 0, False)
        w = jnp.where(chosen[:, e], weights[:, e], 0.0)
        return acc + w[:, None] * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def forward(params: dict, token_ids, masked, cfg: dict, rows=None,
            keep_router=False, program_router=None, router_covers=None):
    """Logits ``[len(rows) or n, vocab]`` of one sequence under the
    block-causal mask.  ``masked`` [n] bool: the positions that are
    undecided; they read the mask token whatever ``token_ids`` holds
    there (whether a position is masked is never read off its id).  With
    ``keep_router`` also the router logits of ``rows``, ``[R, L, E]``.
    ``program_router`` ``[n, L, E]`` with ``router_covers`` [n] bool: the
    program's router logits of the rows it covers, for the near-tie rule
    (:func:`_choose`); then also its report, ``[L, 4]``.  The extras
    follow the logits in that order."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    top_k, experts = cfg["num_experts_per_tok"], cfg["num_experts"]
    gen = generation(cfg)
    block = int(gen["block_length"])
    ids = jnp.where(jnp.asarray(masked, bool), int(gen["mask_token_id"]),
                    jnp.asarray(token_ids, jnp.int32))
    n = ids.shape[0]
    share = 0.0
    if program_router is not None:
        share = cfg["check_tolerance"][
            "near_tie_margin_share_of_router_range"]
    routers, reports = [], []
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"][ids])
        for i, p in enumerate(params["layers"]):
            h = _at("product", _rms_norm(x, p["ln1"], eps))
            qkv = h @ p["qkv"]
            q = qkv[:, :heads * d].reshape(n, heads, d).transpose(1, 0, 2)
            k = qkv[:, heads * d:(heads + kv) * d] \
                .reshape(n, kv, d).transpose(1, 0, 2)
            v = qkv[:, (heads + kv) * d:].reshape(n, kv, d) \
                .transpose(1, 0, 2)
            if gen["qk_norm"]:
                q = _rms_norm(q, p["q_norm"], eps)
                k = _rms_norm(k, p["k_norm"], eps)
            q, k = _rope(q, theta), _rope(k, theta)
            a = _attention(q, k, v, block)
            a = _at("product", a.transpose(1, 0, 2).reshape(n, heads * d))
            x = _at("residual", x + a @ p["wo"])
            h = _rms_norm(x, p["ln2"], eps)
            logits = h @ p["router"]                         # [n, E]
            h = _at("product", h)       # (the router read it whole)
            if keep_router:
                routers.append(logits[jnp.asarray(rows)])
            chosen, report = _choose(
                logits, top_k, None if program_router is None
                else jnp.asarray(program_router)[:, i],
                None if program_router is None
                else jnp.asarray(router_covers, bool), share)
            reports.append(report)
            # softmax over all, select, renormalise = softmax over the
            # chosen logits alone
            weights = jax.nn.softmax(
                jnp.where(chosen, logits, -jnp.inf), -1)
            x = _at("residual", x + _experts(h, chosen, weights,
                                             p["gate_up"], p["down"]))
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        out = (x @ params["head"],)
        if keep_router:
            out += (jnp.stack(routers, axis=1),)
        if program_router is not None:
            out += (jnp.stack(reports),)
        return out if len(out) > 1 else out[0]


def unmask(logits, tokens, masked, quota):
    """One denoising pass's decision on one block, on the host: ``logits``
    [B, V], ``tokens`` [B], ``masked`` [B] bool.  Every undecided position
    proposes ``argmax(logits)`` with the confidence ``softmax(logits)``
    of it; the ``quota`` of highest confidence, ties to the lower index,
    take theirs.  Returns the new ``(tokens, masked)``."""
    logits = np.asarray(logits, np.float32)
    tokens, masked = np.array(tokens), np.array(masked, bool)
    x0 = logits.argmax(-1)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    conf = e.max(-1) / e.sum(-1)
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    for i in order[:quota]:
        tokens[i], masked[i] = x0[i], False
    return tokens, masked


def generate(params: dict, prompt, max_new_tokens: int, cfg: dict,
             passes: int = None, forward_fn=None):
    """The tokens block diffusion generates after ``prompt``, greedy, the
    static schedule: ``passes`` denoising passes a block (the
    configuration's by default).  ``forward_fn(ids, masked, rows)`` stands
    in for ``forward`` (a jitted one, for speed); sequences are padded to
    whole blocks, which the mask keeps out of every real row's sight."""
    gen = generation(cfg)
    B, mask_id = int(gen["block_length"]), int(gen["mask_token_id"])
    passes = int(passes or gen["passes"])
    if forward_fn is None:
        def forward_fn(ids, masked, rows):
            return forward(params, ids, masked, cfg, rows)
    seq = [int(t) for t in prompt]
    base = len(seq) - len(seq) % B
    out = []
    while len(out) < max_new_tokens:
        tokens = np.full((B,), mask_id, np.int64)
        head = len(seq) - base
        tokens[:head] = seq[base:]
        masked = np.arange(B) >= head
        for done in range(passes):
            left = int(masked.sum())
            if not left:
                break
            ids = np.asarray(seq[:base] + list(tokens))
            logits = forward_fn(ids, np.concatenate(
                [np.zeros(base, bool), masked]), np.arange(base, base + B))
            tokens, masked = unmask(logits, tokens, masked,
                                    -(-left // (passes - done)))
        new = [int(t) for t in tokens[head:]]
        seq += new
        out += new
        base += B
    return out[:max_new_tokens]

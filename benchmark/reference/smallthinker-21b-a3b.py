"""Plain reference for ``smallthinker-21b-a3b``: the forward pass in
float32 ``jax.numpy`` at "highest" matmul precision, with no cache, no
paging, no batching and no kernel, written from the configuration's own
equations (ISSUE 28; the configuration's ``assumed`` list):

    l   = x W_r                      # router, from the RAW layer input
    S   = top-6 of l;  w = softmax(l[S])
    h   = rms_norm(x);  q, k, v = h W_q, h W_k, h W_v
    window layer: rope(q, k; theta), token i attends j, i - W < j <= i
    full layer:   no positional encoding, token i attends j <= i
    x'  = x + attention W_o
    out = x' + sum_{e in S} w_e W_down,e(relu(W_gate,e h2) * (W_up,e h2))

Experts are a plain loop over all 64 with a mask (ten times the FLOPs of
the routed program, still about a second on the chip); attention runs in
query blocks so that a 6k prompt fits beside 9.5 GB of weights.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them
(the chip cannot hold the experts twice): the fused matrices are taken
apart inside the jitted ``forward``.

Routing is discrete.  Where ``cfg["_program_router"]`` holds the program's
router logits of the compared rows (the check engine's builder leaves them
there), a compared row whose own 6th-7th logit margin is under the
configuration's ``near_tie_margin_share_of_router_range`` takes the
program's six experts, if each of them is within that margin of the
reference's 6th logit; the deviations and the counts are printed.  Without
the key the reference's own choice stands everywhere.
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
               "wo": get(f"blk{i}.attn_out.w"), "ln2": get(f"blk{i}.ln2"),
               "router": get(f"blk{i}.moe.router.w"),
               "gate_up": get(f"blk{i}.moe.gate_up.w"),
               "down": get(f"blk{i}.moe.down.w")}
              for i in range(cfg["num_hidden_layers"])]
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f"),
            "head": get("head.w")}


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


def _attention(q, k, v, window):
    """q [H, n, d] over k, v [Hkv, n, d], causal, query head g reading KV
    head g // (H // Hkv); ``window``: keys j with i - window < j <= i.
    In blocks of queries: a block's scores are [H, Q_BLOCK, n]."""
    heads, n, d = q.shape
    rep = heads // k.shape[0]
    q, k, v = _at("product", q), _at("pages", k), _at("pages", v)
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    j = jnp.arange(n)[None, :]
    out = []
    for start in range(0, n, Q_BLOCK):
        i = jnp.arange(start, min(start + Q_BLOCK, n))[:, None]
        keep = j <= i
        if window is not None:
            keep = keep & (j > i - window)
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + Q_BLOCK], k) \
            / float(np.sqrt(d))              # weak: keeps q's precision
        p = _at("product",
                jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1))
        out.append(jnp.einsum("hqk,hkd->hqd", p, v))
    return jnp.concatenate(out, axis=1)


def _choose(logits, top_k, rows, prog, margin_share, report, layer):
    """Each token's experts as a mask [n, E].  ``prog`` [R, E]: the
    program's router logits of the compared ``rows`` (or None)."""
    top, idx = jax.lax.top_k(logits, top_k + 1)
    chosen = jax.nn.one_hot(idx[:, :top_k], logits.shape[-1],
                            dtype=bool).any(axis=1)
    if prog is None:
        return chosen
    mine = logits[rows]                                      # [R, E]
    span = mine.max(-1) - mine.min(-1)
    margin = top[rows, top_k - 1] - top[rows, top_k]        # 6th - 7th
    limit = margin_share * span
    theirs = jax.nn.one_hot(jax.lax.top_k(prog, top_k)[1],
                            logits.shape[-1], dtype=bool).any(axis=1)
    # the program's six are all within the margin of my 6th logit
    sound = jnp.all(jnp.where(
        theirs, mine >= (top[rows, top_k - 1] - limit)[:, None], True), -1)
    near = margin < limit
    take = near & sound & jnp.any(theirs != chosen[rows], -1)
    jax.debug.callback(
        report, layer, jnp.max(jnp.abs(prog - mine) / span[:, None]),
        jnp.min(margin / span), near.sum(), take.sum())
    return chosen.at[rows].set(jnp.where(take[:, None], theirs,
                                         chosen[rows]))


def _report(layer, deviation, least_margin, near, taken):
    print(f"[reference smallthinker] layer {int(layer)}: program router "
          f"logits off the reference's by at most {float(deviation):.3g} "
          f"of a row's logit range on the compared rows; least 6th-7th "
          f"margin {float(least_margin):.3g} of it; {int(near)} rows a "
          f"near tie, {int(taken)} took the program's choice", flush=True)


def _experts(h, chosen, weights, gate_up, down):
    """sum_e w_e W_down,e(relu(W_gate,e h) * (W_up,e h)) as a loop over
    every expert, each masked to the tokens that chose it."""
    inter = down.shape[1]

    def one(e, acc):
        gu = h @ jax.lax.dynamic_index_in_dim(gate_up, e, 0, False)
        y = _at("product", jnp.maximum(gu[:, :inter], 0) * gu[:, inter:]) \
            @ jax.lax.dynamic_index_in_dim(down, e, 0, False)
        w = jnp.where(chosen[:, e], weights[:, e], 0.0)
        return acc + w[:, None] * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def forward(params: dict, token_ids, cfg: dict, rows=None,
            keep_router=False):
    """Logits ``[len(rows) or n, vocab]`` of one sequence; with
    ``keep_router`` also the router logits of ``rows``, ``[R, L, E]``."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    top_k = cfg["moe_num_active_primary_experts"]
    ids = jnp.asarray(token_ids, jnp.int32)
    n = ids.shape[0]
    prog = None
    if rows is not None and "_program_router" in cfg:
        share = cfg["check_tolerance"][
            "near_tie_margin_share_of_router_range"]
        n_rows = len(rows)
        n_layers = cfg["num_hidden_layers"]
        experts = cfg["moe_num_primary_experts"]

        def fetch(seq, first_row):
            rec = cfg["_program_router"]
            if list(np.asarray(seq)[:len(rec["ids"])]) != rec["ids"] \
                    or int(first_row) != rec["first_row"]:
                raise RuntimeError("the program's router logits on record "
                                   "are of another sequence")
            return np.asarray(rec["logits"], np.float32)

        prog = jax.pure_callback(
            fetch, jax.ShapeDtypeStruct((n_rows, n_layers, experts),
                                        jnp.float32), ids, rows[0])
    routers = []
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"][ids])
        for i, p in enumerate(params["layers"]):
            window = cfg["sliding_window_size"] \
                if cfg["sliding_window_layout"][i] else None
            logits = x @ p["router"]                         # [n, E]
            if keep_router:
                routers.append(logits[jnp.asarray(rows)])
            chosen = _choose(logits, top_k, rows,
                             None if prog is None else prog[:, i], share
                             if prog is not None else 0.0, _report, i)
            # softmax over the chosen logits alone
            weights = jax.nn.softmax(
                jnp.where(chosen, logits, -jnp.inf), -1)
            h = _at("product", _rms_norm(x, p["ln1"], eps))
            qkv = h @ p["qkv"]
            q = qkv[:, :heads * d].reshape(n, heads, d).transpose(1, 0, 2)
            k = qkv[:, heads * d:(heads + kv) * d] \
                .reshape(n, kv, d).transpose(1, 0, 2)
            v = qkv[:, (heads + kv) * d:].reshape(n, kv, d) \
                .transpose(1, 0, 2)
            if cfg["rope_layout"][i]:
                q, k = _rope(q, theta), _rope(k, theta)
            a = _attention(q, k, v, window)
            a = _at("product", a.transpose(1, 0, 2).reshape(n, heads * d))
            x = _at("residual", x + a @ p["wo"])
            h = _at("product", _rms_norm(x, p["ln2"], eps))
            x = _at("residual", x + _experts(h, chosen, weights,
                                             p["gate_up"], p["down"]))
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        out = x @ params["head"]
        return (out, jnp.stack(routers, axis=1)) if keep_router else out

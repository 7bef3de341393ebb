"""Plain reference for ``lfm2-24b-a2b``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no state, no
paging, no batching and no kernel, written from the configuration's own
equations (ISSUE 34; the configuration's ``assumed`` list).  One layer, x
[n, 2048], every norm an RMSNorm with a learned weight and eps 1e-5, no
bias anywhere:

    h = rms_norm(x)
    conv layer:       [B, C, u] = split3(h W_in);  z = B * u
                      c_t = sum_{j=0..2} w[:, j] * z_{t-2+j},  z_{<0} = 0
                      y = (C * c) W_out
    attention layer:  q, k, v = h W_q, h W_k, h W_v    (32 / 8 / 8 heads of 64)
                      q, k = rms_norm over the 64 of each head, learned [64]
                      q, k = rope(q, k; theta 1e6) at the absolute position
                      y = causal softmax(q k^T / 8) v  W_o   (KV head g // 4)
    x = x + y;  g = rms_norm(x)
    leading dense layers:  x = x + W_2(silu(W_1 g) * (W_3 g))       (11776)
    the others:  s = sigmoid(g W_r);  sel = top4(s + b), ties to the lower
                 index;  a_e = s_e / (sum_{sel} s + 1e-6) * routed_scaling_factor
                 x = x + sum_{e in sel} a_e W_2,e(silu(W_1,e g) * (W_3,e g))

and, after the last layer, the final norm and ``logits = x_norm E^T`` with
``E`` the embedding table (the head is tied).  The convolution is an
explicit sum over three shifted copies of ``z``; experts are a plain loop
over all 64 with a mask; attention runs in blocks of query rows.

Departures from the published description: none in the equations; the
order ``B, C, u`` of the input projection's thirds, QK-norm before RoPE
and the tied head are the family's modelling code, not keys of
``config.json`` (the configuration's ``assumed``); the layers run are the
configuration's cut (published layers 1-5).

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them:
the fused matrices are taken apart inside the jitted ``forward``.

Routing is discrete.  Handed the program's PRE-sigmoid router logits of
the compared ``rows`` (``program_router`` [R, L_moe, E]), a compared row
whose own 4th-5th margin of ``s + b`` is under the configuration's
``near_tie_margin_share_of_router_range`` of the row's range of ``s + b``
takes the program's four experts, if each of them is within that margin of
the reference's 4th; ``forward`` then also returns what it saw, layer by
layer.  Without them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def layer_kinds(cfg: dict) -> list:
    """``(mixer, dense)`` of every layer that is run."""
    return [(kind, i < cfg["num_dense_layers"])
            for i, kind in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]

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
    for i, (kind, dense) in enumerate(layer_kinds(cfg)):
        p = {"ln1": get(f"blk{i}.ln1"), "ln2": get(f"blk{i}.ln2")}
        if kind == "conv":
            p.update(w_in=get(f"blk{i}.conv_in.w"),
                     conv=get(f"blk{i}.conv.w"),
                     w_out=get(f"blk{i}.conv_out.w"))
        else:
            p.update(qkv=get(f"blk{i}.qkv.w"), q_norm=get(f"blk{i}.q_norm"),
                     k_norm=get(f"blk{i}.k_norm"),
                     wo=get(f"blk{i}.attn_out.w"))
        if dense:
            p.update(gate_up=get(f"blk{i}.gate_up.w"),
                     down=get(f"blk{i}.ffn_out.w"))
        else:
            p.update(router=get(f"blk{i}.moe.router.w"),
                     bias=get(f"blk{i}.moe.expert_bias"),
                     gate_up=get(f"blk{i}.moe.gate_up.w"),
                     down=get(f"blk{i}.moe.down.w"))
        layers.append(p)
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f")}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


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


def _attention(q, k, v):
    """q [H, n, d] over k, v [Hkv, n, d], causal, query head g reading KV
    head g // (H // Hkv).  In blocks of queries."""
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
    copies of z [n, H], ``w`` [H, L]."""
    n, taps = z.shape[0], w.shape[1]
    zp = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return sum(zp[j:j + n] * w[:, j].astype(z.dtype) for j in range(taps))


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
    margin = top[rows, top_k - 1] - top[rows, top_k]        # 4th - 5th
    limit = margin_share * span
    theirs = jax.nn.one_hot(jax.lax.top_k(prog_score, top_k)[1],
                            score.shape[-1], dtype=bool).any(axis=1)
    # the program's four are all within the margin of my 4th
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


def _swiglu(h, gate_up, down):
    inter = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    return _at("product", jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) \
        @ down.astype(h.dtype)


def _experts(h, weights, gate_up, down):
    """sum_e a_e W_2,e(silu(W_1,e h) * (W_3,e h)) as a loop over every
    expert; ``weights`` [n, E] is zero where a token did not choose it."""
    def one(e, acc):
        y = _swiglu(h, jax.lax.dynamic_index_in_dim(gate_up, e, 0, False),
                    jax.lax.dynamic_index_in_dim(down, e, 0, False))
        return acc + weights[:, e, None].astype(h.dtype) * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(h))


def route(logits, bias, cfg, rows=None, program_logits=None):
    """Sigmoid routing on pre-sigmoid ``logits`` [n, E]: the weights
    [n, E] (zero off the chosen four) and the near-tie report."""
    top_k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    b = bias.astype(jnp.float32) if cfg["use_expert_bias"] else 0.0
    prog = None if program_logits is None \
        else jax.nn.sigmoid(program_logits.astype(jnp.float32)) + b
    share = cfg["check_tolerance"]["near_tie_margin_share_of_router_range"] \
        if prog is not None else 0.0
    chosen, report = _choose(s + b, top_k, rows, prog, share)
    a = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        a = a / (a.sum(-1, keepdims=True) + 1e-6)
    return a * float(cfg["routed_scaling_factor"]), report


def forward(params: dict, token_ids, cfg: dict, rows=None,
            program_router=None, dtype=jnp.float32, keep_router=False):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  With
    ``program_router`` [R, L_moe, E] (the program's pre-sigmoid router
    logits of ``rows``) also the near-tie report ``[L_moe, 4]``; with
    ``keep_router`` instead its own pre-sigmoid router logits of
    ``rows``, [R, L_moe, E].
    ``dtype``: the precision of every activation and product (float32;
    the bfloat16 control passes the other)."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    eps = cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    hidden = cfg["hidden_size"]
    ids = jnp.asarray(token_ids, jnp.int32)
    n = ids.shape[0]
    if rows is not None:
        rows = jnp.asarray(rows)
    reports, routers = [], []
    moe = 0
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"].astype(dtype)[ids])
        for p, (kind, dense) in zip(params["layers"], layer_kinds(cfg)):
            h = _at("product", _rms_norm(x, p["ln1"], eps))
            if kind == "conv":
                bcu = h @ p["w_in"].astype(dtype)
                z = bcu[:, :hidden] * bcu[:, 2 * hidden:]
                c = _short_conv(z, p["conv"])
                y = _at("product", bcu[:, hidden:2 * hidden] * c) \
                    @ p["w_out"].astype(dtype)
            else:
                qkv = h @ p["qkv"].astype(dtype)
                q = qkv[:, :heads * d].reshape(n, heads, d).transpose(1, 0, 2)
                k = qkv[:, heads * d:(heads + kv) * d] \
                    .reshape(n, kv, d).transpose(1, 0, 2)
                v = qkv[:, (heads + kv) * d:].reshape(n, kv, d) \
                    .transpose(1, 0, 2)
                q = _rope(_rms_norm(q, p["q_norm"], eps), theta)
                k = _rope(_rms_norm(k, p["k_norm"], eps), theta)
                y = _at("product", _attention(q, k, v).transpose(1, 0, 2)
                        .reshape(n, heads * d)) @ p["wo"].astype(dtype)
            x = _at("residual", x + y)
            g = _rms_norm(x, p["ln2"], eps)
            if dense:
                x = _at("residual", x + _swiglu(_at("product", g),
                                                p["gate_up"], p["down"]))
                continue
            logits = g @ p["router"].astype(dtype)
            g = _at("product", g)       # (the router read it whole)
            if keep_router:
                routers.append(logits[rows])
            weights, report = route(
                logits, p["bias"], cfg, rows,
                None if program_router is None else program_router[:, moe])
            moe += 1
            if report is not None:
                reports.append(report)
            x = _at("residual",
                    x + _experts(g, weights, p["gate_up"], p["down"]))
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[rows]
        out = x @ params["embed"].astype(dtype).T
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

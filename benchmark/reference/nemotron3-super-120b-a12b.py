"""Plain reference for ``nemotron3-super-120b-a12b`` (``nemotron_h``): the
forward pass in float32 ``jax.numpy`` at "highest" matmul precision, with
no cache, no state variable, no chunks, no paging, no batching, no dispatch
and no kernel, written from the configuration's own equations (ISSUE 63;
the configuration's ``assumed`` list).  ``x`` [n, 4096] is the residual
stream; layer ``i`` of kind ``c = hybrid_override_pattern[i]`` is ONE
sublayer under ONE norm:

    x = x + f_c(rms_norm(x; w_i, eps 1e-5))

    M (Mamba-2):  z | xBC | dt = h W_in                    [8192 | 10240 | 128]
                  xBC = silu(conv4(xBC) + b)     causal, depthwise, 4 taps,
                                                 zero history
                  x | B | C = xBC        x [128 heads, 64], B, C [8 groups, 128]
                  dt = softplus(dt + dt_bias),  A = -exp(A_log)   one a head
                  head h, g = h // 16:   S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T
                                         y_h = S_h C_g + D_h x_h      S_0 = 0
                  y = y * silu(z), then an RMS norm over each group's 1024
                  channels apart, one learned [8192] weight;  out = y W_out
    * (attention): 32 query over 2 key-value heads of 128, no bias, NO rotary
                  embedding, causal, scale 128^-1/2, W_o [4096, 4096]
    E (LatentMoE): s = sigmoid(h W_r)  [512]; the 22 largest of s + b chosen
                  (b never in the weights); w_e = 5 s_e / (sum of the chosen s
                  + 1e-20);  u = h W_down [1024];
                  r = sum over the chosen e HELD here of w_e W2_e relu(W1_e u)^2
                  f = r W_up + W2_s relu(W1_s h)^2     (the shared expert at
                  full width, 5376)

then ``logits = rms_norm(x; w_f) W_head`` (untied).  The recurrence is kept
as it stands, token by token under ``lax.scan``, the state a head ``[64,
128]`` as the equations have it; the convolution is an explicit sum over
four shifted copies; the experts are a plain loop over the held ones;
attention runs in blocks of query rows.

``held = (first, count)``: the chip holds experts ``first .. first + count
- 1`` of the router's ``expert_share.router_experts``; the router scores
all of them, the weights are normalised over all 22 chosen, and what the
absent experts would add is left out (``held = (0, 512)`` is the uncut
layer).  ``W_up`` is linear, so the shares' ``r W_up`` add up to the whole.

Departures from the published description: none in the equations.  What
``config.json`` has no key for is the family's modelling code and listed in
the configuration's ``assumed``.  The one multi-token-prediction module
drafts tokens and never changes what the model answers: cut
(``num_nextn_predict_layers`` in ``reduced``).  The layers run, the experts
held and the vocabulary are the configuration's cut.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices, and copies none of them:
``params["blocks"]`` are the layers in order, ``params["layers"]`` the
expert layers among them (the same entries), in order.

Routing is discrete.  Handed the program's PRE-sigmoid router logits of
the compared ``rows`` (``program_router`` [R, L_moe, E]), a compared row
whose own 22nd-23rd margin of ``s + b`` is under the configuration's
``near_tie_margin_share_of_router_range`` of the row's range of ``s + b``
takes the program's 22 experts, if each of them is within that margin of
the reference's 22nd; ``forward`` then also returns what it saw, layer by
layer.  Without them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def layer_kinds(cfg: dict) -> list:
    return list(cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]])


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

    blocks = []
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"blk{i}."
        p = {"ln": get(b + "ln1")}
        if kind == "M":
            p.update(w_in=get(b + "ssd_in.w"), conv=get(b + "ssd_conv.w"),
                     conv_b=get(b + "ssd_conv.b"), a_log=get(b + "ssd_A_log"),
                     dt_bias=get(b + "ssd_dt_bias"), d=get(b + "ssd_D"),
                     y_norm=get(b + "ssd_norm"), wo=get(b + "ssd_out.w"))
        elif kind == "*":
            p.update(qkv=get(b + "qkv.w"), wo=get(b + "attn_out.w"))
        else:
            p.update(router=get(b + "moe.router.w"),
                     bias=get(b + "moe.expert_bias"),
                     lat_down=get(b + "moe.latent_down.w"),
                     up=get(b + "moe.up.w"), down=get(b + "moe.down.w"),
                     lat_up=get(b + "moe.latent_up.w"),
                     shared_up=get(b + "moe.shared_up.w"),
                     shared_down=get(b + "moe.shared_down.w"))
        blocks.append(p)
    return {"embed": get("embed"), "blocks": blocks,
            "layers": [p for p in blocks if "router" in p],
            "ln_f": get("ln_f"), "head": get("head.w")}


def _rms_norm(x, w, eps, group=None):
    """Over the last axis, or over each run of ``group`` channels apart;
    one learned weight a channel either way."""
    xs = x if group is None else x.reshape(x.shape[:-1] + (-1, group))
    y = xs * jax.lax.rsqrt(jnp.mean(xs * xs, -1, keepdims=True) + eps)
    return y.reshape(x.shape) * w.astype(x.dtype)


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
    [H], bm, cm [n, G, N] -> y [n, H, P] without the skip; head h reads
    group h // (H / G)'s B and C.  S_0 = 0."""
    per = x.shape[1] // bm.shape[1]

    def token(s, row):
        xt, dtt, bt, ct = row
        bh, ch = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ch)

    s0 = jnp.zeros((x.shape[1], x.shape[2], bm.shape[2]), x.dtype)
    return jax.lax.scan(token, s0, (x, dt, bm, cm))[1]


def _mamba(h, p, cfg, eps):
    dtype = h.dtype
    n = h.shape[0]
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    state, groups = cfg["ssm_state_size"], cfg["n_groups"]
    inner = heads * hp
    zxd = _at("product", h) @ p["w_in"].astype(dtype)
    z, dt = zxd[:, :inner], zxd[:, -heads:]
    xbc = jax.nn.silu(_short_conv(zxd[:, inner:-heads], p["conv"],
                                  p["conv_b"]))
    x = xbc[:, :inner].reshape(n, heads, hp)
    bm = xbc[:, inner:inner + groups * state].reshape(n, groups, state)
    cm = xbc[:, inner + groups * state:].reshape(n, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(dtype))
    a = -jnp.exp(p["a_log"].astype(dtype))
    y = selective_scan(x, dt, a, bm, cm) \
        + p["d"].astype(dtype)[:, None] * x
    y = _rms_norm(y.reshape(n, inner) * jax.nn.silu(z), p["y_norm"], eps,
                  group=inner // groups)
    return _at("product", y) @ p["wo"].astype(dtype)


def _full_attention(h, p, cfg):
    dtype = h.dtype
    n = h.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    qkv = _at("product", h) @ p["qkv"].astype(dtype)

    def split(t, m):
        return t.reshape(n, m, d).transpose(1, 0, 2)

    q = split(qkv[:, :heads * d], heads)
    k = split(qkv[:, heads * d:(heads + kv) * d], kv)
    v = split(qkv[:, (heads + kv) * d:], kv)
    # query head g reads KV head g // (heads / kv)
    k, v = (jnp.repeat(t, heads // kv, axis=0) for t in (k, v))
    y = _attention(q, k, v, d ** -0.5)
    return _at("product", y.transpose(1, 0, 2).reshape(n, heads * d)) \
        @ p["wo"].astype(dtype)


def _relu2_mlp(h, up, down):
    return _at("product", jnp.square(jax.nn.relu(h @ up.astype(h.dtype)))) \
        @ down.astype(h.dtype)


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
    margin = top[rows, top_k - 1] - top[rows, top_k]    # 22nd - 23rd
    limit = margin_share * span
    theirs = jax.nn.one_hot(jax.lax.top_k(prog_score, top_k)[1],
                            score.shape[-1], dtype=bool).any(axis=1)
    # the program's 22 are all within the margin of my 22nd
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
    router's experts: the weights [n, E] (zero off the chosen 22,
    normalised over the 22, times ``routed_scaling_factor``) and the
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
        a = a / (a.sum(-1, keepdims=True) + 1e-20)
    return a * float(cfg["routed_scaling_factor"]), report


def held_experts(u, weights, up, down, first):
    """sum over the HELD experts e = first .. first + len(up) - 1 of w_e
    W2_e relu(W1_e u)^2, as a loop over them; ``weights`` [n, E_router] is
    zero where a token did not choose an expert."""
    def one(e, acc):
        y = _relu2_mlp(u, jax.lax.dynamic_index_in_dim(up, e, 0, False),
                       jax.lax.dynamic_index_in_dim(down, e, 0, False))
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return acc + w[:, None].astype(u.dtype) * y

    return jax.lax.fori_loop(0, up.shape[0], one, jnp.zeros_like(u))


def latent_moe(h, p, cfg, held, rows=None, program_logits=None,
               shared=True):
    """One ``E`` layer on normed rows h [n, hidden] for the chip that holds
    experts ``held = (first, count)`` (``p["up"]`` [count, ..]): ``(f,
    router logits [n, E_router], near-tie report)``.  ``shared`` False
    leaves the shared expert out (the shares of a layer count it once)."""
    dtype = h.dtype
    logits = h @ p["router"].astype(dtype)
    h = _at("product", h)               # (the router read it whole)
    weights, report = route(logits, p["bias"], cfg, rows, program_logits)
    first, count = held
    if p["up"].shape[0] != count:
        raise ValueError(f"{p['up'].shape[0]} expert matrices for a share "
                         f"of {count}")
    u = _at("product", h @ p["lat_down"].astype(dtype))
    r = held_experts(u, weights, p["up"], p["down"], first)
    y = _at("product", r) @ p["lat_up"].astype(dtype)
    if shared and cfg["n_shared_experts"]:
        y = y + _relu2_mlp(h, p["shared_up"], p["shared_down"])
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
    eps = cfg["layer_norm_epsilon"]
    held = held_range(cfg) if held is None else held
    ids = jnp.asarray(token_ids, jnp.int32)
    if rows is not None:
        rows = jnp.asarray(rows)
    reports, routers = [], []
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"].astype(dtype)[ids])
        for p, kind in zip(params["blocks"], layer_kinds(cfg)):
            h = _rms_norm(x, p["ln"], eps)
            if kind == "M":
                y = _mamba(h, p, cfg, eps)
            elif kind == "*":
                y = _full_attention(h, p, cfg)
            else:
                y, logits, report = latent_moe(
                    h, p, cfg, held, rows,
                    None if program_router is None
                    else program_router[:, len(reports)])
                if keep_router:
                    routers.append(logits[rows])
                if report is not None:
                    reports.append(report)
            x = _at("residual", x + y)
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[rows]
        out = x @ params["head"].astype(dtype)
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

"""Plain reference for ``longcat-flash-chat``: the forward pass in float32
``jax.numpy`` at "highest" matmul precision, with no cache, no pages, no
chunks, no batching and no kernel: the whole sequence in one forward,
written from the configuration's own equations (ISSUE 66; the
configuration's ``assumed`` list).  ``N(x; w) = x / sqrt(mean(x^2) + 1e-5)
* w``.  One published layer, x [n, 6144]: two latent-attention sublayers,
two dense SwiGLUs and ONE expert branch that leaves behind the first
sublayer and joins behind the second dense SwiGLU (shortcut-connected):

    a = x + MLA_0(N(x; w_in0))
    u = N(a; w_post0)
    s = MoE(u)                         the branch: leaves here ...
    b = a + SwiGLU_0(u)                dense, 12288
    c = b + MLA_1(N(b; w_in1))
    y = c + SwiGLU_1(N(c; w_post1)) + s        ... and joins here

    MLA(h), in the EXPANDED form only (the program's decode step runs the
    absorbed form, its chunks expand cached rows block by block):
        c_q = 2 N(h W_qa) [1536]       mla_scale_q_lora: (6144 / 1536)^1/2
        [q_nope | q_rope] = c_q W_qb   heads of (128 | 64)
        [c_kv | k_r] = h W_kva  (512 | 64);  c_kv = 3.4641 N(c_kv)
                                       mla_scale_kv_lora: (6144 / 512)^1/2;
                                       k_r is NOT scaled
        q_rope, k_r rotated at their positions: pairs (2i, 2i + 1), base
            1e7, no YaRN
        [k_nope | v] = c_kv W_kvb      heads of (128 | 128)
        a = causal softmax((q_nope . k_nope + q_rope . k_r) * 192^-1/2) v
        y = a W_o                      no bias, no gate
    MoE(u), the router over ALL its E + Z = 768 outputs, of which the last
    Z = 256 are identity ("zero-computation") experts:
        p = softmax(u W_r), float32
        T = the 12 largest of p + bias (ties to the lower index); the bias
            [768] moves the choice and never the weights
        w_e = 6 p_e for e in T, NOT renormalised over the twelve
        s = sum_{e in T, e < 512, e HELD} w_e SwiGLU_e(u)      experts of 2048
            + (sum_{e in T, e >= 512} w_e) u                   identity picks

then the final norm and ``logits = x_norm W_head`` (untied, over the
vocabulary slice).  ``held = (first, count)`` says which of the 512 REAL
experts this chip holds: the sum runs over the chosen experts that are
held, what the absent experts would add is left out, and the identity term
is computed whole (an identity expert has no weights to place).  ``heads =
(first, count)`` likewise cuts the parameters of an uncut sublayer to one
chip's heads (``head_share``): a share's output is its heads' rows of
``W_o``, and the shares of a sublayer add up to it.

Departures from the published description: none in the mathematics.  The
sequence goes through whole: at the cell's 709 rows and 8 heads a layer's
scores are 19 MB and a dense SwiGLU's inner rows 70 MB, so nothing is
blocked (the siblings at 128 heads and 9,000 rows block both).

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices (TWO pattern layers a
published layer: ``blk{2l}`` the first sublayer, its dense SwiGLU and the
branch, ``blk{2l + 1}`` the second and the join) and copies none of them.

Routing is discrete.  Handed the program's router logits of the compared
``rows`` (``program_router`` [R, L, 768]), a compared row whose 12th-13th
margin of ``p + bias`` is under the configuration's
``near_tie_margin_share_of_router_range`` of the row's range of ``p``
takes the program's twelve, if each of them is within that margin of the
reference's 12th; ``forward`` then also returns what it saw, layer by
layer.  Without them the reference's own choice stands everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

JOINS = ("after_second_ffn", "before_second_sublayer", "dropped")


def held_range(cfg: dict) -> tuple:
    """``(first, count)`` of the real experts this chip holds."""
    return int(cfg["expert_share"]["first"]), int(cfg["n_routed_experts"])


def real_experts(cfg: dict) -> int:
    """The router's outputs that are experts with weights: its width less
    the identity experts, which are its last."""
    return int(cfg["expert_share"]["router_experts"]) \
        - int(cfg["zero_expert_num"])

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

    def sublayer(b):
        return {"ln_in": get(b + "ln1"), "ln_post": get(b + "ln2"),
                "q_a": get(b + "q_a.w"), "q_a_norm": get(b + "q_a_norm"),
                "q_b": get(b + "q_b.w"), "kv_a": get(b + "kv_a.w"),
                "kv_a_norm": get(b + "kv_a_norm"), "kv_b": get(b + "kv_b.w"),
                "wo": get(b + "attn_out.w"),
                "gate_up": get(b + "gate_up.w"), "down": get(b + "ffn_out.w")}

    layers = []
    for i in range(cfg["num_layers"]):
        b = f"blk{2 * i}.moe."
        layers.append({
            "sub": [sublayer(f"blk{2 * i}."), sublayer(f"blk{2 * i + 1}.")],
            "router": get(b + "router.w"), "bias": get(b + "expert_bias"),
            "gate_up": get(b + "gate_up.w"), "down": get(b + "down.w")})
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f"),
            "head": get("head.w")}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


# ---------------------------------------------------------------------------
# latent attention, the expanded form
# ---------------------------------------------------------------------------

def lora_scales(cfg: dict) -> tuple:
    """What the two inner norms' outputs are multiplied by:
    ``(hidden / rank) ** 0.5`` where the configuration says so, else 1."""
    h = cfg["hidden_size"]
    return ((h / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"]
            else 1.0,
            (h / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"]
            else 1.0)


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


def _tables(cfg, n, dtype):
    """cos, sin [n, rope / 2] at positions 0 .. n - 1: ``f_i = base^(-2i /
    rope)``; float32 angles, the tables in the activations' precision."""
    d = cfg["qk_rope_head_dim"]
    f = float(cfg["rope_theta"]) ** (-jnp.arange(0, d, 2, dtype=jnp.float32)
                                     / d)
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * f[None, :]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def head_share(p: dict, first: int, count: int, cfg: dict) -> dict:
    """An uncut sublayer's parameters cut to heads ``first .. first + count
    - 1``: their columns of ``W_qb`` and ``W_kvb`` and their rows of
    ``W_o``; everything a token's latent is made from stays whole."""
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return dict(p, q_b=p["q_b"][:, first * (dn + dr):(first + count)
                                * (dn + dr)],
                kv_b=p["kv_b"][:, first * (dn + dv):(first + count)
                               * (dn + dv)],
                wo=p["wo"][first * dv:(first + count) * dv])


def mla(h, p, cfg, cos, sin):
    """One latent-attention sublayer on normed rows h [n, hidden], over as
    many heads as ``p["wo"]`` has rows for."""
    eps, c = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    n, heads = h.shape[0], p["wo"].shape[0] // dv
    q_scale, kv_scale = lora_scales(cfg)
    dtype = h.dtype
    h = _at("product", h)
    c_q = _at("product", _norm(h @ p["q_a"].astype(dtype), p["q_a_norm"],
                               eps) * q_scale)
    q = _at("product",
            (c_q @ p["q_b"].astype(dtype)).reshape(n, heads, dn + dr))
    q_rope = _at("product", _rotate_pairs(q[..., dn:], cos, sin))
    kv_a = h @ p["kv_a"].astype(dtype)
    # the latent row as a program writes it to its pages: [c_kv | k_r]
    c_kv = _at("pages", _norm(kv_a[:, :c], p["kv_a_norm"], eps) * kv_scale)
    k_r = _at("pages", _rotate_pairs(kv_a[:, c:], cos, sin))  # [n, dr]
    kv = _at("product",
             (c_kv @ p["kv_b"].astype(dtype)).reshape(n, heads, dn + dv))
    s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
         + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) \
        * (dn + dr) ** -0.5                  # weak: keeps q's precision
    keep = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    a = jnp.einsum("hqk,khd->qhd", _at("product", jax.nn.softmax(
        jnp.where(keep[None], s, -jnp.inf), -1)), kv[..., dn:])
    return _at("product", a.reshape(n, heads * dv)) @ p["wo"].astype(dtype)


# ---------------------------------------------------------------------------
# the dense SwiGLU and the expert branch
# ---------------------------------------------------------------------------

def swiglu(h, gate_up, down):
    """W_2(silu(W_1 h) * (W_3 h)) with gate | up side by side."""
    width = down.shape[0]
    gu = h @ gate_up.astype(h.dtype)
    return _at("product", jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
        @ down.astype(h.dtype)


def _twelve(scores, top_k):
    """The ``top_k`` largest of ``scores`` [n, E] as a mask; ties to the
    lower index (``lax.top_k``)."""
    return jax.nn.one_hot(jax.lax.top_k(scores, top_k)[1], scores.shape[1],
                          dtype=bool).any(axis=1)


def _choose(p, bias, cfg, rows, prog_p, margin_share):
    """Each token's picks as a mask [n, E + Z]: the ``moe_topk`` largest
    of ``p + bias``.  ``prog_p`` [R, E + Z]: the program's softmax scores
    of the compared ``rows`` (or None).  Returns the mask and a report
    ``[deviation, least margin, near ties, taken]`` of the compared rows;
    a margin is the row's 12th-13th margin of ``p + bias``, taken against
    ``margin_share`` of its range of ``p``."""
    top_k = int(cfg["moe_topk"])
    biased = p + bias
    chosen = _twelve(biased, top_k)
    if prog_p is None:
        return chosen, None
    mine, score = biased[rows], p[rows]
    span = score.max(-1) - score.min(-1)
    top = jax.lax.top_k(mine, top_k + 1)[0]
    margin = top[:, top_k - 1] - top[:, top_k]
    limit = margin_share * span
    theirs = _twelve(prog_p + bias, top_k)
    # each of the program's twelve is within the margin of my 12th
    sound = jnp.all(jnp.where(
        theirs, mine >= (top[:, top_k - 1] - limit)[:, None], True), -1)
    near = margin < limit
    take = near & sound & jnp.any(theirs != chosen[rows], -1)
    report = jnp.stack([
        jnp.max(jnp.abs(prog_p - score) / span[:, None]),
        jnp.min(margin / span), near.sum().astype(jnp.float32),
        take.sum().astype(jnp.float32)])
    return chosen.at[rows].set(jnp.where(take[:, None], theirs,
                                         chosen[rows])), report


def route(logits, bias, cfg, rows=None, program_logits=None):
    """Softmax routing on ``logits`` [n, E + Z] over ALL the router's
    outputs: the weights [n, E + Z] (zero off the chosen twelve, their
    unbiased softmax scores as they are, times ``routed_scaling_factor``)
    and the near-tie report."""
    p = jax.nn.softmax(logits.astype(jnp.float32), -1)
    prog = None if program_logits is None \
        else jax.nn.softmax(program_logits.astype(jnp.float32), -1)
    share = cfg["check_tolerance"]["near_tie_margin_share_of_router_range"] \
        if prog is not None else 0.0
    chosen, report = _choose(p, bias.astype(jnp.float32), cfg, rows, prog,
                             share)
    return jnp.where(chosen, p, 0.0) * float(cfg["routed_scaling_factor"]), \
        report


def held_experts(u, weights, gate_up, down, first):
    """sum over the HELD experts e = first .. first + len(gate_up) - 1 of
    w_e SwiGLU_e(u), as a loop over them; ``weights`` [n, E + Z] is zero
    where a token did not choose an expert."""
    def one(e, acc):
        y = swiglu(u, jax.lax.dynamic_index_in_dim(gate_up, e, 0, False),
                   jax.lax.dynamic_index_in_dim(down, e, 0, False))
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return acc + w[:, None].astype(u.dtype) * y

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(u))


def moe(u, p, cfg, held, rows=None, program_logits=None, identity=True):
    """The expert branch on normed rows u [n, hidden] for the chip that
    holds real experts ``held = (first, count)`` (``p["gate_up"]`` [count,
    ..]): ``(s, router logits [n, E + Z], near-tie report)``.  ``identity``
    False leaves the identity picks' term out (the shares of a layer count
    it once)."""
    logits = u @ p["router"].astype(u.dtype)
    u = _at("product", u)               # (the router read it whole)
    weights, report = route(logits, p["bias"], cfg, rows, program_logits)
    first, count = held
    if p["gate_up"].shape[0] != count or first + count > real_experts(cfg):
        raise ValueError(f"{p['gate_up'].shape[0]} expert matrices for a "
                         f"share of {count} from {first} of "
                         f"{real_experts(cfg)} real experts")
    s = held_experts(u, weights, p["gate_up"], p["down"], first)
    if identity:
        w_zero = weights[:, real_experts(cfg):].sum(-1, keepdims=True)
        s = s + (w_zero * u.astype(jnp.float32)).astype(u.dtype)
    return s, logits, report


def layer(x, p, cfg, cos, sin, held, rows=None, program_logits=None,
          join="after_second_ffn"):
    """One published layer: ``(y, router logits, near-tie report)``.
    ``join``: where the branch joins the stream; anything but the model's
    "after_second_ffn" is a planted fault's reading."""
    if join not in JOINS:
        raise ValueError(f"join is one of {JOINS}")
    eps = cfg["rms_norm_eps"]
    first, second = p["sub"]
    a = _at("residual",
            x + mla(_norm(x, first["ln_in"], eps), first, cfg, cos, sin))
    u = _norm(a, first["ln_post"], eps)
    s, logits, report = moe(u, p, cfg, held, rows, program_logits)
    b = a + swiglu(_at("product", u), first["gate_up"], first["down"])
    if join == "before_second_sublayer":
        b = b + s
    b = _at("residual", b)
    c = _at("residual",
            b + mla(_norm(b, second["ln_in"], eps), second, cfg, cos, sin))
    y = c + swiglu(_at("product", _norm(c, second["ln_post"], eps)),
                   second["gate_up"], second["down"])
    if join == "after_second_ffn":
        y = y + s
    return _at("residual", y), logits, report


def forward(params: dict, token_ids, cfg: dict, rows=None,
            program_router=None, dtype=jnp.float32, keep_router=False,
            held=None, join="after_second_ffn"):
    """Logits ``[len(rows) or n, vocab]`` of one sequence.  With
    ``program_router`` [R, L, E + Z] (the program's router logits of
    ``rows``, one entry a published layer) also the near-tie report ``[L,
    4]``; with ``keep_router`` instead its own router logits of ``rows``,
    [R, L, E + Z].  ``held``: the real experts held (default: the
    configuration's).  ``dtype``: the precision of every activation and
    product (float32; the bfloat16 control passes the other; the router's
    softmax is float32 either way)."""
    held = held_range(cfg) if held is None else held
    ids = jnp.asarray(token_ids, jnp.int32)
    if rows is not None:
        rows = jnp.asarray(rows)
    reports, routers = [], []
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"].astype(dtype)[ids])
        cos, sin = _tables(cfg, ids.shape[0], dtype)
        for i, p in enumerate(params["layers"]):
            x, logits, report = layer(
                x, p, cfg, cos, sin, held, rows,
                None if program_router is None else program_router[:, i],
                join)
            if keep_router:
                routers.append(logits[rows])
            if report is not None:
                reports.append(report)
        x = _at("product", _norm(x, params["ln_f"], cfg["rms_norm_eps"]))
        if rows is not None:
            x = x[rows]
        out = x @ params["head"].astype(dtype)
    if keep_router:
        return out, jnp.stack(routers, axis=1)
    return (out, jnp.stack(reports)) if reports else out

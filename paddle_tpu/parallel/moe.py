"""Expert parallelism: Switch-style gated MoE over an `ep` mesh axis.

New capability (SURVEY.md §2.6 TP/EP/CP/SP row — absent in the reference
vintage, required for the quartet). Design follows the TPU lineage
(Switch Transformer / GShard): top-1 gating, per-expert capacity
C = ceil(tokens/E * capacity_factor), dispatch/combine as one-hot
einsums, and token exchange as a single `lax.all_to_all` pair over the
`ep` axis inside shard_map — the collectives ride ICI. Under GSPMD
(build_sharded_step) the same math runs dense with expert weights
physically sharded over `ep` via `moe_rules`, and XLA inserts the
equivalent collectives from the annotations.

Overflowed tokens (beyond an expert's capacity) contribute zero from the
expert path — callers keep the residual connection so dropped tokens
pass through, exactly the Switch semantics.

Monitor stats: ``collective_all_to_all_calls`` /
``collective_psum_calls`` count collective ops emitted at trace time
(per program build) on the explicit shard_map path.
"""
from __future__ import annotations

import logging
from typing import Optional

import numpy as np

from ..monitor import monitor as _monitor
from ..monitor import stat_add, stat_add_per_device
from .mesh import EP_AXIS

logger = logging.getLogger(__name__)


def moe_ffn_tokens(x, gate_w, w1, b1, w2, b2, *,
                   capacity_factor: float = 1.25,
                   axis_name: Optional[str] = None,
                   activation: str = "gelu"):
    """Top-1 MoE FFN over flat tokens.

    x [N, H]; gate_w [H, E]; w1 [E, H, I]; b1 [E, I]; w2 [E, I, H];
    b2 [E, H]. Returns (out [N, H], aux_loss scalar, expert_counts [E]).

    With `axis_name` bound (shard_map over `ep`): N is the per-device
    token count; experts are partitioned E/ep per device (each device
    computes with its own slice of the expert weights) and tokens move
    via all_to_all. Without it: dense single-participant math.
    """
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    N, H = x.shape
    E = gate_w.shape[1]
    xf = x.astype("float32")
    logits = xf @ gate_w.astype("float32")
    probs = jax.nn.softmax(logits, axis=-1)              # [N, E]
    expert = jnp.argmax(probs, axis=-1)                  # top-1
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]
    onehot = jax.nn.one_hot(expert, E, dtype="float32")  # [N, E]

    # load-balancing auxiliary loss (Switch eq. 4): E * sum_e f_e * P_e
    frac = onehot.mean(0)
    mean_prob = probs.mean(0)
    aux = E * jnp.sum(frac * mean_prob)

    # capacity-factor padding: rank of each token within its expert
    C = max(1, int(np.ceil(N / E * capacity_factor)))
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot    # [N, E]
    keep = (pos < C) & (onehot > 0)
    pos_oh = (jax.nn.one_hot(pos.astype("int32"), C, dtype="float32")
              * keep[..., None].astype("float32"))       # [N, E, C]

    dispatched = jnp.einsum("nec,nh->ech", pos_oh, xf)   # [E, C, H]

    def ffn(tokens, w1_, b1_, w2_, b2_):
        h = jnp.einsum("ech,ehi->eci", tokens, w1_.astype("float32"))
        h = h + b1_.astype("float32")[:, None, :]
        if activation == "gelu":
            h = jax.nn.gelu(h)
        elif activation == "relu":
            h = jnp.maximum(h, 0)
        out = jnp.einsum("eci,eih->ech", h, w2_.astype("float32"))
        return out + b2_.astype("float32")[:, None, :]

    if axis_name:
        ep = lax.psum(1, axis_name)                      # axis size
        stat_add("collective_psum_calls")
        stat_add("collective_all_to_all_calls", 2)  # dispatch + combine
        # per-shard attribution (ep is concrete at trace time — it
        # sizes the expert slice below)
        stat_add_per_device("collective_psum_calls", ep)
        stat_add_per_device("collective_all_to_all_calls", ep, 2)
        el = E // ep                                     # local experts
        me = lax.axis_index(axis_name)
        # each device keeps its expert slice of the (replicated-in-
        # shard_map) weights; GSPMD legs shard them physically instead
        sl = lambda w: lax.dynamic_slice_in_dim(w, me * el, el, axis=0)
        # exchange: split experts across devices, gather every peer's
        # tokens for MY experts along the capacity axis
        expert_in = lax.all_to_all(dispatched, axis_name,
                                   split_axis=0, concat_axis=1,
                                   tiled=True)           # [el, ep*C, H]
        expert_out = ffn(expert_in, sl(w1), sl(b1), sl(w2), sl(b2))
        combined = lax.all_to_all(expert_out, axis_name,
                                  split_axis=1, concat_axis=0,
                                  tiled=True)            # [E, C, H]
    else:
        combined = ffn(dispatched, w1, b1, w2, b2)

    out = jnp.einsum("nec,ech->nh", pos_oh, combined)
    out = out * gate[:, None]
    counts = onehot.sum(0)
    return out.astype(x.dtype), aux.astype("float32"), counts


def moe_rules(mesh, axis: str = EP_AXIS, inner=None):
    """GSPMD rule table for expert weights: 3-D+ params whose leading
    dim divides the `ep` axis shard over it (expert dim first); other
    params fall through to `inner` (e.g. megatron_rules). Compose:
    ``moe_rules(mesh, inner=megatron_rules(mesh))``."""
    from jax.sharding import PartitionSpec as P

    from .sharded import ShardingRules

    size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
    inner_fn = getattr(inner, "_fn", None) or (lambda name, shape: None)

    def fn(name, shape):
        if (size > 1 and shape and len(shape) >= 3
                and "moe" in name and shape[0] % size == 0):
            return P(*([axis] + [None] * (len(shape) - 1)))
        return inner_fn(name, shape)

    return ShardingRules(fn)


# ---------------------------------------------------------------------------
# dropless top-k routing with gated experts (the serving path's expert FFN)
# ---------------------------------------------------------------------------

def group_keep(scores, n_group: int, topk_group: int):
    """Group-limited selection's first step: ``scores`` [N, E] in
    ``n_group`` groups of consecutive experts, a group scored by the MAX of
    its experts; the ``topk_group`` best groups of a row are kept (ties to
    the lower index).  Returns the mask [N, n_group]."""
    import jax
    import jax.numpy as jnp

    n = scores.shape[0]
    best = scores.reshape(n, n_group, -1).max(axis=-1)
    _, kept = jax.lax.top_k(best, topk_group)
    return jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], kept].set(True)


def route_top_k(router_x, router_w, top_k: int, score: str = "softmax",
                expert_bias=None, norm_topk: bool = True,
                route_scale: float = 1.0, n_group: int = 1,
                topk_group: int = 1):
    """Router logits and the top-k choice per token.

    router_x [N, H] (whatever the architecture routes from — it need not
    be the experts' input), router_w [H, E].  Logits are float32 at
    "highest" precision: routing is discrete, and a rounded logit flips
    a choice.  Returns ``(logits [N, E], experts [N, k] int32, weights
    [N, k])``.

    ``score`` "softmax": the k largest logits, weighted by a softmax
    over the k selected, which equals softmax over all E, select,
    renormalise (``norm_topk`` False leaves the softmax over all E as it
    is).  ``score`` "sigmoid": every expert is scored on its own, ``s =
    sigmoid(logits)``; the k largest of ``s + expert_bias`` are chosen
    (``expert_bias`` [E] float32 or None; ties to the lower index), and
    the weights are the UNBIASED ``s`` of the chosen, with ``norm_topk``
    divided by their sum plus 1e-6.  Either way the weights are scaled
    by ``route_scale``.  The logits returned are always the raw ones.

    ``n_group`` > 1 (``score`` "softmax"): group-limited greedy selection
    (DeepSeek-V2's device-limited routing).  ``s = softmax(logits)`` over
    all E; the E experts lie in ``n_group`` groups of consecutive indices
    and only the ``topk_group`` best groups (:func:`group_keep`) keep
    their scores, the others read 0; the ``top_k`` largest of what is left
    are the token's experts, weighted by their ``s`` (with ``norm_topk``
    divided by their sum plus 1e-20).

    ``score`` "softmax" WITH ``expert_bias``: :func:`_softmax_biased`, the
    k largest of ``softmax(logits) + expert_bias`` weighted by their
    unbiased softmax over all E.  Where the last Z of the router's outputs
    are identity experts (:func:`moe_routed_tokens`' ``zero_experts``) they
    are scored and picked as any other: a pick ``e >= E - Z`` is one."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(router_x.astype(jnp.float32),
                     router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if n_group > 1:
        if score != "softmax" or expert_bias is not None:
            raise ValueError("group-limited selection is built over softmax "
                             "scores without a selection bias")
        s = jax.nn.softmax(logits, axis=-1)
        keep = jnp.repeat(group_keep(s, n_group, topk_group),
                          logits.shape[1] // n_group, axis=1)
        weights, experts = jax.lax.top_k(jnp.where(keep, s, 0.0), top_k)
        if norm_topk:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    elif score == "softmax" and expert_bias is not None:
        experts, weights = _softmax_biased(logits, expert_bias, top_k,
                                           norm_topk)
    elif score == "softmax":
        top, experts = jax.lax.top_k(logits, top_k)
        weights = jax.nn.softmax(top, axis=-1) if norm_topk else jnp.exp(
            top - jax.nn.logsumexp(logits, axis=-1, keepdims=True))
    elif score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            s if expert_bias is None
            else s + expert_bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(s, experts, axis=-1)
        if norm_topk:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    else:
        raise ValueError(f"unknown router score {score!r}")
    if route_scale != 1.0:
        weights = weights * route_scale
    return logits, experts.astype(jnp.int32), weights


ROW_TILE = 64     # the row tile XLA:TPU's ragged-dot kernel picks here
RUN_ROWS = 3 * ROW_TILE   # ... for a call of up to this many rows

_LOWERED = {"pallas": _monitor.get("grouped_matmul_lowered_pallas"),
            "ragged_dot": _monitor.get("grouped_matmul_lowered_ragged_dot")}
_downgrades_logged = set()


def _downgrade(reason):
    if reason not in _downgrades_logged:
        _downgrades_logged.add(reason)
        logger.warning("the grouped expert matmul lowered to "
                       "jax.lax.ragged_dot on a TPU backend, not the Pallas "
                       "kernel: %s", reason)


def _one_call(rows, weights, group_sizes, precision):
    import jax
    import jax.numpy as jnp

    # whole row tiles: on a TPU v5e the kernel gave garbage for 12 rows
    # (two slots x top-6: the benchmark's check engine) and the right
    # product for 18, 192 and 3072 (my chip run, PR 28).  Rows past
    # ``group_sizes.sum()`` belong to no group and are cut off again
    m = rows.shape[0]
    pad = -m % ROW_TILE
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = jax.lax.ragged_dot(rows, weights, group_sizes,
                             precision=precision,
                             preferred_element_type=rows.dtype)
    return out[:m] if pad else out


def grouped_matmul(rows, weights, group_sizes, precision=None,
                   mesh_devices=1, row_scale=None, gate=None, act=None):
    """``rows`` [M, K], sorted by group, times ``weights`` [G, K, N]:
    row ``r`` of group ``g`` is multiplied by ``weights[g]``.  ``gate``
    ``(activation, limit)``: the result is :func:`_gated` of the product,
    [M, N // 2] (``act``: :func:`_activation` of it, [M, N]); ``row_scale``
    [M]: row ``r`` of it times ``row_scale[r]``.
    One formulation per shape (README "Routed experts"):

    * float32 at "highest" on a TPU, one device, at least one row block
      of rows: the Pallas kernel of ``ops/pallas/grouped_matmul.py``
      (``tiles``: 64 rows by the widest column block that fits).  It
      visits only the (row block, group) pairs that hold rows and reads a
      group's weights once a column block.  The gate (where a column
      block is all of N, ``gate_fits``) and the scale are epilogues on its
      accumulator there, so neither [M, N] array gets a pass of its own
      (:func:`_with_epilogue`; README "Routed experts" has the times).
    * everything else (off a TPU, another dtype or precision, fewer rows
      than a row block, under a mesh of more than one device: on a TPU that
      last is a downgrade and is logged once): one
      ``jax.lax.ragged_dot`` call (XLA:TPU: a grouped Mosaic kernel that
      pays a whole tile for every group with a row; the CPU: a masked
      dense product), the gate and the scale after it in ``jnp``.

    ``grouped_matmul_lowered_pallas`` / ``..._ragged_dot`` count, per program
    build, which a product lowered to (``_held_share``'s runs call it too);
    ``grouped_matmul_epilogue_gate`` / ``_act`` / ``_scale`` the epilogues."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import grouped_matmul as pallas

    (m, k), n = rows.shape, weights.shape[2]
    tiles = pallas.tiles(m, k, n)
    kernel = (tiles is not None and jax.default_backend() == "tpu"
              and rows.dtype == weights.dtype == jnp.float32
              and precision == jax.lax.Precision.HIGHEST)
    if kernel and mesh_devices > 1:
        kernel = False
        _downgrade(f"grouped_matmul under a {mesh_devices}-device mesh")
    _LOWERED["pallas" if kernel else "ragged_dot"].increase()
    if kernel and (row_scale is not None or gate is not None or act):
        return _with_epilogue(rows, weights, group_sizes, tiles, row_scale,
                              gate, act)
    if kernel:
        return pallas.grouped_matmul(rows, weights, group_sizes,
                                     tm=tiles[0], tn=tiles[1])
    return _after(_one_call(rows, weights, group_sizes, precision),
                  row_scale, gate, act)


def _gated(h, inter, activation, limit=None):
    """``act(gate) * up`` of the fused first product's columns; with
    ``limit`` L the gate is held under L and the up to [-L, L] first."""
    import jax
    import jax.numpy as jnp

    gate, up = h[:, :inter], h[:, inter:]
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    if activation == "relu":
        gate = jnp.maximum(gate, 0)
    elif activation == "silu":
        gate = jax.nn.silu(gate)
    else:
        raise ValueError(f"unknown expert activation {activation!r}")
    return gate * up


# ``_held_share`` (under ``moe_routed_tokens``) multiplies the held pairs in
# runs.  ``RUN_ROWS`` above is the longest where its products are
# ``jax.lax.ragged_dot`` calls: XLA:TPU picks a 64-row tile for a call of up
# to 192 rows and a 512-row one after.  The Pallas kernel has no such cliff,
# and there ``held_run`` sizes a run to the pairs expected, up to:
KERNEL_RUN_ROWS = 1024
# A run's results are added to their tokens' rows in pieces of so many
# rows: from 192-512 rows on XLA:TPU's scatter-add sorts the indices and
# takes the whole accumulator through VMEM, 1.6 ms for 512 rows of 7168
# where four pieces take 0.4 (my chip run, PR 52)
SCATTER_ROWS = 128


def held_run(pairs, held, experts, kernel):
    """Sorted pairs a trip of :func:`_held_share`'s loop, from what the
    program's shapes say: ``pairs`` = N * top_k token-expert pairs, of
    which a share ``held / experts`` is expected here.

    Where the two products are ``jax.lax.ragged_dot`` calls (``kernel``
    False), ``RUN_ROWS``: the most rows for which XLA:TPU picks its 64-row
    tile, so a trip pays a tile for each of the two or three groups it
    touches and not a 512-row one.  Where they are the Pallas kernel,
    which visits only the row blocks that hold rows, a run's empty tail
    costs its share of the gather and the gate and no visit, and a run's
    end inside a group makes the next trip read that group's weights
    again: the run is the expected held pairs and half as many again, in
    whole row blocks, up to ``KERNEL_RUN_ROWS`` (a step, a chunk or a
    rung of up to 2048 rows of the three published shares is one trip;
    the widest rung reads a group about once).  On a v5e, a layer's held
    share, PR 43's runs of 192 through ``ragged_dot`` -> these (my chip
    run, PR 52; ``tools/moe_microbench.py --held 1``): 20 of 320 experts
    of 1280 over 4096 at a step of 64 rows 1.67 -> 1.41 ms, at rungs of
    512 / 1024 / 4096 rows 2.58 -> 2.06, 3.29 -> 2.46, 7.70 -> 5.48; 8 of
    256 of 2048 over 7168 at a step of 32 rows 1.41 -> 1.25, at rungs of
    512 / 1024 / 2048 2.87 -> 2.30, 4.42 -> 2.81, 4.79 -> 4.04; 8 of 128
    of 4096 over 4096 at a step of 10 rows 1.39 -> 0.87, at a chunk of
    1024 5.10 -> 4.02."""
    whole = -(-pairs // ROW_TILE) * ROW_TILE
    if not kernel:
        return min(RUN_ROWS, whole)
    expected = -(-3 * pairs * held // (2 * experts))
    return min(max(-(-expected // ROW_TILE) * ROW_TILE, ROW_TILE),
               KERNEL_RUN_ROWS, whole)


def moe_routed_tokens(x, router_x, router_w, w_gate_up, w_down, *,
                      top_k: int, activation: str = "relu", valid=None,
                      precision=None, score: str = "softmax",
                      expert_bias=None, norm_topk: bool = True,
                      route_scale: float = 1.0, held_first=None,
                      limit=None, mesh_devices: int = 1, n_group: int = 1,
                      topk_group: int = 1, zero_experts: int = 0):
    """Dropless top-k mixture of gated experts over flat tokens.

    x [N, H] is the experts' input, router_x [N, H] what the router reads;
    router_w [H, E]; w_gate_up [E, H, 2I] (gate columns first); w_down [E, I,
    H].  Every token goes to its k experts: nothing dropped.  Pairs are sorted
    by expert and the two grouped matmuls read only experts that got rows.
    ``valid`` [N] bool marks the real tokens: the pairs of a row behind it (a
    rung's pad tail, an idle slot) sort past the last group, no product visits
    them and its ``out`` is 0.  Between the sort and ``out`` the layer writes
    to HBM the gathered rows [N k, H], the GATED first product [N k, I], the
    second product [N k, H] under its routing weight (both epilogues of the
    kernel, :func:`grouped_matmul`) and their gather back (:func:`_combine`):
    64 x 2560 x 768, top 6, rung 4096 of 2,900 real rows 16.70 ms (PR 56) ->
    11.22, 8192 of 5,800 27.94 -> 20.17 (v5e, PR 57).  :func:`_acted`.

    ``held_first`` (one chip's share of an expert-parallel group): the
    weights are those of experts ``held_first .. held_first + w_gate_up
    .shape[0] - 1`` alone.  The router keeps its width E, its ``top_k``
    and the weights' normalisation over all ``top_k`` chosen; only the
    pairs whose expert is held here are multiplied (:func:`_held_share`:
    a pad-tail row's or an idle slot's pairs are not), and ``out`` is
    that part of the layer's sum: what the absent experts would add is
    left out, and nothing stands in for the chips that hold them.

    Returns ``(out [N, H], counts [E] int32, logits [N, E])``; ``counts``
    are the group sizes both grouped matmuls ran with, the valid rows'
    pairs alone, so ``counts.sum() == valid.sum() * k`` proves no token
    was dropped.  Under ``held_first`` they are the pairs routed to each
    of the E experts, and their slice over the held experts is what the
    matmuls ran with.

    ``zero_experts`` Z: the LAST Z of the router's E outputs are identity
    ("zero-computation") experts, which have no weights and return their
    input: such a pick takes one of the token's ``top_k`` places and its
    routing weight, reaches no dispatch and no product, and the row gets
    ``(sum of its identity picks' weights) * x`` (:func:`_zero_term`) added
    once.  The real experts are ``0 .. E - Z - 1`` (``held_first``'s range
    lies among them; without it all of them are held, at index 0), and
    ``counts`` stay [E]: the last Z are the identity picks."""
    import jax
    import jax.numpy as jnp

    N, H = x.shape
    E = router_w.shape[1]
    inter = w_down.shape[1]
    logits, experts, weights = route_top_k(
        router_x, router_w, top_k, score, expert_bias, norm_topk,
        route_scale, n_group, topk_group)
    if zero_experts and held_first is None:
        held_first = 0
    if held_first is not None:
        held = w_gate_up.shape[0]
        pair_valid = jnp.ones((N, 1), bool) if valid is None \
            else valid[:, None]
        here = (experts >= held_first) & (experts < held_first + held) \
            & pair_valid
        out = _held_share(x, jnp.where(here, experts - held_first, held),
                          weights, w_gate_up, w_down, activation, precision,
                          limit, mesh_devices, E)
        if zero_experts:
            out = out + _zero_term(x, experts >= E - zero_experts, weights,
                                   pair_valid)
        counts = jnp.zeros((E,), jnp.int32).at[experts.reshape(-1)].add(
            jnp.broadcast_to(pair_valid, experts.shape).reshape(-1)
            .astype(jnp.int32))
        return out, counts, logits
    flat = experts.reshape(-1)                          # [N*k]
    if valid is not None:   # a row behind it: its pairs past the last group
        flat = jnp.where(jnp.repeat(valid, top_k), flat, E)
    order = jnp.argsort(flat, stable=True)              # rows by expert
    group_sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    rows = jnp.take(x, order // top_k, axis=0, mode="clip")   # [N*k, H]
    # no [N*k, .] array gets a pass of its own where the products are the
    # kernel: the gate (or, for experts of two matrices, the activation) is
    # the first one's epilogue and the routing weight the second one's
    act = grouped_matmul(
        rows, w_gate_up.astype(x.dtype), group_sizes, precision, mesh_devices,
        **_first_epilogue(w_gate_up.shape[2], inter, activation, limit))
    y = grouped_matmul(act, w_down.astype(x.dtype), group_sizes, precision,
                       mesh_devices,
                       row_scale=jnp.take(weights.reshape(-1), order))
    # back to token order by one gather-sum.  With ``valid`` the rows of y
    # past the groups hold what lay in memory: a pad row's sum is selected
    # to 0, never multiplied by it
    return (_combine(y, order, top_k, valid).astype(x.dtype), group_sizes,
            logits)


def _scoped_tiles(rows, weights, precision, mesh_devices):
    """The Pallas kernel's blocks for a product of :func:`_held_share`, or
    None where :func:`grouped_matmul` would not send it to the kernel (its
    own condition, asked ahead of the call; ``rows`` may be a shape with a
    dtype).  The blocks are the ``scoped`` ones, which stay inside the
    default scoped VMEM (``pallas.block_bytes`` says why)."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import grouped_matmul as pallas

    if not (jax.default_backend() == "tpu" and mesh_devices == 1
            and rows.dtype == weights.dtype == jnp.float32
            and precision == jax.lax.Precision.HIGHEST):
        return None
    return pallas.tiles(rows.shape[0], rows.shape[1], weights.shape[2],
                        scoped=True)


def _held_matmul(rows, weights, group_sizes, precision, mesh_devices):
    """:func:`grouped_matmul` for a run of :func:`_held_share`: its route
    and its counters, and on the kernel :func:`_scoped_tiles`' blocks."""
    from ..ops.pallas import grouped_matmul as pallas

    tiles = _scoped_tiles(rows, weights, precision, mesh_devices)
    if tiles is None:
        return grouped_matmul(rows, weights, group_sizes, precision,
                              mesh_devices)
    _LOWERED["pallas"].increase()
    return pallas.grouped_matmul(rows, weights, group_sizes, tm=tiles[0],
                                 tn=tiles[1], scoped=True)


def _held_share(x, local, weights, w_gate_up, w_down, activation,
                precision, limit=None, mesh_devices=1, experts=None,
                run=None):
    """The held experts' part of the layer: ``local`` [N, k] is each
    pair's index among the experts held here, or ``held`` (= ``w_gate_up``
    's leading size) where its expert lives on another chip (one of the
    router's ``experts``).  The pairs are sorted with the held ones first,
    and only the runs of ``run`` sorted pairs (:func:`held_run`, unless
    given) that hold a held one are gathered, multiplied
    (:func:`_held_matmul`: the Pallas kernel on one TPU device at
    float32 "highest") and added to their tokens' rows (a loop whose trip
    count the routing gives): an absent expert's pair costs no row of
    either matmul and adds nothing, and no [N * k, H] array is made.
    A run longer than ``SCATTER_ROWS`` is added in pieces of that many
    rows, those that hold a held pair alone: XLA:TPU's scatter-add of
    more rows at once sorts them and takes the whole of ``out`` through
    VMEM, 1.6 ms for 512 rows of 7168 where four pieces take 0.4 (my chip
    run, PR 52).  Returns ``out`` [N, H]."""
    import jax
    import jax.numpy as jnp

    N, H = x.shape
    top_k = local.shape[1]
    held, inter = w_gate_up.shape[0], w_down.shape[1]
    w_gate_up, w_down = w_gate_up.astype(x.dtype), w_down.astype(x.dtype)
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)              # held pairs first
    sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts, n_held = ends - sizes, ends[-1]
    if run is None:
        block = jax.ShapeDtypeStruct((ROW_TILE, H), x.dtype)
        run = held_run(
            N * top_k, held, experts or held,
            None not in (
                _scoped_tiles(block, w_gate_up, precision, mesh_devices),
                _scoped_tiles(block.update(shape=(ROW_TILE, inter)), w_down,
                              precision, mesh_devices)))
    order = jnp.pad(order, (0, -order.shape[0] % run))
    pair_w = weights.reshape(-1)

    def body(i, out):
        lo = i * run
        pairs = jax.lax.dynamic_slice_in_dim(order, lo, run)
        real = lo + jnp.arange(run) < n_held
        tok = pairs // top_k
        rows = jnp.take(x, tok, axis=0)                 # [run, H]
        size = jnp.clip(jnp.minimum(ends, lo + run) - jnp.maximum(starts, lo),
                        0, None).astype(jnp.int32)
        h = _held_matmul(rows, w_gate_up, size, precision, mesh_devices)
        y = _held_matmul(_acted(h, inter, activation, limit), w_down, size,
                         precision, mesh_devices)
        y = y * jnp.take(pair_w, pairs)[:, None].astype(y.dtype)
        # rows past the held pairs belong to no group: whatever the
        # kernel left there is dropped, not scaled
        y = jnp.where(real[:, None], y, 0)
        if run <= SCATTER_ROWS:
            return out.at[tok].add(y)

        def piece(c, out):
            # the last piece of a run that is not whole pieces starts
            # early, and the rows it shares with the one before add 0
            at = jnp.minimum(c * SCATTER_ROWS, run - SCATTER_ROWS)
            fresh = at + jnp.arange(SCATTER_ROWS) >= c * SCATTER_ROWS
            return out.at[
                jax.lax.dynamic_slice_in_dim(tok, at, SCATTER_ROWS)].add(
                jnp.where(fresh[:, None], jax.lax.dynamic_slice_in_dim(
                    y, at, SCATTER_ROWS), 0))

        return jax.lax.fori_loop(
            0, -(-jnp.minimum(run, n_held - lo) // SCATTER_ROWS), piece, out)

    return jax.lax.fori_loop(0, -(-n_held // run), body,
                             jnp.zeros((N, H), x.dtype))


# ---------------------------------------------------------------------------
# PR 57: the routed layer (``moe_routed_tokens`` without ``held_first``)
# between and after its two products.  Kept down here so that every line
# above stays where PR 56 had it: a Mosaic call's serialised body carries its
# call sites' file and line, and the held experts' programs are the parent's
# (``tools/moe_layer_hash.py``).
# ---------------------------------------------------------------------------

_FUSED = {"gate": _monitor.get("grouped_matmul_epilogue_gate"),
          "act": _monitor.get("grouped_matmul_epilogue_act"),
          "scale": _monitor.get("grouped_matmul_epilogue_scale"),
          "combine": _monitor.get("moe_combine_gather")}


def _after(out, row_scale, gate=None, act=None):
    """:func:`grouped_matmul`'s gate (or activation) and scale as passes of
    their own over ``out``, where the kernel does not take them as
    epilogues."""
    if gate is not None:
        out = _gated(out, out.shape[1] // 2, *gate)
    if act:
        out = _activation(out, act)
    if row_scale is not None:
        out = out * row_scale[:, None].astype(out.dtype)
    return out


def _with_epilogue(rows, weights, group_sizes, tiles, row_scale, gate,
                   act=None):
    """:func:`grouped_matmul` on the Pallas kernel (blocks ``tiles``) with
    ``gate`` (or ``act``) and ``row_scale`` as epilogues on its
    accumulator.  A gate whose columns are not one block (``gate_fits``)
    leaves the kernel's plain call and :func:`_after` to do both; an
    activation is a column's own and fits any block."""
    import functools

    from ..ops.pallas import grouped_matmul as pallas

    tm, tn = tiles
    if gate is not None and not pallas.gate_fits(weights.shape[2], tn):
        return _after(pallas.grouped_matmul(rows, weights, group_sizes,
                                            tm=tm, tn=tn), row_scale, gate)
    if gate is not None:
        _FUSED["gate"].increase()
        gate = functools.partial(_gated, inter=weights.shape[2] // 2,
                                 activation=gate[0], limit=gate[1])
    elif act:
        _FUSED["act"].increase()
        act = functools.partial(_activation, activation=act)
    if row_scale is not None:
        _FUSED["scale"].increase()
    return pallas.grouped_matmul_epilogue(
        rows, weights, group_sizes, row_scale, tm=tm, tn=tn, gate=gate,
        act=act or None)


def _combine(y, order, top_k, valid=None):
    """A token's k results, summed in the order j = 0 .. k - 1: ``y`` [N k,
    H] holds the scaled results in sorted order, row ``r`` that of pair
    ``order[r]`` = n k + j.  ``where[j N + n]`` is the row of pair (n, j) (a
    scatter of N k int32), ONE gather of y's rows by it writes the planes
    [k, N, H], plane j whole before plane j + 1, and the k-sum runs over the
    leading axis, whole rows added to whole rows, where PR 28 to PR 56 wrote
    [N k, H] of zeros, scattered y into them and reduced [N, k, H] over its
    middle axis, k = 6 rows in a sublane tile of 8 (3.9 + 1.5 ms at 64 x
    2560 x 768, top 6, rung 4096, against 1.7 for this; my chip run, PR 57,
    ``tools/moe_microbench.py --pieces 1``).  The indices are in bounds
    and said to be (``mode="clip"``): the default fills what an index out of
    bounds would read, a select over the whole result.  With ``valid`` the
    pairs of a row behind it point past the groups, at rows of y that hold
    what lay in memory (NaN for all anyone knows): its sum is selected to
    0."""
    import jax.numpy as jnp

    _FUSED["combine"].increase()
    pairs = order.shape[0]
    n = pairs // top_k
    where = jnp.zeros((pairs,), jnp.int32).at[
        (order % top_k) * n + order // top_k].set(
        jnp.arange(pairs, dtype=jnp.int32))
    out = jnp.take(y, where, axis=0, mode="clip").reshape(
        top_k, n, -1).sum(axis=0)
    return out if valid is None else jnp.where(valid[:, None], out, 0)


# ---------------------------------------------------------------------------
# PR 63: experts of TWO matrices, ``W2 act(W1 u)`` (no gate matrix): their
# first stack is [E, H, I], not [E, H, 2I], which is how the functions above
# tell them apart.  Down here for PR 57's reason.
# ---------------------------------------------------------------------------

def _activation(h, activation):
    """``relu(h) ** 2``, elementwise: "relu2", the one activation experts
    of two matrices have."""
    import jax.numpy as jnp

    if activation != "relu2":
        raise ValueError(f"experts without a gate take the activation "
                         f"'relu2', not {activation!r}")
    h = jnp.maximum(h, 0)
    return h * h


def _first_epilogue(cols, inter, activation, limit):
    """:func:`grouped_matmul`'s epilogue for a layer's first product of
    ``cols`` columns over experts of width ``inter``: the gate (gate | up,
    2 inter columns), or the activation alone, which no ``limit`` clamps."""
    if cols != inter:
        return {"gate": (activation, limit)}
    if limit is not None:
        raise ValueError("a clamp (limit) is the gated experts'")
    return {"act": activation}


def _acted(h, inter, activation, limit=None):
    """What an expert's second matrix reads, from its first product ``h``
    [M, 2 inter] or [M, inter]: the same epilogue, as a pass of its own."""
    return _after(h, None,
                  **_first_epilogue(h.shape[1], inter, activation, limit))


# ---------------------------------------------------------------------------
# PR 66: a router whose last outputs are identity experts, chosen by softmax
# plus a selection bias.  Down here for PR 57's reason.
# ---------------------------------------------------------------------------

def _softmax_biased(logits, expert_bias, top_k, norm_topk):
    """:func:`route_top_k`'s third form: ``p = softmax(logits)`` over all E;
    the ``top_k`` largest of ``p + expert_bias`` are chosen (ties to the
    lower index); the weights are their UNBIASED ``p``, with ``norm_topk``
    divided by their sum plus 1e-20.  Returns ``(experts, weights)``."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(p + expert_bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(p, experts, axis=-1)
    if norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return experts, weights


def _zero_term(x, zero, weights, pair_valid):
    """What a row's identity picks add: ``(sum of their weights) * x`` in
    float32, once a row.  ``zero`` [N, k] marks the picks, ``pair_valid``
    [N, 1] the real rows: a row behind it is selected to 0, never
    multiplied."""
    import jax.numpy as jnp

    w = jnp.where(zero, weights.astype(jnp.float32), 0.0) \
        .sum(axis=-1, keepdims=True)
    return jnp.where(pair_valid, w * x.astype(jnp.float32), 0.0) \
        .astype(x.dtype)

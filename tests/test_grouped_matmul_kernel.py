"""The Pallas grouped matmul of the experts' products
(``ops/pallas/grouped_matmul.py``) and the route to it
(``parallel/moe.py`` ``grouped_matmul``).

* The kernel in interpret mode against a float64 loop and against
  ``_one_call`` (``jax.lax.ragged_dot``), over the group layouts a sorted
  list of token-expert pairs takes.
* The route: which (M, G, K, N) go to the kernel on a stubbed TPU backend,
  what stays on ``ragged_dot`` there and why, and that the CPU builds what
  it always did.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops.pallas import grouped_matmul as kernel
from paddle_tpu.parallel import moe

HIGHEST = jax.lax.Precision.HIGHEST


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _loop(rows, weights, sizes):
    """Row r of group g times ``weights[g]`` in float64, for the rows that
    belong to a group."""
    ends = np.cumsum(sizes)
    which = np.searchsorted(ends, np.arange(int(ends[-1])), side="right")
    return np.stack([rows[r].astype(np.float64)
                     @ weights[g].astype(np.float64)
                     for r, g in enumerate(which)])


# (m, K, N, tm, tn, group sizes): 8 groups each
LAYOUTS = {
    "even_groups": (128, 16, 128, 16, 128, [16] * 8),
    "skewed_groups": (128, 16, 128, 16, 128,
                      [40, 3, 1, 20, 30, 2, 25, 7]),
    "an_empty_group": (96, 16, 128, 16, 128, [16, 0, 24, 0, 0, 40, 16, 0]),
    "the_first_and_last_groups_empty": (
        64, 16, 128, 16, 128, [0, 10, 20, 4, 20, 6, 4, 0]),
    "a_group_that_straddles_row_blocks": (
        96, 16, 128, 16, 128, [5, 50, 3, 3, 3, 3, 24, 5]),
    "a_short_last_row_block": (100, 16, 128, 32, 128,
                               [12, 13, 12, 13, 12, 13, 12, 13]),
    "rows_past_the_groups": (96, 16, 128, 16, 128,
                             [10, 0, 21, 9, 0, 17, 3, 8]),
    "m_not_a_multiple_of_the_row_block": (
        77, 16, 128, 24, 128, [9, 10, 9, 10, 9, 10, 10, 10]),
    "one_group_holds_every_row": (64, 16, 128, 16, 128,
                                  [0, 0, 0, 64, 0, 0, 0, 0]),
    "a_row_block_of_many_groups": (64, 16, 128, 64, 128,
                                   [8, 1, 15, 2, 14, 3, 13, 8]),
    "two_column_blocks": (96, 24, 256, 16, 128, [12] * 8),
    "the_first_products_widths": (72, 32, 48, 24, 48, [9] * 8),
    "the_second_products_widths": (72, 24, 32, 24, 32, [9] * 8),
}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_is_the_float64_loop_and_the_one_call(layout):
    m, k, n, tm, tn, sizes = LAYOUTS[layout]
    sizes = np.asarray(sizes, np.int32)
    assert sizes.sum() <= m and tm % 8 == 0 and n % tn == 0
    rng = np.random.default_rng(len(layout))
    rows = rng.standard_normal((m, k)).astype(np.float32)
    weights = rng.standard_normal((8, k, n)).astype(np.float32)
    # a group without rows is never read: its weights may hold anything
    weights[sizes == 0] = np.nan
    got = np.asarray(kernel.grouped_matmul(
        jnp.asarray(rows), jnp.asarray(weights), jnp.asarray(sizes),
        tm=tm, tn=tn, interpret=True))
    assert got.shape == (m, n)
    real = int(sizes.sum())
    assert _rel(got[:real], _loop(rows, weights, sizes)) < 1e-5
    one = np.asarray(moe._one_call(
        jnp.asarray(rows), jnp.asarray(np.nan_to_num(weights)),
        jnp.asarray(sizes), None))
    assert _rel(got[:real], one[:real]) < 1e-5


@pytest.mark.parametrize("m,tm,sizes", [
    (128, 16, [16] * 8),                       # every block one group's
    (96, 16, [5, 50, 3, 3, 3, 3, 24, 5]),
    (100, 32, [0, 0, 100, 0, 0, 0, 0, 0]),
    (64, 16, [0, 10, 20, 4, 20, 6, 4, 0]),
    (77, 24, [9, 10, 9, 10, 9, 10, 10, 0]),    # 67 rows in groups
])
def test_visits_are_the_row_block_group_pairs_that_hold_a_row(m, tm, sizes):
    sizes = np.asarray(sizes, np.int32)
    offsets, group, block, n = kernel.visits(jnp.asarray(sizes), m, tm)
    ends = np.cumsum(sizes)
    want = sorted({(r // tm, int(np.searchsorted(ends, r, side="right")))
                   for r in range(int(ends[-1]))})
    n = int(n)
    assert n == len(want) <= group.shape[0] == -(-m // tm) + 7
    assert list(zip(np.asarray(block)[:n].tolist(),
                    np.asarray(group)[:n].tolist())) == want
    assert np.asarray(offsets).tolist() == [0] + ends.tolist()
    # the entries no visit reads still index a block and a group
    assert 0 <= np.asarray(block).min() and np.asarray(block).max() < -(-m // tm)
    assert 0 <= np.asarray(group).min() and np.asarray(group).max() < 8


# ---------------------------------------------------------------------------
# the route
# ---------------------------------------------------------------------------

@pytest.fixture
def as_tpu(monkeypatch):
    """``jax.default_backend()`` answers "tpu": the route takes the kernel
    (traced with ``jax.make_jaxpr``; ``interpreted`` also runs it)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "_downgrades_logged", set())


@pytest.fixture
def interpreted(as_tpu, monkeypatch):
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    kernel.grouped_matmul.clear_cache()
    yield
    kernel.grouped_matmul.clear_cache()


def _eqns(jaxpr, into_kernels=True):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (a Pallas
    kernel's body too, unless ``into_kernels`` is False)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, into_kernels)


def _primitives(jaxpr, into_kernels=True):
    return [eqn.primitive.name for eqn in _eqns(jaxpr, into_kernels)]


def _result_rows(jaxpr, primitive):
    """The leading sizes of the first result of every ``primitive``."""
    return {eqn.outvars[0].aval.shape[0] for eqn in _eqns(jaxpr)
            if eqn.primitive.name == primitive}


def _traced(m, groups, k, n, precision=HIGHEST, dtype=jnp.float32, **kw):
    S = jax.ShapeDtypeStruct
    before = {p: stat_get(f"grouped_matmul_lowered_{p}")
              for p in ("pallas", "ragged_dot")}
    jaxpr = jax.make_jaxpr(
        lambda r, w, s: moe.grouped_matmul(r, w, s, precision, **kw))(
        S((m, k), dtype), S((groups, k, n), dtype), S((groups,), jnp.int32))
    moved = {p: stat_get(f"grouped_matmul_lowered_{p}") - v
             for p, v in before.items()}
    assert jaxpr.out_avals[0].shape == (m, n)
    return _primitives(jaxpr.jaxpr), moved


# the shapes the benchmark's three routed configurations build (rows,
# groups, K, N) and whether the kernel takes them (my chip run, PR 50:
# PERF.md section 6)
SHAPES = [
    # smallthinker-21b-a3b: 64 experts of 768 over 2560, top 6
    (3072, 64, 2560, 1536, True), (3072, 64, 768, 2560, True),
    (6144, 64, 2560, 1536, True), (12288, 64, 768, 2560, True),
    (24576, 64, 2560, 1536, True), (49152, 64, 768, 2560, True),
    (192, 64, 2560, 1536, True), (192, 64, 768, 2560, True),
    (12, 64, 2560, 1536, False),           # the check engine's two slots
    (18, 64, 768, 2560, False),            # ... and its three
    # lfm2-24b-a2b: 64 experts of 1536 over 2048, top 4
    (512, 64, 2048, 3072, True), (16384, 64, 1536, 2048, True),
    (256, 64, 2048, 3072, True),
    # sdar-30b-a3b-chat: 128 experts of 768 over 2048, top 8
    (1024, 128, 2048, 1536, True), (1536, 128, 768, 2048, True),
    (8192, 128, 2048, 1536, True),
    # fewer rows than a row block; columns no lane tile divides, too wide
    (63, 8, 256, 512, False), (4096, 8, 8192, 1000, False),
]


@pytest.mark.parametrize("m,groups,k,n,takes", SHAPES)
def test_route_on_a_tpu_is_chosen_from_the_shape(as_tpu, m, groups, k, n,
                                                 takes):
    prims, moved = _traced(m, groups, k, n)
    assert ("pallas_call" in prims) == takes
    assert ("ragged_dot_general" in prims) == (not takes)
    assert moved == {"pallas": int(takes), "ragged_dot": int(not takes)}
    assert (kernel.tiles(m, k, n) is not None) == takes
    if takes:
        tm, tn = kernel.tiles(m, k, n)
        assert tm == kernel.ROW_BLOCK and n % tn == 0 \
            and (tn % 128 == 0 or tn == n)
        # a block of weights, of rows and of the output, twice each, in
        # well under the kernel's VMEM limit
        assert 8 * (k * tn + tm * k + tm * tn) < 0.6 * kernel.VMEM_LIMIT
        assert "scan" not in prims and "pad" not in prims
        # one small jaxpr a shape: set-up traces and lowers it again in
        # every program that holds it
        assert len(prims) < 150


@pytest.mark.parametrize("m,groups,k,n,takes", SHAPES[:2] + SHAPES[-3:-2])
def test_route_on_the_cpu_is_the_ragged_dot(m, groups, k, n, takes):
    prims, moved = _traced(m, groups, k, n)
    assert "pallas_call" not in prims and "ragged_dot_general" in prims
    assert moved == {"pallas": 0, "ragged_dot": 1}


@pytest.mark.parametrize("why,kw", [
    ("default_precision", {"precision": None}),
    ("bfloat16_operands", {"dtype": jnp.bfloat16, "precision": None}),
])
def test_route_on_a_tpu_leaves_other_arithmetic_alone(as_tpu, caplog, why,
                                                      kw):
    """The kernel is float32 at "highest" and nothing else: another
    precision is not its shape, and no downgrade."""
    with caplog.at_level(logging.WARNING, "paddle_tpu.parallel.moe"):
        prims, moved = _traced(24576, 64, 2560, 1536, **kw)
    assert "pallas_call" not in prims
    assert moved == {"pallas": 0, "ragged_dot": 1}
    assert not caplog.records


def test_route_under_a_mesh_stays_and_says_so_once(as_tpu, caplog):
    with caplog.at_level(logging.WARNING, "paddle_tpu.parallel.moe"):
        for _ in range(2):
            prims, moved = _traced(24576, 64, 2560, 1536, mesh_devices=4)
            assert "pallas_call" not in prims
            assert moved == {"pallas": 0, "ragged_dot": 1}
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "4-device mesh" in said[0]


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_routed_layer_through_the_kernel_is_the_layer(interpreted,
                                                      monkeypatch,
                                                      activation):
    """``moe_routed_tokens`` with both products on the kernel (interpret
    mode; small tiles stand in for the chip's) against a float64 loop."""
    monkeypatch.setattr(kernel, "tiles",
                        lambda m, k, n, scoped=False: (16, n))
    rng = np.random.default_rng(3)
    tokens, hidden, experts, width, top_k = 40, 32, 8, 24, 3
    x = rng.standard_normal((tokens, hidden)).astype(np.float32)
    wr = rng.standard_normal((hidden, experts)).astype(np.float32)
    wgu = rng.standard_normal((experts, hidden, 2 * width)).astype(
        np.float32) * 0.3
    wd = rng.standard_normal((experts, width, hidden)).astype(
        np.float32) * 0.3
    before = stat_get("grouped_matmul_lowered_pallas")
    out, counts, _ = moe.moe_routed_tokens(
        jnp.asarray(x), jnp.asarray(x), wr, wgu, wd, top_k=top_k,
        activation=activation, precision=HIGHEST)
    assert stat_get("grouped_matmul_lowered_pallas") == before + 2
    want = np.zeros((tokens, hidden))
    for t in range(tokens):
        l = x[t].astype(np.float64) @ wr
        top = np.argsort(-l)[:top_k]
        w = np.exp(l[top] - l[top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            gu = x[t].astype(np.float64) @ wgu[e]
            g = gu[:width]
            g = np.maximum(g, 0) if activation == "relu" \
                else g / (1 + np.exp(-g))
            want[t] += we * ((g * gu[width:]) @ wd[e])
    assert _rel(np.asarray(out), want) < 1e-5
    assert int(counts.sum()) == tokens * top_k


# ---------------------------------------------------------------------------
# the held experts' share (``_held_share``, PR 52)
# ---------------------------------------------------------------------------

# the three configurations that serve one chip's share of an expert-
# parallel group: (K, N) of the first and the second product, the column
# block ``tiles`` gives each, and the ``scoped`` one ``_held_share`` runs
HELD_TILES = {
    "solar-open2-250b": ((4096, 2560, 1280, 256), (1280, 4096, 4096, 1024)),
    "gigachat35-432b-a28b": ((7168, 4096, 512, 128),
                             (2048, 7168, 1792, 512)),
    "command-a-plus-05-2026": ((4096, 8192, 1024, 256),
                               (4096, 4096, 1024, 256)),
}


@pytest.mark.parametrize("config", HELD_TILES)
@pytest.mark.parametrize("product", [0, 1])
def test_tiles_of_the_held_shapes_and_a_tail_of_rows_in_no_group(config,
                                                                 product):
    """``tiles`` pinned at the held shapes, wide and ``scoped``; and the
    kernel in interpret mode at the scoped blocks (two column blocks of the
    published K, three groups) over a run whose tail belongs to no group:
    the rows of the groups are the float64 loop's, the tail is left as it
    lies (whatever that is: ``_held_share`` masks it)."""
    k, n, wide, tn = HELD_TILES[config][product]
    for m in (64, 768, 1024):
        assert kernel.tiles(m, k, n) == (kernel.ROW_BLOCK, wide)
        assert kernel.tiles(m, k, n, scoped=True) == (kernel.ROW_BLOCK, tn)
    # a block of weights, of rows and of the output, twice each, inside
    # the 16 MiB of scoped VMEM an operation gets by default
    assert 8 * (k * tn + 64 * k + 64 * tn) < 0.75 * (16 << 20)
    cols = min(n, 2 * tn)
    sizes = np.asarray([70, 0, 41], np.int32)        # 111 of 192 rows
    rng = np.random.default_rng(k + n)
    rows = rng.standard_normal((192, k)).astype(np.float32)
    weights = rng.standard_normal((3, k, cols)).astype(np.float32) * 0.02
    weights[1] = np.nan
    got = np.asarray(kernel.grouped_matmul(
        jnp.asarray(rows), jnp.asarray(weights), jnp.asarray(sizes),
        tm=kernel.ROW_BLOCK, tn=tn, scoped=True, interpret=True))
    assert got.shape == (192, cols)
    at = np.asarray([0, 63, 64, 69, 70, 110])        # both groups' edges
    want = np.stack([rows[r].astype(np.float64)
                     @ weights[0 if r < 70 else 2].astype(np.float64)
                     for r in at])
    assert _rel(got[at], want) < 1e-5
    assert np.isfinite(got[:111]).all()


@pytest.mark.parametrize("held_pairs,run", [(0, 64), (1, 64), (63, 64),
                                            (64, 64), (200, 64), (200, 128),
                                            (230, 192), (200, 256)])
def test_held_share_through_the_kernel_is_the_loop(interpreted, monkeypatch,
                                                   held_pairs, run):
    """``_held_share`` with both products on the kernel (interpret mode;
    small tiles stand in for the chip's), a trip's tail of rows in no
    group included, against a float64 loop over the held experts."""
    monkeypatch.setattr(kernel, "tiles",
                        lambda m, k, n, scoped=False: (16, n))
    rng = np.random.default_rng(held_pairs + run)
    tokens, hidden, held, width, top_k = 60, 32, 5, 24, 4
    x = rng.standard_normal((tokens, hidden)).astype(np.float32)
    w = rng.uniform(size=(tokens, top_k)).astype(np.float32)
    wgu = rng.standard_normal((held, hidden, 2 * width)).astype(
        np.float32) * 0.3
    wd = rng.standard_normal((held, width, hidden)).astype(np.float32) * 0.3
    local = np.full(tokens * top_k, held, np.int32)
    at = rng.choice(tokens * top_k, held_pairs, replace=False)
    local[at] = rng.integers(0, held, held_pairs)
    local = local.reshape(tokens, top_k)
    before = {p: stat_get(f"grouped_matmul_lowered_{p}")
              for p in ("pallas", "ragged_dot")}
    out = np.asarray(moe._held_share(
        jnp.asarray(x), jnp.asarray(local), jnp.asarray(w), jnp.asarray(wgu),
        jnp.asarray(wd), "silu", HIGHEST, experts=20, run=run))
    assert {p: stat_get(f"grouped_matmul_lowered_{p}") - v
            for p, v in before.items()} == {"pallas": 2, "ragged_dot": 0}
    want = np.zeros((tokens, hidden))
    for t, slot in zip(*np.nonzero(local < held)):
        gu = x[t].astype(np.float64) @ wgu[local[t, slot]]
        g = gu[:width] / (1 + np.exp(-gu[:width]))
        want[t] += w[t, slot] * ((g * gu[width:]) @ wd[local[t, slot]])
    assert np.isfinite(out).all()
    assert np.abs(out - want).max() < 1e-5 * max(1.0, np.abs(want).max())


def test_held_share_under_a_mesh_stays_on_ragged_dot_and_says_so(as_tpu,
                                                                 caplog):
    S = jax.ShapeDtypeStruct
    before = stat_get("grouped_matmul_lowered_ragged_dot")
    with caplog.at_level(logging.WARNING, "paddle_tpu.parallel.moe"):
        jaxpr = jax.make_jaxpr(lambda x, l, w, gu, dn: moe._held_share(
            x, l, w, gu, dn, "silu", HIGHEST, mesh_devices=4, experts=320))(
            S((4096, 256), jnp.float32), S((4096, 8), jnp.int32),
            S((4096, 8), jnp.float32), S((20, 256, 512), jnp.float32),
            S((20, 256, 256), jnp.float32))
    prims = _primitives(jaxpr.jaxpr)
    assert "pallas_call" not in prims and "ragged_dot_general" in prims
    assert stat_get("grouped_matmul_lowered_ragged_dot") == before + 2
    assert len([r for r in caplog.records
                if "4-device mesh" in r.getMessage()]) == 1
    # ... in the runs ragged_dot's 64-row tile is good for
    assert _result_rows(jaxpr.jaxpr, "ragged_dot_general") == {moe.RUN_ROWS}


# (rows, the router's experts, held, top k) of a decode step, a chunk or a
# middle rung and the widest rung of each configuration; the run on the
# kernel, and on ``ragged_dot``
HELD_RUNS = [
    ("solar-open2-250b", 64, 320, 20, 8, 64, 192),
    ("solar-open2-250b", 1024, 320, 20, 8, 768, 192),
    ("solar-open2-250b", 4096, 320, 20, 8, 1024, 192),
    ("gigachat35-432b-a28b", 32, 256, 8, 8, 64, 192),
    ("gigachat35-432b-a28b", 1024, 256, 8, 8, 384, 192),
    ("gigachat35-432b-a28b", 2048, 256, 8, 8, 768, 192),
    ("command-a-plus-05-2026", 10, 128, 8, 8, 64, 128),
    ("command-a-plus-05-2026", 1024, 128, 8, 8, 768, 192),
    ("the_check_engines_two_slots", 2, 320, 20, 8, 64, 64),
]


@pytest.mark.parametrize("config,rows,experts,held,top_k,on_kernel,on_ragged",
                         HELD_RUNS)
def test_held_run_is_read_off_the_shapes(config, rows, experts, held, top_k,
                                         on_kernel, on_ragged):
    """One trip for a step, a chunk and every rung but the widest: the
    expected held pairs and half again in whole row blocks, at most
    ``KERNEL_RUN_ROWS``; ``ragged_dot``'s runs are the parent's."""
    pairs = rows * top_k
    assert moe.held_run(pairs, held, experts, True) == on_kernel
    assert moe.held_run(pairs, held, experts, False) == on_ragged
    assert on_kernel % kernel.ROW_BLOCK == 0
    assert on_kernel >= min(pairs * held / experts, moe.KERNEL_RUN_ROWS)


@pytest.mark.parametrize("rows,run", [(64, 64), (4096, 1024)])
def test_held_share_on_a_tpu_takes_the_kernel_at_the_rules_run(as_tpu, rows,
                                                               run):
    """The route and the run inside a traced ``_held_share`` at solar's
    widths: both products are Mosaic calls over ``held_run`` rows."""
    S = jax.ShapeDtypeStruct
    before = stat_get("grouped_matmul_lowered_pallas")
    jaxpr = jax.make_jaxpr(lambda x, l, w, gu, dn: moe._held_share(
        x, l, w, gu, dn, "silu", HIGHEST, experts=320))(
        S((rows, 4096), jnp.float32), S((rows, 8), jnp.int32),
        S((rows, 8), jnp.float32), S((20, 4096, 2560), jnp.float32),
        S((20, 1280, 4096), jnp.float32))
    prims = _primitives(jaxpr.jaxpr)
    assert prims.count("pallas_call") == 2
    assert "ragged_dot_general" not in prims
    assert stat_get("grouped_matmul_lowered_pallas") == before + 2
    assert _result_rows(jaxpr.jaxpr, "pallas_call") == {run}


# ---------------------------------------------------------------------------
# the epilogues (PR 57): the gate on the first product's accumulator, the
# routing weight on the second's
# ---------------------------------------------------------------------------

GATES = {"no_gate": None, "relu": ("relu", None), "silu": ("silu", None),
         "silu_with_limit": ("silu", 0.75)}
# (m, K, N, tm, group sizes): N is two lane tiles, so a half is one
EPILOGUE_LAYOUTS = {
    "even_groups": (128, 16, 256, 16, [16] * 8),
    "a_group_that_straddles_row_blocks": (
        96, 16, 256, 16, [5, 50, 3, 3, 3, 3, 24, 5]),
    "empty_groups": (96, 16, 256, 16, [16, 0, 24, 0, 0, 40, 16, 0]),
    "rows_past_the_groups": (96, 16, 256, 16, [10, 0, 21, 9, 0, 17, 3, 8]),
    "a_short_last_row_block": (100, 16, 256, 32,
                               [12, 13, 12, 13, 12, 13, 12, 13]),
}


def _bound(gate, n):
    import functools

    return gate and functools.partial(moe._gated, inter=n // 2,
                                      activation=gate[0], limit=gate[1])


@pytest.mark.parametrize("gate,scaled", [
    (g, s) for g in GATES for s in (False, True) if s or GATES[g]],
    ids=lambda v: v if isinstance(v, str) else ("unscaled", "scaled")[v])
@pytest.mark.parametrize("layout", EPILOGUE_LAYOUTS)
def test_epilogue_kernel_is_the_plain_formulation(layout, gate, scaled):
    """The gated and the scaled kernel (interpret mode) against the plain
    kernel followed by ``_gated`` and the scale in ``jnp``: the same bits
    in every row of a group; a group without rows is never read."""
    m, k, n, tm, sizes = EPILOGUE_LAYOUTS[layout]
    sizes = np.asarray(sizes, np.int32)
    real = int(sizes.sum())
    rng = np.random.default_rng(len(layout) + len(gate))
    rows = rng.standard_normal((m, k)).astype(np.float32)
    rows[real:] = np.nan
    weights = rng.standard_normal((8, k, n)).astype(np.float32)
    weights[sizes == 0] = np.nan
    scale = rng.uniform(0.1, 1.0, m).astype(np.float32)
    operands = (jnp.asarray(rows), jnp.asarray(weights), jnp.asarray(sizes))
    got = np.asarray(kernel.grouped_matmul_epilogue(
        *operands, jnp.asarray(scale) if scaled else None, tm=tm, tn=n,
        gate=_bound(GATES[gate], n), interpret=True))
    want = moe._after(
        kernel.grouped_matmul(*operands, tm=tm, tn=n, interpret=True),
        jnp.asarray(scale) if scaled else None, GATES[gate])
    assert got.shape == want.shape == (m, n // 2 if GATES[gate] else n)
    assert np.isfinite(got[:real]).all()
    assert np.array_equal(got[:real], np.asarray(want)[:real])
    if GATES[gate]:
        gu = _loop(rows, weights, sizes)
        g, up = gu[:, :n // 2], gu[:, n // 2:]
        limit = GATES[gate][1]
        if limit is not None:
            g, up = np.minimum(g, limit), np.clip(up, -limit, limit)
        g = np.maximum(g, 0) if GATES[gate][0] == "relu" \
            else g / (1 + np.exp(-g))
        loop = g * up * (scale[:real, None] if scaled else 1.0)
        assert _rel(got[:real], loop) < 1e-5


def test_scale_epilogue_over_two_column_blocks_and_a_gate_that_does_not_fit():
    """The scale is an epilogue at any column block; the gate only where
    one block holds a row's two halves in whole lane tiles."""
    m, k, n, tm, sizes = EPILOGUE_LAYOUTS["empty_groups"]
    rng = np.random.default_rng(7)
    rows = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    weights = jnp.asarray(
        np.nan_to_num(rng.standard_normal((8, k, n))).astype(np.float32))
    scale = jnp.asarray(rng.uniform(0.1, 1.0, m).astype(np.float32))
    sizes = jnp.asarray(sizes, jnp.int32)
    real = int(sizes.sum())
    got = kernel.grouped_matmul_epilogue(rows, weights, sizes, scale, tm=tm,
                                         tn=n // 2, interpret=True)
    want = kernel.grouped_matmul(rows, weights, sizes, tm=tm, tn=n // 2,
                                 interpret=True) * scale[:, None]
    assert np.array_equal(np.asarray(got)[:real], np.asarray(want)[:real])
    assert kernel.gate_fits(256, 256) and kernel.gate_fits(3072, 3072)
    assert not kernel.gate_fits(256, 128)      # two column blocks
    assert not kernel.gate_fits(48, 48)        # a half is no lane tile
    with pytest.raises(ValueError, match="one block"):
        kernel.grouped_matmul_epilogue(
            rows, weights, sizes, tm=tm, tn=n // 2,
            gate=_bound(("relu", None), n), interpret=True)


EPILOGUE_COUNTERS = ("grouped_matmul_epilogue_gate",
                     "grouped_matmul_epilogue_scale", "moe_combine_gather")


def _traced_with(m, groups, k, n, gate, scaled, tiles=None, **kw):
    """``moe.grouped_matmul`` with an epilogue asked for, traced: its
    primitives outside any kernel's body, the result's shape, how far each
    counter moved."""
    S = jax.ShapeDtypeStruct
    names = EPILOGUE_COUNTERS + ("grouped_matmul_lowered_pallas",
                                 "grouped_matmul_lowered_ragged_dot")
    before = {c: stat_get(c) for c in names}
    args = [S((m, k), jnp.float32), S((groups, k, n), jnp.float32),
            S((groups,), jnp.int32)] + ([S((m,), jnp.float32)] * scaled)
    jaxpr = jax.make_jaxpr(
        lambda r, w, s, c=None: moe.grouped_matmul(
            r, w, s, HIGHEST, row_scale=c, gate=gate, **kw))(*args)
    moved = [stat_get(c) - before[c] for c in names]
    return (_primitives(jaxpr.jaxpr, into_kernels=False),
            jaxpr.out_avals[0].shape, moved)


# SmallThinker's, LFM2's and SDAR's first and second product at a rung's
# and a step's rows
@pytest.mark.parametrize("m,groups,k,inter,act", [
    (24576, 64, 2560, 768, "relu"), (192, 64, 2560, 768, "relu"),
    (4096, 64, 2048, 1536, "silu"), (1536, 128, 2048, 768, "silu")])
def test_route_on_a_tpu_takes_the_epilogues_into_the_kernel(as_tpu, m, groups,
                                                            k, inter, act):
    prims, shape, moved = _traced_with(m, groups, k, 2 * inter, (act, None),
                                       False)
    assert prims.count("pallas_call") == 1 and shape == (m, inter)
    assert moved == [1, 0, 0, 1, 0]
    # the gate ran inside the kernel: nothing of it is left outside
    assert not {"max", "logistic", "mul"} & set(
        prims[prims.index("pallas_call") + 1:])
    prims, shape, moved = _traced_with(m, groups, inter, k, None, True)
    assert prims.count("pallas_call") == 1 and shape == (m, k)
    assert moved == [0, 1, 0, 1, 0]
    assert "mul" not in prims[prims.index("pallas_call") + 1:]


@pytest.mark.parametrize("why,kw,route", [
    ("the_cpu", {}, "ragged_dot"),
    ("fewer_rows_than_a_row_block", {"m": 18}, "ragged_dot"),
    ("under_a_mesh", {"mesh_devices": 4}, "ragged_dot"),
    ("two_column_blocks", {"tiles": (64, 768)}, "pallas"),
])
def test_route_elsewhere_gates_and_scales_after_the_call(monkeypatch, why, kw,
                                                         route):
    """Everywhere the kernel does not take an epilogue the same arithmetic
    runs after the call, and the epilogue counters say so by standing."""
    kw = dict(kw)
    if why != "the_cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(moe, "_downgrades_logged", set())
    tiles = kw.pop("tiles", None)
    if tiles:
        monkeypatch.setattr(kernel, "tiles",
                            lambda m, k, n, scoped=False: tiles)
    m = kw.pop("m", 3072)
    prims, shape, moved = _traced_with(m, 64, 2560, 1536, ("relu", None),
                                       True, **kw)
    assert shape == (m, 768)
    assert moved == [0, 0, 0, int(route == "pallas"),
                     int(route == "ragged_dot")]
    product = "pallas_call" if route == "pallas" else "ragged_dot_general"
    assert prims.count(product) == 1
    after = prims[prims.index(product) + 1:]
    assert "max" in after and after.count("mul") >= 2


@pytest.mark.parametrize("activation,limit", [("relu", None), ("silu", None),
                                              ("silu", 0.5)])
def test_routed_layer_with_both_epilogues_is_the_layer(interpreted,
                                                       monkeypatch,
                                                       activation, limit):
    """``moe_routed_tokens`` with the gate and the routing weight inside the
    kernels (interpret mode; a width of one lane tile so the gate fits)
    against a float64 loop, and the three counters each up by one."""
    monkeypatch.setattr(kernel, "tiles",
                        lambda m, k, n, scoped=False: (16, n))
    rng = np.random.default_rng(5)
    tokens, hidden, experts, width, top_k = 40, 32, 8, 128, 3
    x = rng.standard_normal((tokens, hidden)).astype(np.float32)
    wr = rng.standard_normal((hidden, experts)).astype(np.float32)
    wgu = rng.standard_normal((experts, hidden, 2 * width)).astype(
        np.float32) * 0.3
    wd = rng.standard_normal((experts, width, hidden)).astype(
        np.float32) * 0.3
    before = [stat_get(c) for c in EPILOGUE_COUNTERS]
    out, counts, _ = moe.moe_routed_tokens(
        jnp.asarray(x), jnp.asarray(x), wr, wgu, wd, top_k=top_k,
        activation=activation, limit=limit, precision=HIGHEST)
    assert [stat_get(c) - b for c, b in zip(EPILOGUE_COUNTERS, before)] \
        == [1, 1, 1]
    want = np.zeros((tokens, hidden))
    for t in range(tokens):
        l = x[t].astype(np.float64) @ wr
        top = np.argsort(-l)[:top_k]
        w = np.exp(l[top] - l[top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            gu = x[t].astype(np.float64) @ wgu[e]
            g, up = gu[:width], gu[width:]
            if limit is not None:
                g, up = np.minimum(g, limit), np.clip(up, -limit, limit)
            g = np.maximum(g, 0) if activation == "relu" \
                else g / (1 + np.exp(-g))
            want[t] += we * ((g * up) @ wd[e])
    assert _rel(np.asarray(out), want) < 1e-5
    assert int(counts.sum()) == tokens * top_k

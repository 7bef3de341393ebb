"""Paged decode attention: the Pallas kernel against a plain reference,
and the op's two lowerings.

* **Kernel** (``ops/pallas/paged_attention.py``, ``interpret=True`` on the
  CPU) against a float32 ``jax.numpy`` reference at "highest" matmul
  precision that gathers each slot's live columns and nothing else.
  Tolerance ``TOL`` of the reference's range: both sides are float32
  throughout and differ in the order of accumulation only (online softmax
  over granules against one softmax row).
* **Nothing beyond the live length is read**: every unmapped page, the
  trash page beyond its first position and the tail of each slot's last
  live page hold NaN; the output must be finite and equal the reference.
* **Op** ``paged_decode_attention``: on a non-TPU backend it books
  ``attention_lowered_paged_decode_reference`` and equals the
  ``kv_pool_gather`` x 2 + ``cached_attention`` triple bit for bit, alone
  and inside ``build_llama_decode``.
* **For the chip, without one**: the kernel compiles for a described TPU
  v5e at both serving cells' shapes (skipped where no topology can be
  described; the topology is described inside a fixture of this one file,
  because one process at a time may load the TPU's library); a whole
  decode step and a whole-prompt prefill compile with no copy of a pool
  (``kv_pool_write``'s forms, ``ops/decode_ops.py``).  The same
  fixture serves the one compile of the training attention kernels under
  a four-chip mesh (``ops/attention_ops.py`` ``kernel_partition``, PR 31),
  which is here and not in ``test_attention.py`` for that reason.
"""
import functools
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

# float32 on both sides, another order of accumulation: measured 5e-7 of
# the range at these sizes; eight times that
TOL = 4e-6


def _case(rng, lengths, H, Hkv, D=128, pt_=8, NP=12, nan=True):
    """Pools, a permuted block table and positions for slots attending
    ``lengths`` columns (0 = an idle slot: position 0 on the trash page)."""
    import jax.numpy as jnp

    B = len(lengths)
    P = B * NP + 1
    pk = rng.standard_normal((P, Hkv, pt_, D)).astype(np.float32)
    pv = rng.standard_normal((P, Hkv, pt_, D)).astype(np.float32)
    q = (2.0 * rng.standard_normal((B, H, 1, D))).astype(np.float32)
    bt = rng.permutation(np.arange(1, P)).reshape(B, NP).astype(np.int32)
    pos = np.array([max(n, 1) - 1 for n in lengths], np.int32)
    for b, n in enumerate(lengths):
        live = 0 if n == 0 else -(-n // pt_)
        if nan:
            for i in range(live, NP):
                pk[bt[b, i]] = pv[bt[b, i]] = np.nan
            if live and n % pt_:
                pk[bt[b, live - 1], :, n % pt_:] = np.nan
                pv[bt[b, live - 1], :, n % pt_:] = np.nan
        bt[b, live:] = 0
    if nan:
        # an idle slot attends column 0 of the trash page, and only that
        pk[0, :, 1:] = pv[0, :, 1:] = np.nan
    return tuple(jnp.asarray(a) for a in (q, pk, pv, bt, pos))


def _reference(q, pk, pv, bt, pos, scale=None):
    """One softmax row per slot and head over the slot's live columns."""
    import jax
    import jax.numpy as jnp

    B, H, _, D = q.shape
    _, Hkv, pt_, _ = pk.shape
    scale = scale if scale is not None else 1.0 / D ** 0.5
    out = []
    for b in range(B):
        n = int(pos[b]) + 1
        pages = bt[b, :-(-n // pt_)]
        k = jnp.moveaxis(pk[pages], 1, 0).reshape(Hkv, -1, D)[:, :n]
        v = jnp.moveaxis(pv[pages], 1, 0).reshape(Hkv, -1, D)[:, :n]
        k, v = (jnp.repeat(t, H // Hkv, axis=0) for t in (k, v))
        s = jnp.einsum("hd,hkd->hk", q[b, :, 0], k,
                       precision="highest") * scale
        out.append(jnp.einsum("hk,hkd->hd", jax.nn.softmax(s, axis=-1), v,
                              precision="highest"))
    return np.asarray(jnp.stack(out))[:, :, None, :]


def _check(args, **kw):
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    got = np.asarray(paged_decode_attention(*args, interpret=True, **kw))
    want = _reference(*args, scale=kw.get("scale"))
    assert np.isfinite(got).all(), "something beyond the live length was read"
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


# page 8, 12 pages a slot: max_seq 96
@pytest.mark.parametrize("rep", [4, 1])
@pytest.mark.parametrize("granule", [8, 32, 1024])
def test_kernel_matches_reference_over_ragged_lengths(rep, granule):
    """Lengths 1, k*pt, k*pt + 1, max_seq, an idle slot first, in the
    middle and last; one page, four pages and a whole slot per granule."""
    lengths = [0, 1, 8, 9, 0, 32, 33, 57, 96, 0]
    args = _case(np.random.default_rng(granule + rep), lengths,
                 H=2 * rep, Hkv=2)
    _check(args, granule=granule)


def test_kernel_default_granule_page_16():
    """The serving cells' page size and the default granule."""
    args = _case(np.random.default_rng(5), [16, 17, 1, 128, 129, 160],
                 H=8, Hkv=2, pt_=16, NP=10)
    _check(args)


def test_kernel_takes_a_scale_and_a_wider_head():
    args = _case(np.random.default_rng(6), [5, 40, 17], H=4, Hkv=2, D=256)
    _check(args, granule=16, scale=0.05)


def test_kernel_pads_a_group_that_is_no_whole_sublane_tile():
    """12 query heads a KV head: padded to 16 rows inside, sliced off."""
    args = _case(np.random.default_rng(7), [24, 3], H=12, Hkv=1)
    _check(args, granule=16)


def test_kernel_single_slot_single_page():
    args = _case(np.random.default_rng(8), [3], H=4, Hkv=4, NP=1)
    _check(args)


@pytest.mark.parametrize("q_shape,pool_shape,ok", [
    ((32, 32, 1, 128), (2817, 8, 16, 128), True),
    ((8, 32, 1, 128), (1857, 8, 16, 128), True),
    ((3, 4, 1, 8), (19, 2, 16, 8), False),      # head 8: no lane tile
    ((3, 4, 1, 128), (19, 2, 4, 128), False),   # page 4: no sublane tile
    ((1, 4, 5, 128), (19, 2, 16, 128), True),   # a block of query rows
    ((1, 64, 16, 128), (19, 2, 16, 128), False),  # 512 rows a KV head
])
def test_supported_shapes(q_shape, pool_shape, ok):
    from paddle_tpu.ops.pallas.paged_attention import supported

    assert supported(q_shape, pool_shape) is ok
    # a sliding window gives each row of a block its own columns
    assert supported(q_shape, pool_shape, window=64) \
        is (ok and q_shape[2] == 1)


# ---------------------------------------------------------------------------
# the op: reference lowering off the TPU, bit for bit the old op triple
# ---------------------------------------------------------------------------

def _attention_program(B, H, Hkv, D, pt_, NP, fused):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        def data(name, shape, dtype="float32"):
            return layers.data(name, shape, dtype=dtype,
                               append_batch_size=False)

        q = data("q", [B, H, 1, D])
        pk = data("pk", [B * NP + 1, Hkv, pt_, D])
        pv = data("pv", [B * NP + 1, Hkv, pt_, D])
        bt = data("bt", [B, NP], "int32")
        pos = data("pos", [B], "int32")
        if fused:
            out = layers.paged_decode_attention(q, pk, pv, bt, pos)
        else:
            out = layers.cached_attention(q, layers.kv_pool_gather(pk, bt),
                                          layers.kv_pool_gather(pv, bt), pos)
    return main, out


@pytest.mark.parametrize("D,pt_", [(128, 8), (16, 4)])
def test_op_reference_lowering_is_the_old_triple_bit_for_bit(D, pt_):
    """Off the TPU the op is the gather + einsum code itself, at a shape
    the kernel takes and at one it does not."""
    B, H, Hkv, NP = 3, 4, 2, 6
    args = _case(np.random.default_rng(11), [1, pt_ * 3 + 1, pt_ * NP],
                 H=H, Hkv=Hkv, D=D, pt_=pt_, NP=NP, nan=False)
    feed = dict(zip(("q", "pk", "pv", "bt", "pos"), map(np.asarray, args)))
    before = stat_get("attention_lowered_paged_decode_reference")
    got = {}
    for fused in (True, False):
        main, out = _attention_program(B, H, Hkv, D, pt_, NP, fused)
        got[fused] = pt.Executor().run(main, feed=feed, fetch_list=[out])[0]
    assert stat_get("attention_lowered_paged_decode_reference") == before + 1
    assert np.array_equal(got[True], got[False])
    want = _reference(*args)
    assert np.abs(got[True] - want).max() <= 1e-5 * np.abs(want).max()


def test_decode_program_books_the_reference_and_keeps_its_bits(monkeypatch):
    """``build_llama_decode`` (paged) calls the new op once a layer; on
    this backend that is the reference lowering, and the step's logits
    equal those of the same program built with the old op triple."""
    from paddle_tpu.models.llama import build_llama_decode
    from paddle_tpu.ops import attention_ops

    model = dict(vocab_size=61, hidden=32, num_layers=3, num_heads=4,
                 num_kv_heads=2, intermediate=64)
    slots, max_seq, page = 3, 32, 8
    pages = slots * (max_seq // page) + 1

    def build():
        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        with pt.program_guard(main, startup):
            feeds, fetches, caches = build_llama_decode(
                slots, max_seq, name="pda", num_pages=pages,
                page_tokens=page, **model)
        return main, startup, fetches, caches

    def triple(q, pool_k, pool_v, block_table, positions):
        return layers.cached_attention(
            q, layers.kv_pool_gather(pool_k, block_table),
            layers.kv_pool_gather(pool_v, block_table), positions)

    new = build()
    types = [op.type for op in new[0].global_block().ops]
    assert types.count("paged_decode_attention") == model["num_layers"]
    assert "kv_pool_gather" not in types and "cached_attention" not in types
    monkeypatch.setattr(layers, "paged_decode_attention", triple)
    old = build()
    types = [op.type for op in old[0].global_block().ops]
    assert types.count("cached_attention") == model["num_layers"]

    rng = np.random.default_rng(3)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(new[1], scope=scope)
    pools = {n: rng.standard_normal(
        (pages, model["num_kv_heads"], page, 8)).astype("float32")
        for n in new[3]}
    bt = rng.permutation(np.arange(1, pages)).reshape(slots, -1)
    feed = {"tokens": rng.integers(0, 61, (slots, 1)).astype("int64"),
            "positions": np.array([0, 9, 31], "int32"),
            "block_tables": bt.astype("int32"),
            "live": np.array([0, 1, 1], "int32")}
    counts = {k: stat_get(f"attention_lowered_{k}")
              for k in attention_ops._LOWERED}
    logits = []
    for main, _, fetches, _ in (new, old):
        for n, a in pools.items():
            scope.set_var(n, a.copy())
        logits.append(exe.run(main, feed=feed, scope=scope,
                              fetch_list=[fetches["logits"]])[0])
    moved = {k: stat_get(f"attention_lowered_{k}") - v
             for k, v in counts.items()}
    assert moved.pop("paged_decode_reference") == model["num_layers"]
    assert not any(moved.values()), moved
    assert np.isfinite(logits[0]).all()
    assert np.array_equal(logits[0], logits[1])


def test_reference_path_on_a_tpu_backend_is_logged_once(caplog):
    from paddle_tpu.ops import attention_ops

    before = stat_get("attention_lowered_paged_decode_reference")
    with caplog.at_level("WARNING", logger="paddle_tpu.ops.attention"):
        for _ in range(2):
            attention_ops._lowered("paged_decode_reference",
                                   "test: page of 4 tokens")
    assert stat_get("attention_lowered_paged_decode_reference") == before + 2
    said = [r.getMessage() for r in caplog.records
            if "test: page of 4 tokens" in r.getMessage()]
    assert len(said) == 1 and "paged_decode_reference" in said[0]


# ---------------------------------------------------------------------------
# for the chip, without one
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    """A described four-chip TPU v5e host: nothing is attached."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One device of it."""
    return topo.devices[0]


def _spec(shape, dtype, sharding):
    """A step argument by shape alone (no array can be put on a described
    device)."""
    import jax

    dtype = {"int64": "int32"}.get(str(dtype), str(dtype))
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                sharding=sharding)


def _state(block, names, sharding):
    return tuple(_spec(v.shape, v.dtype, sharding) for v in
                 map(block._find_var_recursive, names))


@pytest.mark.parametrize("slots,max_seq", [(32, 1408), (8, 3712)])
def test_kernel_compiles_for_a_described_v5e(chip, slots, max_seq):
    """Mistral-7B widths (32 query heads over 8 KV heads of 128, float32,
    page 16) at the two serving cells' slot grids: Mosaic takes the
    kernel, and the pools are operands of the custom call, not of a
    gather before it.  Nothing runs: a compile is not a chip run."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    page, np_slot = 16, max_seq // 16
    pages = slots * np_slot + 1
    one_chip = SingleDeviceSharding(chip)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(paged_decode_attention).lower(
        spec((slots, 32, 1, 128)), spec((pages, 8, page, 128)),
        spec((pages, 8, page, 128)), spec((slots, np_slot), jnp.int32),
        spec((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "gather" not in text
    # no dense view of a pool among the temporaries: Q, the output and
    # the padded group rows only
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("m,groups,k,n,scoped", [
    (49152, 64, 2560, 1536, False),  # smallthinker-21b-a3b, rung 8192, gate + up
    (192, 64, 768, 2560, False),     # ... a decode step's rows, down
    (16384, 64, 2048, 3072, False),  # lfm2-24b-a2b, rung 4096: the widest block
    (1536, 128, 768, 2048, False),   # sdar-30b-a3b-chat, a block pass, down
    # the held share's runs (PR 52), blocks inside the default scoped
    # VMEM: solar's widest rung, gate + up; giga's (32 and 14 column
    # blocks a product); cmda's chunk and its step
    (1024, 20, 4096, 2560, True), (768, 8, 7168, 4096, True),
    (768, 8, 2048, 7168, True), (768, 8, 4096, 8192, True),
    (64, 8, 4096, 4096, True),
    # PR 63, 32 held experts of two matrices in a latent of 1024: a decode
    # step's run of held pairs, up and down, and the widest rung's
    (320, 32, 1024, 2688, True), (320, 32, 2688, 1024, True),
    (1024, 32, 1024, 2688, True), (1024, 32, 2688, 1024, True),
])
def test_grouped_matmul_kernel_compiles_for_a_described_v5e(chip, m, groups,
                                                            k, n, scoped):
    """The experts' products at published widths, at the blocks
    ``grouped_matmul.tiles`` gives them: Mosaic takes the kernel (a block
    of weights twice in VMEM, "highest" products), and nothing is padded
    or copied around it.  Nothing runs: a compile is not a chip run."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import grouped_matmul as kernel

    one_chip = SingleDeviceSharding(chip)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tm, tn = kernel.tiles(m, k, n, scoped)
    compiled = jax.jit(
        lambda r, w, s: kernel.grouped_matmul(
            r, w, s, tm=tm, tn=tn, scoped=scoped)).lower(
        spec((m, k)), spec((groups, k, n)),
        spec((groups,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the trace's name for it: the benchmark's expert share reads the
    # operations whose text holds "ragged-dot"
    assert "%grouped_matmul_ragged-dot" in text
    # the visit lists and the call's own scratch (6.6 MB at the largest
    # shape): no copy of the rows, the output or a group's weights
    assert compiled.memory_analysis().temp_size_in_bytes \
        < min(2 ** 23, 4 * k * n)


@pytest.mark.parametrize("m,groups,k,inter,activation,limit", [
    (49152, 64, 2560, 768, "relu", None),   # smallthinker-21b-a3b, rung 8192
    (192, 64, 2560, 768, "relu", None),     # ... a decode step's pairs
    (16384, 64, 2048, 1536, "silu", None),  # lfm2-24b-a2b, rung 4096
    (1536, 128, 2048, 768, "silu", 7.0),    # sdar-30b-a3b-chat's pass, clamped
])
def test_grouped_matmul_epilogues_compile_for_a_described_v5e(
        chip, m, groups, k, inter, activation, limit):
    """A routed layer's two products with their epilogues (PR 57) at
    published widths: the gate on the first one's accumulator, stored half
    as wide, and the routing weight, a ``(tm, 1)`` block beside the rows,
    on the second's.  Mosaic takes both; nothing runs."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    one_chip = SingleDeviceSharding(chip)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tm, tn = kernel.tiles(m, k, 2 * inter)
    assert kernel.gate_fits(2 * inter, tn)
    gate = functools.partial(moe._gated, inter=inter, activation=activation,
                             limit=limit)
    first = jax.jit(lambda r, w, s: kernel.grouped_matmul_epilogue(
        r, w, s, tm=tm, tn=tn, gate=gate)).lower(
        spec((m, k)), spec((groups, k, 2 * inter)),
        spec((groups,), jnp.int32)).compile()
    tm, tn = kernel.tiles(m, inter, k)
    second = jax.jit(lambda r, w, s, c: kernel.grouped_matmul_epilogue(
        r, w, s, c, tm=tm, tn=tn)).lower(
        spec((m, inter)), spec((groups, inter, k)),
        spec((groups,), jnp.int32), spec((m,))).compile()
    for compiled in (first, second):
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "%grouped_matmul_ragged-dot" in text
    # no [M, 2I] array beside the gated one
    assert first.memory_analysis().temp_size_in_bytes < 2 ** 23
    assert first.memory_analysis().output_size_in_bytes == 4 * m * inter


def test_grouped_matmul_activation_epilogue_compiles_for_a_described_v5e(
        chip):
    """PR 63: experts of two matrices.  The first product's epilogue is an
    activation alone (``relu2``), a column's own, so it keeps its width
    and takes any column block; the routing weight rides the second as it
    does the gated experts'.  512 experts of 2688 in a latent of 1024, a
    rung of 512 rows at 22 a token."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    one_chip = SingleDeviceSharding(chip)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    m, groups, k, inter = 512 * 22, 512, 1024, 2688
    tm, tn = kernel.tiles(m, k, inter)
    act = functools.partial(moe._activation, activation="relu2")
    first = jax.jit(lambda r, w, s: kernel.grouped_matmul_epilogue(
        r, w, s, tm=tm, tn=tn, act=act)).lower(
        spec((m, k)), spec((groups, k, inter)),
        spec((groups,), jnp.int32)).compile()
    text = first.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%grouped_matmul_ragged-dot" in text
    assert first.memory_analysis().output_size_in_bytes == 4 * m * inter
    with pytest.raises(ValueError, match="gate epilogue"):
        kernel.grouped_matmul_epilogue(
            jnp.zeros((64, 128)), jnp.zeros((2, 128, 384)),
            jnp.zeros((2,), jnp.int32), tm=64, tn=128, gate=act)


def test_decode_step_for_a_described_v5e_reads_the_pools_in_place(
        chip, monkeypatch):
    """The whole paged decode step, small but at a head of 128, compiled
    for the described chip with the backend answered for (the op asks
    ``jax.default_backend()``): one Mosaic call a layer, and no copy of a
    pool anywhere — the step's ``kv_pool_write`` must leave the pool in
    the layout the kernel reads (a ``[Hkv, D]`` scatter window made XLA
    re-lay every pool in and out, each layer, each step)."""
    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import compile_cache
    from paddle_tpu.models.llama import build_llama_decode
    from paddle_tpu.parallel import build_sharded_step, dp_mesh
    from paddle_tpu.parallel import sharded

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "ensure_compile_cache", lambda: None)
    monkeypatch.setattr(sharded, "ensure_compile_cache", lambda: None)
    layers_, slots, max_seq, page = 2, 4, 64, 16
    pages = slots * (max_seq // page) + 1
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, _ = build_llama_decode(
            slots, max_seq, name="pdc", num_pages=pages,
            page_tokens=page, vocab_size=61, hidden=512, num_layers=layers_,
            num_heads=4, num_kv_heads=2, intermediate=128)
    before = stat_get("attention_lowered_paged_decode")
    mesh = dp_mesh(1, devices=[chip])
    fn, mut_in, const_in, _ = build_sharded_step(
        main, feeds, [fetches["next_token"].name], mesh)
    rep = NamedSharding(mesh, P())
    block = main.global_block()
    shapes = {"tokens": ((slots, 1), "int32"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, max_seq // page), "int32"),
              "live": ((slots,), "int32")}
    text = fn.lower(tuple(_spec(*shapes[n], rep) for n in feeds),
                    _state(block, mut_in, rep), _state(block, const_in, rep),
                    _spec((), "int32", rep)).compile().as_text()
    assert stat_get("attention_lowered_paged_decode") == before + layers_
    assert text.count('custom_call_target="tpu_custom_call"') == layers_
    pool = rf"f32\[{pages},2,{page},128\]"
    assert re.search(pool, text), "no pool in the step's text"
    assert not re.findall(pool + r"\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("head_dim", [128, 64])
def test_prefill_for_a_described_v5e_writes_the_pools_in_place(
        chip, monkeypatch, head_dim):
    """The whole-prompt prefill program of a small Llama (two layers; a
    head of 128, and a head of 64 over pools packed two heads a row),
    compiled for the described chip: every pool goes in page by page
    (``kv_pool_write_pages`` once a pool) and no copy of a pool-sized
    array is in the text.  The chunk program, whose base position is a
    feed, keeps the [Hkv, D] window a row (``kv_pool_write_rows``), and
    XLA:TPU re-lays every pool for it, in and out: the copies the prefill
    left behind, and the proof that the pattern sees one."""
    import math
    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import compile_cache
    from paddle_tpu.models.llama import (build_llama_prefill,
                                         build_llama_prefill_chunk)
    from paddle_tpu.ops.decode_ops import pool_shape
    from paddle_tpu.parallel import build_sharded_step, dp_mesh
    from paddle_tpu.parallel import sharded

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "ensure_compile_cache", lambda: None)
    monkeypatch.setattr(sharded, "ensure_compile_cache", lambda: None)
    layers_, slots, max_seq, page, bucket = 2, 4, 256, 16, 128
    pages = slots * (max_seq // page) + 1
    model = dict(vocab_size=61, hidden=4 * head_dim, num_layers=layers_,
                 num_heads=4, num_kv_heads=4, intermediate=128)
    mesh = dp_mesh(1, devices=[chip])
    rep = NamedSharding(mesh, P())
    shape = pool_shape(pages, 4, page, head_dim)
    pool = r"f32\[%s\]" % ",".join(map(str, shape))
    pool_elems = math.prod(shape)

    def compiled_text(build, fetch, shapes):
        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        with pt.program_guard(main, startup):
            feeds, fetches = build()[:2]
        fn, mut_in, const_in, _ = build_sharded_step(
            main, feeds, [fetches[fetch].name], mesh)
        block = main.global_block()
        assert len(mut_in) == 2 * layers_
        text = fn.lower(tuple(_spec(*shapes[n], rep) for n in feeds),
                        _state(block, mut_in, rep),
                        _state(block, const_in, rep),
                        _spec((), "int32", rep)).compile().as_text()
        assert re.search(pool, text), "no pool in the program's text"
        # a copy of anything with a pool's element count: the compiler
        # also re-lays a pool under a bitcast shape, [P * pt, Hkv, D]
        copied = re.findall(r"= f32\[([\d,]+)\]\{[^}]*\} copy\(", text)
        return [dims for dims in copied
                if math.prod(map(int, dims.split(","))) == pool_elems]

    def booked():
        return [stat_get("kv_pool_write_" + k) for k in ("pages", "rows")]

    table = ((1, max_seq // page), "int32")
    before = booked()
    copies = compiled_text(
        lambda: build_llama_prefill(
            1, bucket, name="pwp", cache_slots=slots, max_seq_len=max_seq,
            num_pages=pages, page_tokens=page, **model),
        "next_token",
        {"input_ids": ((1, bucket), "int32"), "last_pos": ((1,), "int32"),
         "block_table": table, "prompt_len": ((1,), "int32")})
    assert booked() == [before[0] + 2 * layers_, before[1]]
    assert not copies

    before = booked()
    copies = compiled_text(
        lambda: build_llama_prefill_chunk(
            bucket, max_seq, pages, page, name="pwc", **model),
        "next_token",
        {"chunk_ids": ((1, bucket), "int32"), "base": ((1,), "int32"),
         "block_table": table, "chunk_len": ((1,), "int32"),
         "last_off": ((1,), "int32")})
    assert booked() == [before[0], before[1] + 2 * layers_]
    assert len(copies) >= 2 * layers_


def test_bert_step_for_a_described_v5e_mesh_keeps_the_pallas_kernels(
        topo, monkeypatch):
    """A two-layer BERT training step at the dp4 cell's widths (hidden 768,
    12 heads, sequence 512) and the published 64 sequences a chip, lowered
    through ``build_sharded_step`` for the described 2x2 mesh: every
    attention op runs the packed Pallas kernels per ``dp`` shard (Mosaic
    calls in the text), no float32 ``[B, h, S, bk]`` score block of the
    blockwise reference is left, and the step fits the chip."""
    import re

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import compile_cache
    from paddle_tpu.models.bert import build_bert_train_programs
    from paddle_tpu.parallel import build_sharded_step, dp_mesh
    from paddle_tpu.parallel import sharded

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "ensure_compile_cache", lambda: None)
    monkeypatch.setattr(sharded, "ensure_compile_cache", lambda: None)
    layers_, chips, per_chip, seq, pred = 2, 4, 64, 512, 77
    batch = chips * per_chip
    main_p, _, feed_names, loss, _ = build_bert_train_programs(
        dict(batch_size=batch, seq_len=seq, vocab_size=30522, hidden=768,
             num_layers=layers_, num_heads=12, intermediate=3072,
             max_predictions=pred, use_flash=True, dropout=0.1))
    names = ("lowered_pallas", "lowered_pallas_sharded", "lowered_blockwise",
             "grad_saved", "grad_relowered")
    before = {n: stat_get(f"attention_{n}") for n in names}
    mesh = dp_mesh(chips, devices=list(topo.devices)[:chips])
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [loss.name], mesh)
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    block = main_p.global_block()
    shapes = {"input_ids": ((batch, seq), "int32"),
              "token_type_ids": ((batch, seq), "int32"),
              "attn_mask": ((batch, seq), "float32"),
              "mlm_positions": ((batch, pred), "int32"),
              "mlm_labels": ((batch, pred), "int32"),
              "mlm_weights": ((batch, pred), "float32")}
    # a compile for a described device is written to the persistent cache
    # but can never be read back
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = fn.lower(
            tuple(_spec(*shapes[n], dp) for n in feed_names),
            _state(block, mut_in, rep), _state(block, const_in, rep),
            _spec((), "int32", rep)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    moved = {n: stat_get(f"attention_{n}") - before[n] for n in names}
    # every layer's op once: its grad op reads the forward's saved output
    # and statistic and lowers no forward of its own (PR 35)
    assert moved == {"lowered_pallas": layers_,
                     "lowered_pallas_sharded": layers_,
                     "lowered_blockwise": 0, "grad_saved": layers_,
                     "grad_relowered": 0}
    text = compiled.as_text()
    # a layer's forward kernel, its dK/dV and its dQ kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 3 * layers_
    assert "all-reduce" in text
    assert not re.search(rf"f32\[({per_chip}|{batch}),12,{seq},{seq}\]", text)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < 15.75 * 2 ** 30, total


def test_gated_delta_kernels_compile_for_a_described_v5e(chip):
    """The delta rule's two kernels at the published head sizes (30 heads,
    keys of 96, values of 192): the step over 32 slots with its state
    aliased in place, the whole scan over a 6144 rung.  Both hold one
    Mosaic call; the step's state is no temporary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import gated_delta as kern

    one_chip = SingleDeviceSharding(chip)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, H, Dk, Dv, T = 32, 30, 96, 192, 6144
    state = sds((n + 1, H, Dk, Dv))
    step = jax.jit(kern.step, donate_argnums=5).lower(
        sds((n, H, Dk)), sds((n, H, Dk)), sds((n, H, Dv)), sds((n, H)),
        sds((n, H)), state, sds((n,), jnp.int32)).compile()
    assert step.as_text().count("tpu_custom_call") == 1
    state_bytes = (n + 1) * H * Dk * Dv * 4
    assert step.memory_analysis().temp_size_in_bytes < state_bytes // 4

    def prefill(q, k, v, g, beta, valid):
        return kern.chunk(q, k, v, g, beta, valid=valid)

    # ... and the scan at the three shapes the benchmark's cells run: a
    # number a head (30 heads 96 x 192; 64 of 128 x 128) and a channel
    for H, Dk, Dv, T, gdim in ((H, Dk, Dv, T, ()), (64, 128, 128, 2048, ()),
                               (64, 128, 128, 4096, (128,))):
        chunk = jax.jit(prefill).lower(
            sds((1, T, H, Dk)), sds((1, T, H, Dk)), sds((1, T, H, Dv)),
            sds((1, T, H) + gdim), sds((1, T, H)),
            sds((1,), jnp.int32)).compile()
        assert chunk.as_text().count("tpu_custom_call") == 1
        assert chunk.memory_analysis().temp_size_in_bytes < 1.0e9


def test_state_space_step_kernel_compiles_for_a_described_v5e(chip):
    """The SSD step at the ``granite4h-micro-manychats`` cell's shapes (64
    heads of 64 over 128 state rows, 128 slots): one Mosaic call, the
    state ``[129, 128, 4096]`` aliased in place and no temporary of its
    size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import ssd as kern

    one_chip = SingleDeviceSharding(chip)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, H, P, N = 128, 64, 64, 128
    assert kern.step_supported((n + 1, N, H * P))
    step = jax.jit(kern.step, donate_argnums=6).lower(
        sds((n, H, P)), sds((n, H)), sds((H,)), sds((n, N)), sds((n, N)),
        sds((H,)), sds((n + 1, N, H * P)), sds((n,), jnp.int32)).compile()
    assert step.as_text().count("tpu_custom_call") == 1
    state_bytes = (n + 1) * N * H * P * 4
    assert step.memory_analysis().temp_size_in_bytes < state_bytes // 4


@pytest.mark.parametrize("rung", [None, 128, 256, 512])
def test_grouped_state_space_kernels_compile_for_a_described_v5e(chip, rung):
    """Both SSD kernels with EIGHT groups of B and C at the
    ``nemotron3-super-agentfleet`` cell's shapes (128 heads of 64 over 128
    state rows, a group's 16 heads 1024 lanes): the step over 128 slots
    (``rung`` None), its state ``[129, 128, 8192]`` aliased in place, and
    the scan over each prefill rung.  One Mosaic call each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import ssd as kern
    from paddle_tpu.ops.ssd_ops import CHUNK

    one_chip = SingleDeviceSharding(chip)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, H, P, N, G = 128, 128, 64, 128, 8
    if rung is None:
        assert kern.step_supported((n + 1, N, H * P), G)
        step = jax.jit(kern.step, donate_argnums=6).lower(
            sds((n, H, P)), sds((n, H)), sds((H,)), sds((n, G, N)),
            sds((n, G, N)), sds((H,)), sds((n + 1, N, H * P)),
            sds((n,), jnp.int32)).compile()
        assert step.as_text().count("tpu_custom_call") == 1
        state_bytes = (n + 1) * N * H * P * 4
        assert step.memory_analysis().temp_size_in_bytes < state_bytes // 4
        return
    assert kern.chunk_supported((1, rung, H, P), N, CHUNK, G)

    def prefill(x, dt, a, bm, cm, d, valid):
        return kern.chunk(x, dt, a, bm, cm, d, valid=valid)

    scan = jax.jit(prefill).lower(
        sds((1, rung, H, P)), sds((1, rung, H)), sds((H,)),
        sds((1, rung, G, N)), sds((1, rung, G, N)), sds((H,)),
        sds((1,), jnp.int32)).compile()
    assert scan.as_text().count("tpu_custom_call") == 1
    assert scan.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("rung", [128, 256, 512, 1024])
def test_state_space_scan_kernel_compiles_for_a_described_v5e(chip, rung):
    """The SSD chunked scan over each of the cell's prefill rungs, at the
    chunk the program runs (``ssd_ops.CHUNK``): one Mosaic call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import ssd as kern
    from paddle_tpu.ops.ssd_ops import CHUNK

    one_chip = SingleDeviceSharding(chip)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, P, N = 64, 64, 128
    assert kern.chunk_supported((1, rung, H, P), N, CHUNK)

    def prefill(x, dt, a, bm, cm, d, valid):
        return kern.chunk(x, dt, a, bm, cm, d, valid=valid)

    scan = jax.jit(prefill).lower(
        sds((1, rung, H, P)), sds((1, rung, H)), sds((H,)),
        sds((1, rung, N)), sds((1, rung, N)), sds((H,)),
        sds((1,), jnp.int32)).compile()
    assert scan.as_text().count("tpu_custom_call") == 1
    assert scan.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("rows", [256, 1024])
def test_latent_chunk_kernel_compiles_for_a_described_v5e(chip, rows):
    """The chunk kernel over latent rows at DeepSeek-V2's published head
    sizes (128 heads of nope 128 + rope 64 over a latent of 512, values of
    128) over a slot's view of 12,800 rows of 640 lanes: one Mosaic call,
    and no temporary that grows with context x heads (the 1024 rung's
    expanded keys and values would be 2 GB)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import latent_attention as kern

    one_chip = SingleDeviceSharding(chip)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, dn, dr, dv, C, S = 128, 128, 64, 128, 512, 12800
    assert kern.chunk_supported(H, rows, (S, 640), C, dn, dv)
    compiled = jax.jit(functools.partial(
        kern.mla_chunk_attention, scale=0.11472, nope_dim=dn,
        latent_dim=C)).lower(
        sds((H, rows, dn)), sds((H, rows, dr)), sds((S, 640)),
        sds((C, H * (dn + dv))), sds((1,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # (the rotated queries padded to the row's 128 lanes behind the latent)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.5 * H * rows * 128 * 4

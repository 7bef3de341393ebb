"""A whole-prompt prefill rung's dense products stop at the prompt's end
(ISSUE 64; ISSUE 65: in the rungs of 1024 to 2047 rows too, the fused SwiGLU
and the products of a wide weight): op ``mul_valid_rows`` against the plain
``mul``, ``build_llama_prefill`` with the mechanism on against the same
program with it off (every mixer kind, a dense and a shared-expert FFN,
under the rule of the long rungs, of the short ones and the one that
ships), the programs that must stay the parent's (a pinned hash of one
small program of each kind), and the engine's account of the rows.
"""
import contextlib
import functools
import hashlib
import importlib
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, telemetry
from paddle_tpu.framework import core
from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import math_ops, registry

# (``paddle_tpu.models`` exports a function of the module's name)
llama = importlib.import_module("paddle_tpu.models.llama")

SEG = 16           # the segment the small programs here are built with


def _small_rule(monkeypatch, all_rows=4 * SEG):
    """Segments of 16 rows in rungs of 64 or more; every product from
    ``all_rows`` rows up, under that the fused SwiGLU and the single
    products of a weight of 32 rows or more (``hidden`` here)."""
    monkeypatch.setattr(math_ops, "VALID_ROW_SEGMENT", SEG)
    monkeypatch.setattr(llama, "DENSE_MIN_ROWS", 4 * SEG)
    monkeypatch.setattr(llama, "DENSE_ALL_ROWS", all_rows)
    monkeypatch.setattr(llama, "DENSE_MIN_K", 32)


@pytest.fixture
def small_segment(monkeypatch):
    """The rule of the long rungs at the small size: every product."""
    _small_rule(monkeypatch)


@pytest.fixture
def rule(request, monkeypatch):
    """``every_product``: :func:`small_segment`'s.  ``wide_k_only``: the
    rule of the short rungs at that size (the latent products, 16 weight
    rows, stay plain).  ``ships``: the constants as they are."""
    if request.param == "every_product":
        _small_rule(monkeypatch)
    elif request.param == "wide_k_only":
        _small_rule(monkeypatch, all_rows=1 << 20)
    return request.param


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def _both_products(rung, k=24, n=20):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [1, rung, k], dtype="float32",
                        append_batch_size=False)
        v = layers.data("v", [1], dtype="int32", append_batch_size=False)
        plain = layers.fc(x, n, num_flatten_dims=2, bias_attr=False,
                          param_attr="w")
        stopped = layers.fc_valid_rows(x, n, v, param_attr="w")
    exe = pt.Executor()
    exe.run(startup)
    xv = np.random.default_rng(rung).standard_normal(
        (1, rung, k)).astype("float32")

    def run(valid):
        return exe.run(main, feed={"x": xv,
                                   "v": np.asarray([valid], "int32")},
                       fetch_list=[plain, stopped])
    return run


@pytest.mark.parametrize("rung", [4 * SEG, 4 * SEG + 8])
@pytest.mark.parametrize("valid", ["1", "seg-1", "seg", "seg+1", "rung-1",
                                   "rung"])
def test_mul_valid_rows_is_mul_on_real_rows_and_zero_behind(
        small_segment, rung, valid):
    valid = {"1": 1, "seg-1": SEG - 1, "seg": SEG, "seg+1": SEG + 1,
             "rung-1": rung - 1, "rung": rung}[valid]
    plain, stopped = _both_products(rung)(valid)
    run = min(rung, -(-valid // SEG) * SEG)
    # (XLA:CPU orders a product's accumulation by its shape: last bits)
    np.testing.assert_allclose(stopped[:, :run], plain[:, :run], rtol=0,
                               atol=2e-6)
    assert not stopped[:, run:].any()
    assert run == rung or np.abs(plain[:, run:]).min() > 0


@pytest.mark.parametrize("limit", [None, 0.5])
@pytest.mark.parametrize("valid", [1, SEG, SEG + 1, 4 * SEG + 7])
def test_swiglu_valid_rows_is_the_swiglu_on_real_rows_and_zero_behind(
        small_segment, valid, limit):
    rung, k, width = 4 * SEG + 8, 24, 20
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [1, rung, k], dtype="float32",
                        append_batch_size=False)
        v = layers.data("v", [1], dtype="int32", append_batch_size=False)
        plain = llama._swiglu(x, k, width, "gate_up", "down", limit=limit)
        stopped = llama._swiglu(x, k, width, "gate_up", "down", limit=limit,
                                rows=v)
    assert [op.type for op in main.global_block().ops][-1] \
        == "swiglu_valid_rows"
    exe = pt.Executor()
    exe.run(startup)
    xv = np.random.default_rng(valid).standard_normal(
        (1, rung, k)).astype("float32")
    plain, stopped = exe.run(
        main, feed={"x": xv, "v": np.asarray([valid], "int32")},
        fetch_list=[plain, stopped])
    run = min(rung, -(-valid // SEG) * SEG)
    np.testing.assert_allclose(stopped[:, :run], plain[:, :run], rtol=0,
                               atol=2e-6)
    assert not stopped[:, run:].any() and np.abs(plain).min() > 0


def test_mul_valid_rows_multiplies_nothing_at_valid_zero(small_segment):
    _, stopped = _both_products(4 * SEG)(0)
    assert not stopped.any()


def test_swiglu_valid_rows_refuses_matrices_that_do_not_chain():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [1, 64, 8], dtype="float32",
                        append_batch_size=False)
        v = layers.data("v", [1], dtype="int32", append_batch_size=False)
        block = main.global_block()
        gate_up = layers.data("gate_up", [8, 12], dtype="float32",
                              append_batch_size=False)
        down = layers.data("down", [5, 8], dtype="float32",
                           append_batch_size=False)
        with pytest.raises(Exception, match="swiglu_valid_rows"):
            block.append_op(
                "swiglu_valid_rows",
                inputs={"X": [x], "GateUp": [gate_up], "Down": [down],
                        "ValidRows": [v]},
                outputs={"Out": [block.create_var(name="out",
                                                  dtype="float32")]},
                attrs={"segment": 16})


@pytest.mark.parametrize("x_shape,y_shape,segment", [
    ([2, 64, 8], [8, 4], 16), ([1, 64, 8], [7, 4], 16),
    ([1, 64, 8], [8, 4], 128), ([64, 8], [8, 4], 16)])
def test_mul_valid_rows_refuses_what_it_is_not_built_for(x_shape, y_shape,
                                                         segment):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        block = main.global_block()
        x = layers.data("x", x_shape, dtype="float32",
                        append_batch_size=False)
        y = layers.data("y", y_shape, dtype="float32",
                        append_batch_size=False)
        v = layers.data("v", [1], dtype="int32", append_batch_size=False)
        out = block.create_var(name="out", dtype="float32")
        with pytest.raises(Exception, match="mul_valid_rows"):
            block.append_op("mul_valid_rows",
                            inputs={"X": [x], "Y": [y], "ValidRows": [v]},
                            outputs={"Out": [out]},
                            attrs={"segment": segment})


# ---------------------------------------------------------------------------
# the prefill program, mechanism on against off
# ---------------------------------------------------------------------------

BASE = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
            num_kv_heads=2, intermediate=48)
SHARED = {"experts": 4, "top_k": 2, "width": 24, "activation": "silu",
          "route_from": "normed", "shared_width": 40}
MODELS = {
    "attention_dense": dict(BASE),
    "attention_gate_shared_expert": dict(BASE, layer_pattern=[
        {"attn_gate": True, "ffn": SHARED}, {}]),
    "conv": dict(BASE, layer_pattern=[
        {"mixer": {"kind": "conv", "L_cache": 3, "bias": False}}, {}]),
    "gated_delta": dict(BASE, layer_pattern=[
        {"mixer": {"kind": "gated_delta", "key_heads": 2, "value_heads": 4,
                   "key_dim": 8, "value_dim": 8, "conv": 4,
                   "neg_eigval": True}, "ffn": SHARED}, {}]),
    "ssd": dict(BASE, layer_pattern=[
        {"mixer": {"kind": "ssd", "heads": 4, "head_dim": 8, "state": 8,
                   "groups": 2, "conv": 4, "conv_bias": True}}, {}]),
    "latent": dict(BASE, layer_pattern=[
        {"mla": {"q_rank": 16, "kv_rank": 16, "nope_dim": 8, "rope_dim": 8,
                 "v_dim": 8}, "attn_gate": True}, {}]),
}
PAGE, SLOTS = 8, 2


def _prefill(model, rung, **kw):
    """``(main, startup, feeds, fetches, spec)`` of a paged prefill."""
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = 7
    np_slot = rung // PAGE
    with pt.program_guard(main, startup):
        feeds, fetches = llama.build_llama_prefill(
            1, rung, name="llama", attn_impl="xla", cache_slots=SLOTS,
            max_seq_len=rung, num_pages=SLOTS * np_slot + 1,
            page_tokens=PAGE, **kw, **model)
    spec = llama.cache_spec(
        "llama", model["num_layers"], model.get("layer_pattern"),
        num_slots=SLOTS, num_pages=SLOTS * np_slot + 1, page_tokens=PAGE,
        num_kv_heads=model["num_kv_heads"],
        head_dim=model["hidden"] // model["num_heads"],
        hidden=model["hidden"])
    return main, startup, feeds, fetches, spec


def _ops(main):
    return [op.type for op in main.global_block().ops]


# (rule, rung, prompt): the small segment at every edge of a prompt's last
# segment, and the rule that ships at its shortest rung (Mistral's two
# prompts of ``chat-steady`` that land there short of it, and a full one)
CASES = [(r, 4 * SEG + 8, p) for r in ("every_product", "wide_k_only")
         for p in (1, SEG + 3, 3 * SEG, 4 * SEG + 5)] \
    + [("ships", 1024, p) for p in (593, 1024)]


@pytest.mark.parametrize("kind", sorted(MODELS))
@pytest.mark.parametrize("rule,rung,prompt", CASES, indirect=["rule"],
                         ids=["%s-%d-%d" % c for c in CASES])
def test_prefill_that_stops_at_the_prompt_leaves_what_the_plain_one_does(
        rule, rung, prompt, kind):
    """Logits of the last real row and everything written for the slot
    (pages but the trash page, the slot's state rows) within 1e-6 of
    their range of the same program with the mechanism off (XLA:CPU orders
    a product's accumulation by its shape)."""
    model = MODELS[kind]
    on = _prefill(model, rung)
    off = _prefill(model, rung, stop_at_prompt=False)
    stopped = _ops(on[0]).count("mul_valid_rows") \
        + 2 * _ops(on[0]).count("swiglu_valid_rows")
    # (a SwiGLU's two products are one op where they stop at the prompt)
    assert stopped + _ops(on[0]).count("mul") == _ops(off[0]).count("mul")
    assert "mul_valid_rows" not in _ops(off[0])
    assert "swiglu_valid_rows" in _ops(on[0])
    if rule == "every_product":
        # (the one ``mul`` left is the head's, on the one gathered row)
        assert _ops(on[0]).count("mul") == 1
    elif rule == "ships":
        # (hidden 32: no single product has a weight of 4096 rows)
        assert "mul_valid_rows" not in _ops(on[0])
    else:
        assert "mul_valid_rows" in _ops(on[0])
        assert (_ops(on[0]).count("mul") > 1) == (kind == "latent")
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(on[1], scope=scope)
    np_slot = rung // PAGE
    feed = {"input_ids": np.random.default_rng(prompt).integers(
                0, model["vocab_size"], (1, rung)).astype("int64"),
            "last_pos": np.asarray([prompt - 1], "int64"),
            "block_table": np.arange(1, np_slot + 1,
                                     dtype="int32")[None],
            "prompt_len": np.asarray([prompt], "int32"),
            "slot": np.asarray([1], "int32")}
    left = []
    for main, _startup, feeds, fetches, spec in (on, off):
        for e in spec:
            scope.set_var(e["name"], np.zeros(e["shape"], "float32"))
        logits, = exe.run(main, feed={n: feed[n] for n in feeds},
                          fetch_list=[fetches["logits"]], scope=scope)
        kept = [logits]
        for e in spec:
            got = np.asarray(scope.find_var(e["name"]))
            # (the trash page takes the pad tail's rows: indeterminate)
            kept.append(got[1] if e["kind"] == "slot_state" else got[1:])
        left.append(kept)
    assert len(left[0]) > 1
    for got, want in zip(*left):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-6 * (want.max() - want.min()))
    assert np.abs(left[0][1]).max() > 0


def test_the_unpaged_prefill_and_the_switch_build_the_plain_products(
        small_segment):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        llama.build_llama_prefill(1, 8 * SEG, name="llama", attn_impl="xla",
                                  **MODELS["gated_delta"])
    assert "mul_valid_rows" not in _ops(main)
    assert "mul_valid_rows" not in _ops(
        _prefill(MODELS["ssd"], 4 * SEG - 8)[0])
    assert "mul_valid_rows" in _ops(_prefill(MODELS["ssd"], 4 * SEG)[0])


@pytest.mark.parametrize("rung,prompt,run", [
    (2048, 600, 768), (2048, 2048, 2048), (2048, 1, 256),
    (3712, 3585, 3712), (3712, 3584, 3584), (3712, 1024, 1024),
    (6144, 5878, 5888), (1536, 1, 256),
    # ``chat-steady``'s prompts on their rungs: three on rung 1024, and
    # the rungs under it run every row
    (1024, 593, 768), (1024, 755, 768), (1024, 1024, 1024),
    (512, 325, 512), (256, 133, 256), (128, 87, 128)])
def test_dense_rows_run_at_the_constant_that_ships(rung, prompt, run):
    assert math_ops.VALID_ROW_SEGMENT == 256
    assert (llama.DENSE_MIN_ROWS, llama.DENSE_ALL_ROWS,
            llama.DENSE_MIN_K) == (1024, 2048, 4096)
    assert llama.dense_rows_run(rung, prompt) == run


@pytest.mark.parametrize("rung,k,segment", [
    (1024, None, 256), (1024, 4096, 256), (1024, 14336, 256),
    (1024, 4095, None), (2047, 2048, None), (2047, None, 256),
    (2048, 128, 256), (8192, 64, 256), (1023, None, None),
    (512, 14336, None), (256, None, None)])
def test_which_products_of_which_rungs_the_rule_that_ships_takes(
        rung, k, segment):
    """A rung's fused SwiGLU (k None) and its single products of k weight
    rows: 256-row segments, or the plain product."""
    assert llama.dense_rows_segment(rung, k) == segment


# ---------------------------------------------------------------------------
# programs that must stay the parent's
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _names_from_zero():
    """Build with the name and seed counters at zero, and put them back."""
    ids, seed = dict(core._name_gen._ids), registry._OP_SEED[0]
    core.reset_unique_name()
    registry.reset_op_seed()
    try:
        yield
    finally:
        core._name_gen._ids.update(ids)
        registry.reset_op_seed(seed)


def _hash(build):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with _names_from_zero(), pt.program_guard(main, startup):
        build()
    text = json.dumps(main.to_dict(), sort_keys=True, default=str)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


HYBRID = dict(BASE, num_layers=3, layer_pattern=[
    MODELS["conv"]["layer_pattern"][0],
    MODELS["gated_delta"]["layer_pattern"][0],
    {"attn_gate": True, "ffn": SHARED}])
PAGED = dict(num_pages=2 * 16 + 1, page_tokens=8)


def _paged(rung):
    return dict(name="llama", cache_slots=2, max_seq_len=rung,
                num_pages=2 * (rung // 8) + 1, page_tokens=8)


# Program JSON of PR 64's parent (5216b6b), by this file's ``_hash``
PARENTS = {
    "prefill_512": ("a4b64cd9683877a7", lambda: llama.build_llama_prefill(
        1, 512, **_paged(512), **HYBRID)),
    "prefill_4096_unpaged": ("dddd73f2bd408743", lambda: llama.build_llama_prefill(
        1, 4096, name="llama", **HYBRID)),
    "prefill_block_causal": ("ecfef0c42f5ec100", lambda: llama.build_llama_prefill(
        1, 128, name="llama", cache_slots=2, max_seq_len=128, mask_block=4,
        **PAGED, **BASE)),
    "decode": ("400f874fab3dfea3", lambda: llama.build_llama_decode(
        2, 128, name="llama", **PAGED, **HYBRID)),
    "decode_block": ("8b9d85a17827fc3d", lambda: llama.build_llama_decode(
        2, 128, name="llama", block=4, mask_id=60, **PAGED, **BASE)),
    "chunk_4096": ("576473432ec4844f", lambda: llama.build_llama_prefill_chunk(
        4096, 8192, 2 * 1024 + 1, 8, name="llama", page_aligned=True,
        **MODELS["latent"])),
    "verify": ("4192cc66c8dc0fe0", lambda: llama.build_llama_verify(
        8, 128, 33, 8, name="llama", **BASE)),
    "forward_4096": ("2c9d16fe3fb4e7b2", lambda: llama.build_llama_forward(
        1, 4096, name="llama", **HYBRID)),
    "train_2048": ("934d6ab364582336", lambda: llama.build_llama_train(
        1, 2048, **BASE)),
}


# Rungs of 2048 and more are PR 64's programs: Program JSON of PR 65's
# parent (4b90ba5), which has the mechanism there
LONG_PARENTS = {
    "prefill_2048": ("d4561b0b44d312ec", lambda: llama.build_llama_prefill(
        1, 2048, **_paged(2048), **HYBRID)),
    "prefill_3712_latent": ("8deb905e56394d82", lambda: llama.build_llama_prefill(
        1, 3712, **_paged(3712), **MODELS["latent"])),
}


def _ops_of(build):
    main, startup = pt.Program(), pt.Program()
    with _names_from_zero(), pt.program_guard(main, startup):
        build()
    return _ops(main)


@pytest.mark.parametrize("kind", sorted(PARENTS))
def test_programs_under_the_rule_are_the_parents(kind):
    """Rungs under 1024 rows, every decode, block, chunk and verify
    program and whatever has no ``prompt_len`` (the unpaged prefill, the
    full forward, training) come out as they did before the mechanism."""
    pinned, build = PARENTS[kind]
    assert _hash(build) == pinned


@pytest.mark.parametrize("kind", sorted(LONG_PARENTS))
def test_rungs_of_2048_and_more_are_pr_64s_programs(kind):
    """The rule of the short rungs (ISSUE 65) leaves the long ones'
    programs as they were: every product, 256-row segments."""
    pinned, build = LONG_PARENTS[kind]
    assert "mul_valid_rows" in _ops_of(build)
    assert _hash(build) == pinned


@pytest.mark.parametrize("rung,plain", [(2048, "1fbd2bc474694a9a"),
                                        (1024, "a5d525a1c8bfdee9")])
def test_a_long_rung_is_no_longer_the_parents_program(rung, plain):
    """``plain``: the rung's program before the mechanism reached it (2048:
    PR 64's parent, 1024: PR 65's), which ``stop_at_prompt=False`` still
    builds."""
    on = _hash(lambda: llama.build_llama_prefill(1, rung, **_paged(rung),
                                                 **BASE))
    off = _hash(lambda: llama.build_llama_prefill(
        1, rung, stop_at_prompt=False, **_paged(rung), **BASE))
    assert off == plain and on != off


# ---------------------------------------------------------------------------
# the engine's account
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rung", [2048, 1024])
def test_engine_counts_the_rows_a_prefill_ran_and_skipped(rung):
    from paddle_tpu.serving import GenerationEngine

    kw = dict(num_slots=2, max_seq_len=rung + 64, max_new_tokens=2,
              prefill_buckets=[64, rung], page_tokens=64, prefill_chunk=0,
              prefix_reuse=False, speculate=False, attn_impl="xla", seed=0,
              keep_logits=True, deadline_ms=600000.0,
              # (the float32 table's rungs and segments: the fixture's)
              dtype="float32")
    old = pt.get_flags(["FLAGS_telemetry"])
    pt.set_flags({"FLAGS_telemetry": True})
    eng = GenerationEngine(BASE, **kw)
    plain = GenerationEngine(BASE, scope=eng.scope.new_scope(), **kw)
    plain._build_fn_prefill = functools.partial(llama.build_llama_prefill,
                                                stop_at_prompt=False)
    try:
        on, off = ([op.type for op in e._prefill_prog_for(
            rung)[0].global_block().ops] for e in (eng, plain))
        # (rung 1024 at hidden 32: the SwiGLU alone stops at the prompt)
        assert "swiglu_valid_rows" in on and "swiglu_valid_rows" not in off
        assert ("mul_valid_rows" in on) == (rung == 2048)
        assert "mul_valid_rows" not in off
        run0 = stat_get("serving_prefill_rows_run")
        skip0 = stat_get("serving_prefill_rows_skipped")
        rng = np.random.default_rng(64)
        long, short = (rng.integers(1, 60, n).astype("int64")
                       for n in (600, 40))
        telemetry.clear_spans()
        got = [eng.submit(p).result(timeout=600) for p in (long, short)]
        want = [plain.submit(p).result(timeout=600) for p in (long, short)]
        c = eng.stats()["counters"]
        # 600 tokens on rung 2048 (1024): three segments of 256 run, five
        # (one) are skipped; rung 64 is under the rule: every row runs
        assert (c["prefill_rows_run"], c["prefill_rows_skipped"]) \
            == (768 + 64, rung - 768)
        assert stat_get("serving_prefill_rows_run") - run0 >= 768 + 64
        assert stat_get("serving_prefill_rows_skipped") - skip0 \
            >= rung - 768
        spans = {s.attrs["bucket"]: s.attrs["rows_run"]
                 for s in telemetry.get_spans()
                 if s.name == "generation/prefill"
                 and "rows_run" in s.attrs}
        assert spans == {rung: 768, 64: 64}
        for g, w in zip(got, want):
            assert g["tokens"] == w["tokens"]
            np.testing.assert_allclose(np.asarray(g["logits"]),
                                       np.asarray(w["logits"]), rtol=0,
                                       atol=1e-6)
    finally:
        eng.close()
        plain.close()
        pt.set_flags(old)

"""The ``smallthinker21b-mixedlen`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, and compile-only sizing of its decode
program and widest prefill rung for a described TPU v5e (the topology is
described inside a fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_smallthinker.py -s
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HBM_BYTES = 16 * 2 ** 30
CELL = "smallthinker21b-mixedlen"

from test_manifest import TABLE, check_cell  # noqa: E402


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "smallthinker-21b-a3b.json")
MIX = _json("traffic", "mixedlen-pool.json")


def test_configuration_keeps_every_published_width():
    published = {"hidden_size": 2560, "num_attention_heads": 28,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "moe_num_primary_experts": 64,
                 "moe_num_active_primary_experts": 6,
                 "moe_ffn_hidden_size": 768, "sliding_window_size": 4096,
                 "rope_theta": 1500000, "vocab_size": 151936,
                 "rms_norm_eps": 1e-06, "max_position_embeddings": 16384}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 4          # one whole period
    assert CFG["published"] == {"num_hidden_layers": 52}
    assert CFG["sliding_window_layout"] == [0, 1, 1, 1] * 13 \
        == CFG["rope_layout"]
    assert CFG["as_run"]["dtype"] == "float32"


def test_builder_reads_the_pattern_from_the_layouts():
    import harness

    pattern = harness.load_module("builders", CFG["builder"]) \
        .model_args(CFG)["layer_pattern"]
    assert [(p["window"], p["rope"]) for p in pattern] \
        == [(None, False)] + [(4096, True)] * 3
    assert all(p["ffn"] == {"experts": 64, "top_k": 6, "width": 768,
                            "activation": "relu"} for p in pattern)


def test_mix_is_the_issues_block():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert list(p) == [318, 548, 746, 942, 1148, 1370, 1616, 1894, 2215,
                       2596, 3062, 3655, 4452, 5623, 7651, 8192]
    assert p.sum() == 46028 and o.sum() == 3543
    assert (p > CFG["sliding_window_size"]).sum() == 4
    assert (MIX["workers_per_slot"], MIX["block"], MIX["warm_blocks"]) \
        == (2, 16, 2)
    e = MIX["engine"]
    assert p.max() + o.max() <= e["max_seq_len"]
    assert p.max() <= max(e["prefill_buckets"])


def test_counts_by_hand():
    import ops_bytes_moe as ob

    # a layer: QKV 2560 x 4608, output 3584 x 2560, router 2560 x 64
    assert ob.attention_params(CFG) == 11796480 + 9175040 + 163840
    assert ob.expert_params(CFG) == 3 * 2560 * 768
    assert ob.window_layer_count(CFG) == 3
    assert ob.kv_bytes_per_position(CFG, 4) == 4096
    # nothing routed, nothing cached: attention, norms and the head
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 0.0, 4)
    assert base == 4 * (4 * (21135360 + 5120) + 2560 + 2560 * 151936)
    # 60 experts a layer and 28 contexts of 6000: one full layer reads
    # them whole, three read 4096 of each
    full = ob.decode_step_bytes(CFG, 60.0, 28 * 6000.0, 28 * 4096.0, 4)
    assert full - base == 4 * 4 * 60 * 5898240 \
        + 4096 * (28 * 6000 + 3 * 28 * 4096)
    # prefill inside the window is plain causal attention
    n = 1000
    per_token = 2.0 * (21135360 + 6 * 5898240)
    attn = 4.0 * 128 * 28 * 4 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, n) == pytest.approx(
        4 * per_token * n + attn + 2.0 * 2560 * 151936)
    # past it the window layers attend W keys a token
    n = 8192
    band = 4096 * 4097 / 2 + (n - 4096) * 4096
    attn = 4.0 * 128 * 28 * (n * (n + 1) / 2 + 3 * band)
    assert ob.prefill_flops(CFG, n) == pytest.approx(
        4 * per_token * n + attn + 2.0 * 2560 * 151936)


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "mixedlen-pool"
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # the entries of its groups, each moving the gate, and the start-up
    # account's four: as many values as on its newest ledger line
    assert check_cell(CELL) == TABLE[CELL][2]


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3000000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert out.stdout.count("took the program's choice") == 3 * 4


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control.py``): the reference computed
    in bfloat16 throughout, its router logits handed to the float32
    reference as the program's are, is over ``share_of_range`` on at
    least one prompt, even at the toy widths (measured 0.0193, 0.0128,
    0.0287; the float32 program reads 2e-6 there).  The reading at
    published widths is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 3000000019)
    assert len(got) == 3 and max(rel for _, rel in got) > cell.tolerance


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_tpu(monkeypatch):
    """As ``test_compile_only.as_tpu``: the program asks
    ``jax.default_backend()`` to choose its lowerings; the test answers
    for the described chip, and keeps the compile cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu import compile_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "ensure_compile_cache", lambda: None)
    import paddle_tpu.parallel.sharded as sharded
    monkeypatch.setattr(sharded, "ensure_compile_cache", lambda: None)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def test_programs_compile_and_fit(topo, as_tpu):
    """Weights, both page pools and the step's temporaries fit one chip
    at the mix's 32 slots x 8704; the windowed kernels and the grouped
    expert matmul are in the programs."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import (build_llama_decode,
                                         build_llama_prefill)

    import harness
    from test_compile_only import _compile, _report

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    e = MIX["engine"]
    slots, pt_ = e["num_slots"], e["page_tokens"]
    np_slot = e["max_seq_len"] // pt_
    pages = slots * np_slot + 1
    wpages = slots * (CFG["sliding_window_size"] // pt_ + 1) + 1
    one = list(topo.devices)[:1]

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, _ = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, num_window_pages=wpages,
            **model)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32"),
              "block_tables_window": ((slots, np_slot), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name,
                                      fetches["expert_counts"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"SmallThinker decode step: {slots} slots x "
                    f"{e['max_seq_len']}, {pages} + 3 x {wpages} pages",
                    compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "ragged-dot" in text and "paged_decode_attention" in text

    bucket = max(e["prefill_buckets"])
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches = build_llama_prefill(
            1, bucket, name="llama", attn_impl="auto", cache_slots=slots,
            max_seq_len=e["max_seq_len"], paged=True, num_pages=pages,
            page_tokens=pt_, num_window_pages=wpages, **model)
    shapes = {"input_ids": ((1, bucket), "int64"),
              "last_pos": ((1,), "int64"),
              "block_table": ((1, np_slot), "int32"),
              "prompt_len": ((1,), "int32"),
              "block_table_window": ((1, np_slot), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name,
                                      fetches["expert_counts"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"SmallThinker paged prefill: rung {bucket}", compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "ragged-dot" in text and "tpu_custom_call" in text

"""The ``nemotron3-super-agentfleet`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, the check's plan at 128 slots (compared
prompts land in reused slots), the check's bfloat16 control at toy widths
(NOT correct), the counts of ``ops_bytes_nemotron_h`` by hand, and
compile-only sizing of its decode program at 128 slots x 1280 and of its
widest prefill rung for a described TPU v5e (the topology is described
inside a fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_nemotron_h.py -s
"""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HBM_BYTES = 16 * 2 ** 30
CELL = "nemotron3-super-agentfleet"

import test_manifest as manifest  # noqa: E402
from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import check_cell, check_cell_loads, resolved  # noqa: E402

# the entries PR 59's cell and this one brought are groups of their own,
# and the cell's row is here until a ``benchmark`` PR puts them in
# ``test_manifest`` (PERF.md section 7)
manifest.GROUPS["state space"] = [
    "ssm_step_roofline.pool", "ssm_chunk_roofline.pool",
    "ssm_kernel_share_pct.pool"]
NEW = ["expert_kernel_roofline.pool", "moe_rows_per_held_expert.pool"]
manifest.GROUPS["experts in a latent row"] = NEW
ROW = ("served_tokens_per_s", [
    "closed loop", "experts", "experts, a share held",
    "whole-prompt prefill", "step on its span", "paged decode kernel",
    "slot state", "state space", "experts in a latent row"],
    22 + 2 + 2 + 1 + 1 + 1 + 2 + 3 + 2 + 4)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "nemotron3-super-120b-a12b.json")
MIX = _json("traffic", "agentfleet-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the five cut."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide on this machine")
    with open(catalog) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == sorted(CFG["published"])


def test_the_configurations_arithmetic_is_its_files():
    """ISSUE 63's sizing, from the file's own keys."""
    import ops_bytes_nemotron_h as ob

    m, a = ob.mamba_params(CFG), ob.attention_params(CFG)
    fixed, expert = ob.expert_layer_fixed_params(CFG), ob.expert_params(CFG)
    assert round(m / 1e6, 2) == 109.64 and round(a / 1e6, 2) == 35.65
    assert round(fixed / 1e6, 2) == 54.53 and round(expert / 1e6, 3) == 5.505
    # no chip holds one layer's 512 experts in float32
    assert 512 * expert * 4 > 11.2e9
    kinds = ob.layer_kinds(CFG)
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (5, 5, 1)
    weights = 5 * m + a + 5 * fixed + 5 * 32 * expert \
        + 2 * CFG["vocab_size"] * CFG["hidden_size"]
    assert round(4 * weights / 1e9, 2) == 7.49
    per_slot = 5 * (ob.ssm_state_bytes_per_slot(CFG, 4)
                    + ob.conv_state_bytes_per_slot(CFG, 4))
    assert round(per_slot / 1e6, 1) == 21.6
    slots, seq = MIX["engine"]["num_slots"], MIX["engine"]["max_seq_len"]
    assert round((slots + 1) * per_slot / 1e9, 2) == 2.78
    assert slots * seq * ob.kv_bytes_per_position(CFG, 4) == 2 ** 25 * 10
    # a touched expert multiplies 5.5 rows a step; the deployment's sixteen
    # chips would bring it 88
    assert slots * ob.held_pairs_per_token(CFG) / 32 == 5.5
    assert 16 * 5.5 == 88
    assert seq == max(MIX["engine"]["prefill_buckets"]) \
        + MIX["output_len"]["max"]


def test_counts_by_hand():
    import ops_bytes_nemotron_h as ob

    mamba = 4096 * 18560 + 8192 * 4096 + 10240 * 5 + 3 * 128 + 8192
    assert ob.ssm_dims(CFG) == (128, 64, 128, 8, 8192 + 2 * 8 * 128)
    assert ob.mamba_params(CFG) == mamba == 109635968
    assert ob.attention_params(CFG) == 4096 * (2 * 4096 + 2 * 256) \
        == 35651584
    assert ob.expert_params(CFG) == 2 * 1024 * 2688 == 5505024
    fixed = 4097 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert ob.expert_layer_fixed_params(CFG) == fixed == 54526464
    assert ob.held_pairs_per_token(CFG) == 22 * 32 / 512 == 1.375
    assert ob.kv_bytes_per_position(CFG, 4) == 2 * 2 * 128 * 4 == 2048
    assert ob.ssm_state_bytes_per_slot(CFG, 4) == 128 * 64 * 128 * 4 \
        == 4194304
    assert ob.conv_state_bytes_per_slot(CFG, 4) == 3 * 10240 * 4
    assert ob.paged_kernel_bytes(CFG, 128 * 500.0, 4) == 2048 * 64000
    assert ob.ssm_step_bytes(CFG, 128.0, 4) == 2 * 5 * 128 * 4194304 \
        == 5368709120
    assert resolved("state_slots_pct.pool", CELL)[1]["scale"] \
        == pytest.approx(100 / MIX["engine"]["num_slots"])
    assert ob.ssm_chunk_bytes(CFG, 1000.0, 4) \
        == 4 * 5 * ((2 * 8192 + 2 * 8 * 128 + 128) * 1000 + 8192 * 128)
    # the recurrence's operations take less of the chip than its bytes
    assert ob.ssm_chunk_flops(CFG, 1000.0) / 197e12 \
        < ob.ssm_chunk_bytes(CFG, 1000.0, 4) / 819e9
    # five layers' touched experts and the held pairs' rows, both products
    assert ob.expert_kernel_bytes(CFG, 30.0, 880.0, 4) \
        == 4 * (5 * 30 * 5505024 + 880 * 2 * (1024 + 2688))
    assert resolved("moe_rows_per_held_expert.pool", CELL)[1]["scale"] \
        == 1 / ob.n_of(CFG, "E")
    # no slot, nothing cached, no expert touched: mixers, routers, latent
    # pairs, shared experts, norms, the final norm and the head's slice
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 0.0, 4)
    assert base == 4 * (5 * mamba + 35651584 + 5 * fixed + 11 * 4096
                        + 4096 + 4096 * 16384)
    full = ob.decode_step_bytes(CFG, 32.0, 128 * 500.0, 128.0, 4)
    assert full - base == pytest.approx(
        4 * (5 * 32 * 5505024 + 128 * 4096) + 2048 * 64000 + 5368709120
        + 2 * 5 * 128 * 3 * 10240 * 4)
    # 12.9 GB a step, the state step two fifths of it, the held experts
    # 3.5 GB if all 160 are touched
    assert 12.7e9 < full < 13.0e9
    assert 0.40 < ob.ssm_step_bytes(CFG, 128.0, 4) / full < 0.44
    assert round(4 * 5 * 32 * 5505024 / 1e9, 1) == 3.5
    n = 1000.0
    want = 2 * 4096 * 16384 + 2 * n * (
        5 * (4096 * 18560 + 8192 * 4096) + 35651584
        + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 1.375 * 5505024)) \
        + 5 * (2 * n * 4 * 10240 + 6 * n * 128 * 64 * 128) \
        + 4.0 * 128 * 32 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, 1000) == pytest.approx(want)


def test_mix_is_the_issues_after_its_ladder():
    e = MIX["engine"]
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"]) == ("serve_share", "closed", 2, 16)
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"]) \
        == (128, 1280, 16)
    assert (e["prefill_chunk"], e["prefix_reuse"], e["speculate"]) \
        == (0, False, False)
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.7, "min": 32, "max": 512}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 320,
                                 "sigma": 0.45, "min": 128, "max": 768}
    # of the ladder's step (b) the rung alone went: rung 128 gone, short
    # prompts and the check's fillers padded onto 256, the traffic and the
    # three reference prompts as the issue named them
    assert e["prefill_buckets"] == [256, 512]
    assert MIX["reference_prompts"] == [40, 200, 450]
    assert all(b % 128 == 0 and b % e["page_tokens"] == 0
               for b in e["prefill_buckets"])
    assert "rung 128" in MIX["why"] and "rung 128" in CFG["deployment"]


def test_cell_is_declared_with_its_metrics():
    # (``scan_pad_pct.pool`` sits in the delta rule's group; this cell's
    # scan is the state-space layers', and it reports that one entry of it)
    groups = dict(manifest.GROUPS)
    groups["slot state"] = groups["slot state"] + ["scan_pad_pct.pool"]
    manifest.GROUPS, kept = groups, manifest.GROUPS
    try:
        assert check_cell(CELL, ROW) == ROW[2] == 40
    finally:
        manifest.GROUPS = kept
    check_cell_loads(CELL)
    bench = _json("..", "BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="nemotron3-super-120b-a12b",
                        traffic="agentfleet-pool", chips=1)
    assert len(bench["per_layer"]) == 102 <= 128
    assert [m["name"] for m in bench["per_layer"][-2:]] == NEW


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "6300000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert out.stdout.count("in reused slot") == 2
    assert out.stdout.count("NOT") == 0
    # what the check read, each beside its limit, on the line itself
    check = line["check"]
    assert check["plan_held"] and check["exact_tokens"]
    assert sorted(check["rel"]) == ["27", "5"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())
    assert 0 < check["pairs_held_pct"] < 100
    assert all(t <= n for t, n in zip(check["taken"].values(),
                                      check["near_ties"].values()))


def test_the_check_lands_compared_prompts_in_reused_slots():
    """``serve_state.check_plan`` at the mix's own size: 128 fillers take
    the 128 slots, seven of them (never two side by side, never the edge)
    finish first; the three reference prompts and their joiners follow;
    the fillers' prompts take rung 256, the smallest."""
    import serve_state

    slots = MIX["engine"]["num_slots"]
    plan = serve_state.check_plan(CFG, MIX, 4294967311)
    kinds = [k for _, _, k in plan]
    assert kinds[:slots].count("early") == 7
    assert kinds[slots:] == [0, "joiner", 1, "joiner", 2, "joiner", "joiner"]
    early = [i for i, k in enumerate(kinds[:slots]) if k == "early"]
    assert early[0] >= 1 and early[-1] <= slots - 2
    assert all(b - a > 1 for a, b in zip(early, early[1:]))
    assert [len(plan[i][0]) for i in (slots, slots + 2, slots + 4)] \
        == MIX["reference_prompts"]
    rungs = MIX["engine"]["prefill_buckets"]
    assert sorted({min(b for b in rungs if b >= len(p))
                   for p, _, _ in plan}) == rungs
    assert all(len(p) <= 100 for p, _, k in plan if not isinstance(k, int))
    assert max(n for _, n, _ in plan) <= MIX["output_len"]["max"]


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_nemotron_h.py``): the reference
    computed in bfloat16 throughout goes through the cell's own comparison
    (``serve_state.check_request``) in the program's place and comes out
    not correct, even at the toy widths.  The reading at published widths
    is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control_nemotron_h import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 6300000019)
    assert len(got) == 2 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights, the page pool, both slot states and the temporaries of the
    decode program at the mix's 128 slots x 1280 and of its widest prefill
    rung fit one chip; the paged kernel, the two state-space kernels and
    the grouped kernel of the held experts are in the programs."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import (build_llama_decode,
                                         build_llama_prefill)
    from paddle_tpu.monitor import stat_get

    import harness
    from test_compile_only import _compile, _report

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    e = MIX["engine"]
    slots, pt_ = e["num_slots"], e["page_tokens"]
    np_slot = e["max_seq_len"] // pt_
    pages = slots * np_slot + 1
    one = list(topo.devices)[:1]
    ref0 = stat_get("ssd_lowered_reference")
    pal0 = stat_get("ssd_lowered_pallas")
    rag0 = stat_get("grouped_matmul_lowered_ragged_dot")

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert len(caches) == 2          # the one attention layer's K and V
    state = main.global_block().var("llama.ssm_state_0")
    assert tuple(state.shape) == (slots + 1, 128, 8192)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"nemotron3-super decode program: {slots} slots x "
                    f"{e['max_seq_len']}, {pages} pages", compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "paged_decode_attention" in text
    assert text.count("ssd_step") >= 5
    assert "f32[320,2688]" in text and "grouped_matmul_ragged-dot" in text

    bucket = max(e["prefill_buckets"])
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches = build_llama_prefill(
            1, bucket, name="llama", attn_impl="auto", cache_slots=slots,
            max_seq_len=e["max_seq_len"], paged=True, num_pages=pages,
            page_tokens=pt_, **model)
    shapes = {"input_ids": ((1, bucket), "int64"),
              "last_pos": ((1,), "int64"),
              "block_table": ((1, np_slot), "int32"),
              "prompt_len": ((1,), "int32"), "slot": ((1,), "int32")}
    assert "slot" in feeds
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"nemotron3-super paged prefill: rung {bucket}",
                    compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert text.count("ssd_chunk") >= 5
    assert "f32[1024,2688]" in text
    # ten state-space ops were lowered, every one to its kernel, and every
    # product of the held experts to the grouped kernel
    assert stat_get("ssd_lowered_pallas") == pal0 + 10
    assert stat_get("ssd_lowered_reference") == ref0
    assert stat_get("grouped_matmul_lowered_ragged_dot") == rag0

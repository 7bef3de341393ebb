"""The ``granite4h-micro-manychats`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, the check's plan at 128 slots (compared
prompts land in reused slots), planted faults and the check's bfloat16
control at toy widths (all NOT correct), the readers of its three new
entries, and compile-only sizing of its decode program at 128 slots x 1792
and of its widest prefill rung for a described TPU v5e (the topology is
described inside a fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_granite_hybrid.py -s
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
CELL = "granite4h-micro-manychats"

import test_manifest as manifest  # noqa: E402
from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import check_cell, check_cell_loads, resolved  # noqa: E402

# the three entries this cell brought are a group of their own, and the
# cell's row is here until a ``benchmark`` PR puts both in ``test_manifest``
# (PERF.md section 7)
NEW = ["ssm_step_roofline.pool", "ssm_chunk_roofline.pool",
       "ssm_kernel_share_pct.pool"]
manifest.GROUPS["state space"] = NEW
ROW = ("served_tokens_per_s", [
    "closed loop", "whole-prompt prefill", "step on its span",
    "paged decode kernel", "slot state", "state space"], 22 + 4 + 3 + 4 + 1)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "granite-4.0-h-micro.json")
MIX = _json("traffic", "manychats-pool.json")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the two cut."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    # one whole period of the published pattern (the issue's last resort:
    # two did not fit the run's time limit, PERF.md section 6), no width
    # changed
    assert CFG["num_hidden_layers"] == 10 and CFG["layer_types"] == PERIOD
    assert CFG["published"] == {"num_hidden_layers": 40,
                                "layer_types": PERIOD * 4}
    assert (CFG["as_run"]["dtype"], CFG["as_run"]["attention_precision"]) \
        == ("float32", "highest")
    from paddle_tpu.ops.ssd_ops import CHUNK
    assert CFG["as_run"]["ssm_chunk"] == CHUNK
    assert CFG["assumed"]["eos_id"] == -1
    assert len(CFG["assumed"]["why"]) >= 7 \
        and "pipeline stages" in CFG["deployment"]
    assert CFG["check_tolerance"]["share_of_range"] == 2.0 ** -10
    assert CFG["source"].endswith("ibm-granite/granite-4.0-h-micro/blob/"
                                  "main/config.json")
    # the toy sizes cut widths and depth; the pattern keeps both kinds
    assert set(CFG["rehearse"]["layer_types"]) == {"mamba", "attention"}


def test_builder_reads_the_published_keys():
    import harness

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    ssd = {"kind": "ssd", "heads": 64, "head_dim": 64, "state": 128,
           "groups": 1, "conv": 4, "conv_bias": True}
    common = {"window": None, "rope": False, "ffn": "dense",
              "attn_precision": "highest"}
    assert model["layer_pattern"] == [
        dict(common, mixer=ssd if k == "mamba" else "attention")
        for k in PERIOD]
    assert (model["hidden"], model["num_heads"], model["num_kv_heads"],
            model["intermediate"], model["tie_head"], model["rms_norm_eps"],
            model["vocab_size"], model["embed_scale"],
            model["residual_scale"], model["attn_scale"],
            model["logit_scale"]) \
        == (2048, 32, 8, 8192, True, 1e-5, 100352, 12.0, 0.22, 0.015625,
            0.125)
    assert "head_dim" not in model       # hidden / heads = 64
    assert "rope_base" not in model      # no layer rotates


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert p.min() >= 32 and p.max() <= 1024 and 170 < sorted(p)[8] < 215
    assert o.min() >= 128 and o.max() <= 768 and 300 < sorted(o)[8] < 340
    print(f"\n[manychats-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["trace_s"],
            MIX["deadline_ms"]) == ("serve_delta", "closed", 2, 16, 8, 8,
                                    240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
         "max": 1024},
        {"dist": "lognormal", "median": 320, "sigma": 0.45, "min": 128,
         "max": 768})
    e = MIX["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"],
            e["prefill_buckets"]) == (128, 1792, 16, [128, 256, 512, 1024])
    assert not (e["prefill_chunk"] or e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"] == 1024 + 768
    # rungs are whole pages and whole chunks of the scan
    from paddle_tpu.ops.ssd_ops import CHUNK
    assert all(b % CHUNK == 0 and b % e["page_tokens"] == 0
               for b in e["prefill_buckets"])
    assert MIX["reference_prompts"] == [40, 300, 900]
    # the slots are full when the window opens
    assert MIX["warm_blocks"] * MIX["block"] == e["num_slots"]
    # the stream holds more requests than a window takes (about 350)
    assert MIX["blocks"] * MIX["block"] >= 700


def test_counts_by_hand():
    import ops_bytes_granite_hybrid as ob

    mamba = 2048 * 8512 + 4096 * 2048 + 4352 * 5 + 3 * 64 + 4096
    assert ob.mamba_mixer_params(CFG) == mamba == 25847232
    assert ob.attention_mixer_params(CFG) == 2048 * (2 * 2048 + 2 * 512) \
        == 10485760
    assert ob.dense_params(CFG) == 3 * 2048 * 8192 == 50331648
    assert ob.kv_bytes_per_position(CFG, 4) == 2 * 8 * 64 * 4 == 4096
    assert ob.ssm_state_bytes_per_slot(CFG, 4) == 64 * 64 * 128 * 4 \
        == 2097152
    assert ob.conv_state_bytes_per_slot(CFG, 4) == 3 * 4352 * 4
    assert ob.paged_kernel_bytes(CFG, 128 * 500.0, 4) == 4096 * 64000
    assert ob.ssm_step_bytes(CFG, 128.0, 4) == 2 * 9 * 128 * 2097152 \
        == 4831838208
    assert resolved("state_slots_pct.pool", CELL)[1]["scale"] \
        == pytest.approx(100 / MIX["engine"]["num_slots"])
    assert ob.ssm_chunk_bytes(CFG, 1000.0, 4) \
        == 4 * 9 * ((2 * 4096 + 256 + 64) * 1000 + 64 * 64 * 128)
    # the recurrence's operations take less of the chip than its bytes
    assert ob.ssm_chunk_flops(CFG, 1000.0) / 197e12 \
        < ob.ssm_chunk_bytes(CFG, 1000.0, 4) / 819e9
    # no slot, nothing cached: mixers, SwiGLUs, norms, the final norm and
    # the tied table: 3.81 GB of weights (one period)
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 4)
    assert base == 4 * (9 * mamba + 10485760 + 10 * 50331648
                        + 10 * 2 * 2048 + 2048 + 2048 * 100352)
    assert 3.8e9 < base < 3.82e9
    full = ob.decode_step_bytes(CFG, 128 * 500.0, 128.0, 4)
    assert full - base == pytest.approx(
        4096 * 64000 + 4831838208 + 2 * 9 * 128 * 3 * 4352 * 4)
    # 9 GB a step, of which the state step is more than half
    assert 8.8e9 < full < 9.1e9
    assert ob.ssm_step_bytes(CFG, 128.0, 4) / full > 0.53
    n = 1000.0
    want = 2 * 2048 * 100352 + 2 * n * (
        9 * (2048 * 8512 + 4096 * 2048) + 10485760 + 10 * 50331648) \
        + 9 * (2 * n * 4 * 4352 + 6 * n * 64 * 64 * 128) \
        + 4.0 * 64 * 32 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, 1000) == pytest.approx(want)


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "granite-4.0-h-micro", "manychats-pool")
    # (by count and place at its PR; a later cell comes behind it)
    assert bench["workloads"].index(cell) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = [c for c in bench["configs"]
               if c["name"] == "granite-4.0-h-micro"]
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # a dense decoder with state-space layers: no group of the expert
    # path.  (``scan_pad_pct.pool`` sits in the delta rule's group; this
    # cell's scan is the state-space layers', and it reports that one)
    groups = dict(manifest.GROUPS)
    groups["slot state"] = groups["slot state"] + ["scan_pad_pct.pool"]
    manifest.GROUPS, kept = groups, manifest.GROUPS
    try:
        assert check_cell(CELL, ROW) == ROW[2] == 34
    finally:
        manifest.GROUPS = kept
    assert [m["name"] for m in bench["per_layer"]][97:100] == NEW
    for m in bench["per_layer"][97:100]:
        assert m == {"name": m["name"], "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "served_tokens_per_s", "workloads": [CELL]}
    assert len(bench["per_layer"]) >= 100      # 97 and the cell's three


def test_the_new_entries_read_the_two_kernels_and_leave_out_what_is_not_there():
    import harness
    import ops_bytes_granite_hybrid as ob

    class Span:
        def __init__(self, name, start, **attrs):
            self.name, self.start, self.attrs = name, start, attrs

    class Run:
        peaks = {"hbm_bytes_per_s": 819e9}
        trace_t0, trace_t1 = 100.0, 101.0     # the traced seconds

    trace = {"to_monotonic": 100.0, "busy_s": 0.5,
             "modules": {"decode": [(0.0, 0.01), (0.02, 0.03), (0.5, 0.51)],
                         "p1024": [(0.04, 0.24)], "p256": [(0.3, 0.36)],
                         "tiny": [(0.4, 0.4001)]},
             "op_seconds": {"ssd_chunk.3": 0.004, "fusion.2": 0.2,
                            "ssd_step.1": 0.06},
             "op_text": {"ssd_chunk.3": "%ssd_chunk.3 = (f32[1,1024,4096])",
                         "fusion.2": "%fusion",
                         "ssd_step.1": "%ssd_step.1 = x"}}
    spans = [Span("generation/prefill", 100.035, tokens=900,
                  scan_tokens=900),
             Span("generation/prefill", 100.29, tokens=200, scan_tokens=200),
             Span("generation/decode_step", 100.01, state_slots=128),
             Span("generation/decode_step", 100.02, state_slots=120)]
    ctx = {"run": Run(), "cfg": CFG, "trace": trace, "trace_spans": spans,
           "spans": spans}
    cell = harness.Cell(CELL)
    reader, args = cell.reader_of("ssm_chunk_roofline.pool")
    assert (reader, args) == ("roofline_kernel_prefill", {
        "peak": "hbm_bytes_per_s", "attr": "scan_tokens",
        "pattern": "^%?ssd_chunk",
        "fn": "ops_bytes_granite_hybrid.ssm_chunk_bytes"})
    read = harness.load_module("readers", reader).read
    assert read(ctx, **args) == pytest.approx(
        100 * (ob.ssm_chunk_bytes(CFG, 900, 4)
               + ob.ssm_chunk_bytes(CFG, 200, 4)) / 819e9 / 0.004)
    reader, args = cell.reader_of("ssm_step_roofline.pool")
    assert (reader, args["pattern"], args["attrs"], args["fn"]) == (
        "roofline_kernel", "^%?ssd_step", ["state_slots"],
        "ops_bytes_granite_hybrid.ssm_step_bytes")
    step = harness.load_module("readers", reader).read(ctx, **args)
    assert step == pytest.approx(
        100 * 3 * ob.ssm_step_bytes(CFG, 124.0, 4) / 819e9 / 0.06)
    assert 0 < step < 100
    reader, args = cell.reader_of("ssm_kernel_share_pct.pool")
    assert (reader, args) == ("trace_op_share", {"pattern": "^%?ssd_"})
    assert harness.load_module("readers", reader).read(ctx, **args) \
        == pytest.approx(100 * 0.064 / 0.5)
    # a program without the kernels (the parent's), or no trace: nothing,
    # and nothing raised
    none = dict(trace, op_seconds={"fusion.2": 0.2},
                op_text={"fusion.2": "%fusion"})
    for name in NEW:
        reader, args = cell.reader_of(name)
        read = harness.load_module("readers", reader).read
        assert read(dict(ctx, trace=none), **args) is None
        assert read({}, **args) is None
    # the data files and the cell's own name readers and functions that
    # are there
    check_cell_loads(CELL)


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "5900000019", "--seconds", "2"],
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


def test_the_check_lands_compared_prompts_in_reused_slots():
    """``serve_state.check_plan`` at the mix's own size: 128 fillers take
    the 128 slots, seven of them (never two side by side, never the edge)
    finish first; the three reference prompts and their joiners follow."""
    import serve_state

    slots = MIX["engine"]["num_slots"]
    plan = serve_state.check_plan(CFG, MIX, 4294967311)
    kinds = [k for _, _, k in plan]
    assert kinds[:slots].count("early") == 7
    assert kinds[slots:] == [0, "joiner", 1, "joiner", 2, "joiner",
                             "joiner"]
    early = [i for i, k in enumerate(kinds[:slots]) if k == "early"]
    assert early[0] >= 1 and early[-1] <= slots - 2
    assert all(b - a > 1 for a, b in zip(early, early[1:]))
    assert [len(plan[i][0]) for i in (slots, slots + 2, slots + 4)] \
        == MIX["reference_prompts"]
    rungs = MIX["engine"]["prefill_buckets"]
    assert [min(b for b in rungs if b >= n)
            for n in MIX["reference_prompts"]] == [128, 512, 1024]
    assert all(len(p) <= 100 for p, _, k in plan if not isinstance(k, int))
    # every filler's answer fits the engine's max_new_tokens
    assert max(n for _, n, _ in plan) <= MIX["output_len"]["max"]


FAULTS = [None, "a reused slot keeps its state",
          "the state-space state is not written", "embedding_multiplier",
          "residual_multiplier", "logits_scaling",
          "attention_multiplier (64^-1/2 in its place)"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_catches_a_planted_fault(fault, monkeypatch):
    """``serve_delta.reference_check`` at toy widths on eight slots: the
    compared requests land in reused slots between live neighbours and
    are the reference's; an engine whose prefill writes the trash row
    instead of the slot's, a program whose prefill leaves the state-space
    state unwritten, and a program built without one of the family's four
    multipliers are NOT correct."""
    import harness
    import serve_delta

    cell = harness.Cell(CELL, rehearse=True)
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    builder = cell.builder()
    left_out = {"embedding_multiplier": {"embed_scale": 1.0},
                "residual_multiplier": {"residual_scale": 1.0},
                "logits_scaling": {"logit_scale": 1.0},
                "attention_multiplier (64^-1/2 in its place)":
                    {"attn_scale": None}}
    if fault == "a reused slot keeps its state":
        from paddle_tpu.serving import GenerationEngine

        real = GenerationEngine._run_fetching

        def stale(self, exe, prog, fetches, feed):
            if "slot" in feed and len(self._slots) > 2:
                feed = dict(feed, slot=feed["slot"] * 0 + self.num_slots)
            return real(self, exe, prog, fetches, feed)

        monkeypatch.setattr(GenerationEngine, "_run_fetching", stale)
    elif fault == "the state-space state is not written":
        from paddle_tpu.ops.registry import get_op_def

        write = get_op_def("slot_state_write")
        real_lower = write.lower
        channels = cell.cfg["mamba_n_heads"] * cell.cfg["mamba_d_head"]

        def lower(ctx, op):
            if ctx.get_input(op, "State").shape[-1] == channels:
                return ctx.set_output(op, "StateOut",
                                      ctx.get_input(op, "State"))
            return real_lower(ctx, op)

        monkeypatch.setattr(write, "lower", lower)
    elif fault:
        real_args = builder.model_args
        monkeypatch.setattr(builder, "model_args", lambda cfg: dict(
            real_args(cfg), **left_out[fault]))
    ok, scope = serve_delta.reference_check(run, cell.cfg, cell.mix,
                                            5900000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "in reused slot" in line]
    assert len(lines) == 2
    assert any("NOT correct" in line for line in lines) == bool(fault)
    held = [line for line in said if "used and left" in line]
    assert len(held) == 2 and not any("NOT" in line for line in held)
    assert "prefills wrote a slot's state" in said[-1]


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_granite_hybrid.py``): the
    reference computed in bfloat16 throughout goes through the cell's own
    comparison (``serve_delta.check_request``) in the program's place and
    comes out not correct, even at the toy widths.  The reading at
    published widths is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control_granite_hybrid import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 5900000019)
    assert len(got) == 2 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights, the page pool, both slot states and the temporaries of the
    decode program at the mix's 128 slots x 1792 and of its widest prefill
    rung fit one chip; the paged kernel, the prefill attention kernel and
    the two state-space kernels are in the programs."""
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

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert len(caches) == 2          # the one attention layer's K and V
    state = main.global_block().var("llama.ssm_state_0")
    assert tuple(state.shape) == (slots + 1, 128, 4096)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"granite-4.0-h-micro decode program: {slots} slots x "
                    f"{e['max_seq_len']}, {pages} pages", compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "paged_decode_attention" in text
    assert text.count("ssd_step") >= 9

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
    total = _report(f"granite-4.0-h-micro paged prefill: rung {bucket}",
                    compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert text.count("ssd_chunk") >= 9
    assert "tpu_custom_call" in text
    # eighteen state-space ops were lowered, every one to its kernel
    assert stat_get("ssd_lowered_pallas") == pal0 + 18
    assert stat_get("ssd_lowered_reference") == ref0

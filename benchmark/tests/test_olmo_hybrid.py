"""The ``olmo-hybrid7b-longdoc`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, the check's plan (compared prompts land
in reused slots), a planted state fault and the check's bfloat16 control
at toy widths (both NOT correct), the new reader, and compile-only sizing
of its decode program at 28 slots x 6656 and of its widest prefill rung
for a described TPU v5e (the topology is described inside a fixture; a
compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_olmo_hybrid.py -s
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
CELL = "olmo-hybrid7b-longdoc"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import (  # noqa: E402
    TABLE, check_cell, check_cell_loads, resolved)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "olmo-hybrid-7b.json")
MIX = _json("traffic", "longdoc-pool.json")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the two cut."""
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CFG["num_hidden_layers"] == 4 and CFG["layer_types"] == PERIOD
    assert CFG["published"] == {"num_hidden_layers": 32,
                                "layer_types": PERIOD * 8}
    assert (CFG["as_run"]["dtype"], CFG["as_run"]["attention_precision"]) \
        == ("float32", "highest")
    a = CFG["assumed"]
    assert (a["qk_norm"], a["norm"], a["rope"], a["eos_id"]) \
        == ("proj", "post", False, -1)
    assert len(a["why"]) >= 10 and "first stage" in CFG["deployment"]
    assert CFG["check_tolerance"]["share_of_range"] == 2.0 ** -6
    assert CFG["source"].endswith("allenai/Olmo-Hybrid-7B/blob/main/"
                                  "config.json")
    # the toy sizes cut widths, never the pattern
    assert "layer_types" not in CFG["rehearse"]


def test_builder_reads_the_published_keys():
    import harness

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    delta = {"kind": "gated_delta", "key_heads": 30, "value_heads": 30,
             "key_dim": 96, "value_dim": 192, "conv": 4, "neg_eigval": True}
    common = {"window": None, "rope": False, "ffn": "dense",
              "attn_precision": "highest"}
    assert model["layer_pattern"] == [dict(common, mixer=delta)] * 3 \
        + [dict(common, mixer="attention")]
    assert (model["hidden"], model["num_heads"], model["num_kv_heads"],
            model["intermediate"], model["qk_norm"], model["norm"],
            model["tie_head"], model["rms_norm_eps"], model["vocab_size"]) \
        == (3840, 30, 30, 11008, "proj", "post", False, 1e-6, 100352)
    assert "head_dim" not in model       # hidden / heads = 128
    assert "rope_base" not in model      # no layer rotates


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert p.min() >= 256 and p.max() <= 6144 and 1900 < sorted(p)[8] < 2300
    assert o.min() >= 48 and o.max() <= 512 and 180 < sorted(o)[8] < 210
    print(f"\n[longdoc-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["blocks"], MIX["warm_blocks"], MIX["trace_s"],
            MIX["deadline_ms"]) == ("serve_delta", "closed", 2, 16, 32, 2,
                                    8, 240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 2048, "sigma": 0.8, "min": 256,
         "max": 6144},
        {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 48,
         "max": 512})
    e = MIX["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"],
            e["prefill_buckets"]) == (28, 6656, 16, [512, 1024, 2048, 4096,
                                                     6144])
    assert not (e["prefill_chunk"] or e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    # rungs are whole pages and whole chunks of the scan
    assert all(b % 64 == 0 and b % e["page_tokens"] == 0
               for b in e["prefill_buckets"])
    assert MIX["reference_prompts"] == [300, 1800, 5000]
    # the slots came down from the issue's 32 by its own rule (the 6144
    # rung over 14.5 GB); the warm set stays two blocks
    assert MIX["warm_blocks"] * MIX["block"] >= e["num_slots"]


def test_counts_by_hand():
    import ops_bytes_olmo_hybrid as ob

    assert ob.linear_mixer_params(CFG) == 3840 * 11520 + 2 * 3840 * 5760 \
        + 3840 * 60 + 11520 * 4 == 88750080
    assert ob.attention_mixer_params(CFG) == 4 * 3840 * 3840 == 58982400
    assert ob.dense_params(CFG) == 3 * 3840 * 11008 == 126812160
    assert ob.kv_bytes_per_position(CFG, 4) == 2 * 30 * 128 * 4 == 30720
    assert ob.delta_state_bytes_per_slot(CFG, 4) == 30 * 96 * 192 * 4
    assert ob.conv_state_bytes_per_slot(CFG, 4) == 3 * 11520 * 4
    assert ob.paged_kernel_bytes(CFG, 32 * 3000.0, 4) == 30720 * 96000
    assert ob.delta_step_bytes(CFG, 32.0, 4) \
        == 2 * 3 * 32 * 30 * 96 * 192 * 4 == 424673280
    assert resolved("state_slots_pct.pool", CELL)[1]["scale"] \
        == pytest.approx(100 / MIX["engine"]["num_slots"])
    assert ob.delta_chunk_bytes(CFG, 1000.0, 4) \
        == 4 * 3 * (30 * (192 + 384 + 2) * 1000 + 30 * 96 * 192)
    # no slot, nothing cached: mixers, SwiGLUs, norms and gates' vectors,
    # the final norm and the head
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 4)
    assert base == 4 * (3 * 88750080 + 58982400 + 4 * 126812160
                        + 4 * 2 * 3840 + 3 * (60 + 192) + 2 * 3840
                        + 3840 + 3840 * 100352)
    full = ob.decode_step_bytes(CFG, 32 * 3000.0, 32.0, 4)
    assert full - base == pytest.approx(
        4 * 32 * 3840 + 30720 * 96000 + 424673280
        + 2 * 3 * 32 * 3 * 11520 * 4)
    # the ISSUE's "about 8 GB" a step
    assert 7.5e9 < full < 8.6e9
    n = 1000.0
    want = 2 * 3840 * 100352 + 2 * n * (
        3 * 88750080 + 58982400 + 4 * 126812160) \
        + 3 * 6 * n * 30 * 96 * 192 + 4.0 * 128 * 30 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, 1000) == pytest.approx(want)


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "olmo-hybrid-7b", "longdoc-pool")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = [c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b"]
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # a dense decoder with delta layers: no group of the expert path
    assert check_cell(CELL) == TABLE[CELL][2]
    assert not [g for g in TABLE[CELL][1] if g.startswith("experts")]


def test_the_prefill_kernel_reader_reads_spans_and_leaves_out_what_is_not_there():
    import harness
    import ops_bytes_olmo_hybrid as ob

    class Span:
        def __init__(self, name, start, **attrs):
            self.name, self.start, self.attrs = name, start, attrs

    class Run:
        peaks = {"hbm_bytes_per_s": 819e9}

    chunk = "%gated_delta_chunk.3 = (f32[1,30,96,64,192]) custom-call()"
    trace = {"to_monotonic": 100.0,
             "modules": {"decode": [(0.0, 0.01), (0.02, 0.03), (0.5, 0.51)],
                         "p2048": [(0.04, 0.24)], "p512": [(0.3, 0.36)],
                         "tiny": [(0.4, 0.4001)]},
             "op_seconds": {"gated_delta_chunk.3": 0.004, "fusion.2": 0.2,
                            "gated_delta_step.1": 0.001},
             "op_text": {"gated_delta_chunk.3": chunk, "fusion.2": "%fusion",
                         "gated_delta_step.1": "%gated_delta_step.1 = x"}}
    spans = [Span("generation/prefill", 100.035, tokens=1500,
                  scan_tokens=1500),
             Span("generation/prefill", 100.29, tokens=400, scan_tokens=400),
             Span("generation/decode_step", 100.01, state_slots=32)]
    ctx = {"run": Run(), "cfg": CFG, "trace": trace, "trace_spans": spans}
    reader = harness.load_module("readers", "roofline_kernel_prefill")
    args = ("ops_bytes_olmo_hybrid.delta_chunk_bytes", "hbm_bytes_per_s",
            "scan_tokens", "^%?gated_delta_chunk")
    assert reader.read(ctx, *args) == pytest.approx(
        100 * (ob.delta_chunk_bytes(CFG, 1500, 4)
               + ob.delta_chunk_bytes(CFG, 400, 4)) / 819e9 / 0.004)
    # a program whose spans lack the attribute (the parent's), no trace,
    # or no such kernel: nothing, and nothing raised
    old = [Span("generation/prefill", 100.035, tokens=1500)]
    assert reader.read(dict(ctx, trace_spans=old), *args) is None
    assert reader.read({}, *args) is None
    assert reader.read(ctx, *args[:3], "no such kernel") is None
    # the data files and the cell's own name readers and functions that
    # are there
    check_cell_loads(CELL)


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "4100000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert out.stdout.count("in reused slot") == 2
    assert out.stdout.count("NOT") == 0
    assert "slot-layers of delta state moved on" in out.stdout
    # what the check read, each beside its limit, on the line itself
    check = line["check"]
    assert check["plan_held"] and check["exact_tokens"]
    assert sorted(check["rel"]) == ["100", "5"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())


def test_the_check_lands_compared_prompts_in_reused_slots():
    """``serve_state.check_plan`` at the mix's own size: 28 fillers take
    the 28 slots, seven of them (never two side by side, never the edge)
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
            for n in MIX["reference_prompts"]] == [512, 2048, 6144]
    assert all(len(p) <= 100 for p, _, k in plan if not isinstance(k, int))


@pytest.mark.parametrize("fault", [None, "a reused slot keeps its state",
                                   "the delta state is not written"])
def test_the_check_catches_a_state_fault(fault, monkeypatch):
    """``serve_delta.reference_check`` at toy widths on eight slots: the
    compared requests land in reused slots between live neighbours and
    are the reference's; an engine whose prefill writes the trash row
    instead of the slot's, or a program whose prefill leaves the delta
    state unwritten, is NOT correct."""
    import harness
    import serve_delta

    cell = harness.Cell(CELL, rehearse=True)
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    if fault == "a reused slot keeps its state":
        from paddle_tpu.serving import GenerationEngine

        real = GenerationEngine._run_fetching

        def stale(self, exe, prog, fetches, feed):
            if "slot" in feed and len(self._slots) > 2:
                feed = dict(feed, slot=feed["slot"] * 0 + self.num_slots)
            return real(self, exe, prog, fetches, feed)

        monkeypatch.setattr(GenerationEngine, "_run_fetching", stale)
    elif fault:
        from paddle_tpu.ops.registry import get_op_def

        write = get_op_def("slot_state_write")
        real_lower = write.lower

        def lower(ctx, op):
            if len(ctx.get_input(op, "State").shape) == 4:
                return ctx.set_output(op, "StateOut",
                                      ctx.get_input(op, "State"))
            return real_lower(ctx, op)

        monkeypatch.setattr(write, "lower", lower)
    ok, scope = serve_delta.reference_check(run, cell.cfg, cell.mix,
                                            4100000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "in reused slot" in line]
    assert len(lines) == 2
    assert any("NOT correct" in line for line in lines) == bool(fault)
    held = [line for line in said if "used and left" in line]
    assert len(held) == 2 and not any("NOT" in line for line in held)
    assert "prefills wrote a slot's state" in said[-1]


def stamped_before_submit(builder, cfg, mix, scope, plan):
    """``serve_state.served_plan`` as it was until PR 67: the engine's
    milliseconds added to a stamp the harness took BEFORE ``submit``.
    Kept here as the reading that fails: a plan that held reads as not
    held where a compared request's ``submit`` is slow."""
    import time

    rungs = mix["engine"]["prefill_buckets"]
    buckets = sorted({min(b for b in rungs if b >= len(p))
                      for p, _, _ in plan})
    gen = builder.engine(cfg, mix, scope=scope, keep_logits=True,
                         buckets=buckets)
    try:
        gen.warmup()
        sent, futures = [], []
        for prompt, n_new, kind in plan:
            sent.append(time.monotonic())
            futures.append(gen.submit(prompt, n_new,
                                      keep_logits=isinstance(kind, int)))
        results = [f.result(600) for f in futures]
        times = [(t + r["queue_wait_ms"] / 1e3, t + r["ttft_ms"] / 1e3,
                  t + r["total_ms"] / 1e3) for t, r in zip(sent, results)]
        return results, times, gen.stats()["counters"]
    finally:
        gen.close()
        scope.erase(list(gen.cache_names) + list(gen.state_names))


def test_the_plan_is_read_on_the_engines_clock(monkeypatch):
    """A compared request whose ``submit`` takes 20 ms (a long prompt's
    list turned into an array, the scheduler thread holding the
    interpreter) still reads as landing in a slot its earlier tenant had
    left: ``serve_state.served_plan`` (``serve_delta.served_plan`` is the
    same function since PR 67) tells every time on the engine's clock.
    Times that start at a stamp taken before the call, as
    ``serve_state``'s did until then, read the same plan as NOT held."""
    import time

    import harness
    import serve_delta
    import serve_state
    from paddle_tpu.serving import GenerationEngine

    assert serve_delta.served_plan is serve_state.served_plan
    cell = harness.Cell(CELL, rehearse=True)
    real = GenerationEngine.submit

    def slow(self, prompt, *args, **kw):
        if kw.get("keep_logits"):
            time.sleep(0.02)
        return real(self, prompt, *args, **kw)

    monkeypatch.setattr(GenerationEngine, "submit", slow)
    builder = cell.builder()
    plan = serve_state.check_plan(cell.cfg, cell.mix, 4100000033)
    scope = serve_delta.seeded_scope(builder, cell.cfg, cell.mix,
                                     4100000033)
    for served, holds in ((serve_state.served_plan, True),
                          (stamped_before_submit, False)):
        results, times, _ = served(builder, cell.cfg, cell.mix, scope, plan)
        held, notes = serve_state.plan_held(plan, results, times)
        assert held == holds, notes
        assert len(notes) == 2
        assert all(("0 earlier request(s)" in n) != holds for n in notes)


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_olmo_hybrid.py``): the
    reference computed in bfloat16 throughout goes through the cell's own
    comparison (``serve_delta.check_request``) in the program's place and
    comes out not correct, even at the toy widths.  The reading at
    published widths is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control_olmo_hybrid import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 4100000019)
    assert len(got) == 2 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights, the page pool, both slot states and the temporaries of the
    decode program at the mix's 28 slots x 6656 and of its widest prefill
    rung fit one chip; the paged kernel, the prefill attention kernel and
    the two delta-rule kernels are in the programs."""
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
    ref0 = stat_get("gated_delta_lowered_reference")
    pal0 = stat_get("gated_delta_lowered_pallas")

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert len(caches) == 2          # the one full layer's K and V
    pool = main.global_block().var(caches[0])
    assert tuple(pool.shape) == (pages, 30, pt_, 128)
    state = main.global_block().var("llama.delta_state_0")
    assert tuple(state.shape) == (slots + 1, 30, 96, 192)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"Olmo-Hybrid decode program: {slots} slots x "
                    f"{e['max_seq_len']}, {pages} pages", compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "paged_decode_attention" in text
    assert text.count("gated_delta_step") >= 3

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
    total = _report(f"Olmo-Hybrid paged prefill: rung {bucket}", compiled)
    text = compiled.as_text()
    assert total < 14.5e9               # the issue's line for this rung
    assert text.count("gated_delta_chunk") >= 3
    assert "tpu_custom_call" in text
    # six delta ops were lowered, every one to its kernel
    assert stat_get("gated_delta_lowered_pallas") == pal0 + 6
    assert stat_get("gated_delta_lowered_reference") == ref0

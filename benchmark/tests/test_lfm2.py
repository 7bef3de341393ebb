"""The ``lfm2-24b-longanswer`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, the check's plan (compared prompts land
in reused slots), the check's bfloat16 control at toy widths, and
compile-only sizing of its decode program at 64 slots x 5120 and of its
widest prefill rung for a described TPU v5e (the topology is described
inside a fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_lfm2.py -s
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
CELL = "lfm2-24b-longanswer"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import TABLE, check_cell  # noqa: E402


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "lfm2-24b-a2b.json")
MIX = _json("traffic", "longanswer-pool.json")
PUBLISHED_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                      "conv"] * 9 + ["full_attention", "conv"]


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the three cut."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"]) == (5, 1)
    assert CFG["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"] == PUBLISHED_TYPES[1:6]
    assert CFG["published"] == {"num_hidden_layers": 40,
                                "num_dense_layers": 2,
                                "layer_types": PUBLISHED_TYPES}
    assert (CFG["as_run"]["dtype"], CFG["as_run"]["attention_precision"]) \
        == ("float32", "highest")
    a = CFG["assumed"]
    assert (a["tie_word_embeddings"], a["qk_norm"], a["in_proj_order"],
            a["eos_id"]) == (True, True, ["B", "C", "x"], -1)
    assert 0 < a["expert_bias_scale"] < 0.1 and len(a["why"]) >= 8
    assert "first stage" in CFG["deployment"]
    assert CFG["source"].endswith("LiquidAI/LFM2-24B-A2B/blob/main/"
                                  "config.json")


def test_builder_reads_the_published_keys():
    import harness

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    experts = {"experts": 64, "top_k": 4, "width": 1536,
               "activation": "silu", "route_from": "normed",
               "score": "sigmoid", "expert_bias": True, "norm_topk": True,
               "route_scale": 1.0}
    conv = {"kind": "conv", "L_cache": 3, "bias": False}
    common = {"window": None, "rope": True, "attn_precision": "highest"}
    assert model["layer_pattern"] == [
        dict(common, mixer=conv, ffn="dense"),
        dict(common, mixer="attention", ffn=experts),
        dict(common, mixer=conv, ffn=experts),
        dict(common, mixer=conv, ffn=experts),
        dict(common, mixer=conv, ffn=experts)]
    assert (model["hidden"], model["num_heads"], model["num_kv_heads"],
            model["intermediate"], model["qk_norm"], model["tie_head"],
            model["rope_base"], model["rms_norm_eps"], model["vocab_size"]) \
        == (2048, 32, 8, 11776, True, True, 1e6, 1e-5, 65536)
    assert "head_dim" not in model       # hidden / heads = 64


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert p.min() >= 64 and p.max() <= 4096 and 440 < sorted(p)[8] < 600
    assert o.min() >= 192 and o.max() <= 1024 and 470 < sorted(o)[8] < 560
    print(f"\n[longanswer-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["trace_s"],
            MIX["deadline_ms"]) == ("serve_state", "closed", 2, 16, 4, 8,
                                    240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 64,
         "max": 4096},
        {"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 192,
         "max": 1024})
    e = MIX["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"],
            e["prefill_buckets"]) == (64, 5120, 16, [128, 256, 512, 1024,
                                                     2048, 4096])
    assert not (e["prefill_chunk"] or e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    assert MIX["reference_prompts"] == [70, 900, 3000]
    assert MIX["warm_blocks"] * MIX["block"] == e["num_slots"]


def test_counts_by_hand():
    import ops_bytes_lfm2 as ob

    assert ob.conv_mixer_params(CFG) == 2048 * 6144 + 2048 * 2048 + 2048 * 3
    assert ob.attention_mixer_params(CFG) \
        == 2048 * (2048 + 2 * 512) + 2048 * 2048 + 128
    assert ob.dense_params(CFG) == 3 * 2048 * 11776 == 72351744
    assert ob.expert_params(CFG) == 3 * 2048 * 1536 == 9437184
    assert ob.router_params(CFG) == 2049 * 64
    assert ob.kv_bytes_per_position(CFG, 4) == 4096
    assert ob.state_bytes_per_slot(CFG, 4) == 2 * 2048 * 4
    # nothing routed, nothing cached, no slot: mixers, norms, the dense
    # layer, routers, the final norm and the tied table once
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 0.0, 4)
    mixers = 4 * 16783360 + 10485888
    assert base == 4 * (mixers + 5 * 2 * 2048 + 72351744 + 4 * 2049 * 64
                        + 2048 + 2048 * 65536)
    # 62.7 of 64 experts, 64 slots 1000 deep, all advancing
    full = ob.decode_step_bytes(CFG, 62.7, 64 * 1000.0, 64.0, 4)
    assert full - base == pytest.approx(
        4 * 4 * 62.7 * 9437184 + 4096 * 64 * 1000
        + 2 * 16384 * 4 * 64)
    assert ob.paged_kernel_bytes(CFG, 64 * 1000.0, 4) == 4096 * 64 * 1000
    # the ISSUE's "about 10.9 GB" a step
    assert 10.6e9 < full < 11.2e9
    # prefill of 1000 tokens: four conv mixers, one attention mixer with
    # causal pairs, the dense layer, router and 4 experts in four layers,
    # the head on one row
    n = 1000.0
    want = 2 * 2048 * 65536 + 2 * n * (
        4 * 16783360 + (10485888 - 128) + 72351744
        + 4 * (2048 * 64 + 4 * 9437184)) + 4.0 * 64 * 32 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, 1000) == pytest.approx(want)


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "lfm2-24b-a2b", "longanswer-pool")
    config, = [c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # the entries of its groups, each moving the gate, and the start-up
    # account's four: as many values as on its newest ledger line
    assert check_cell(CELL) == TABLE[CELL][2]


def test_new_readers_read_spans_and_leave_out_what_is_not_there():
    """``roofline_span`` / ``roofline_kernel`` over decode-step spans that
    carry what a step did; a program without such attributes (the
    parent's) or a run without a trace gives nothing, and nothing is
    raised."""
    import harness

    class Span:
        def __init__(self, name, start, **attrs):
            self.name, self.start, self.attrs = name, start, attrs

    class Run:
        trace_t0, trace_t1 = 10.0, 18.0
        peaks = {"hbm_bytes_per_s": 819e9}

    step = "generation/decode_step"
    attrs = ["experts_touched", "live_positions", "state_slots"]
    trace = {"modules": {"decode": [(0.0, 0.02), (0.03, 0.05)],
                         "prefill": [(0.06, 0.16)]},
             "op_seconds": {"custom-call.1": 0.001, "fusion.2": 0.03},
             "op_text": {"custom-call.1": "%custom-call.1 = f32[64,4,8,128]"
                         " custom-call(), custom_call_target="
                         "\"tpu_custom_call\"", "fusion.2": "%fusion.2"}}
    spans = [Span(step, 11.0, experts_touched=62.0, live_positions=64000,
                  state_slots=64),
             Span(step, 12.0, experts_touched=63.0, live_positions=66000,
                  state_slots=64),
             Span(step, 30.0, experts_touched=1.0, live_positions=1,
                  state_slots=1),              # outside the traced seconds
             Span(step, 13.0, active=3)]       # a settle-only span
    ctx = {"run": Run(), "cfg": CFG, "trace": trace, "trace_spans": spans}
    import ops_bytes_lfm2 as ob

    span_reader = harness.load_module("readers", "roofline_span")
    got = span_reader.read(ctx, "ops_bytes_lfm2.decode_step_bytes",
                           "hbm_bytes_per_s", attrs)
    assert got == pytest.approx(
        100 * ob.decode_step_bytes(CFG, 62.5, 65000.0, 64.0, 4)
        / 819e9 / 0.02)
    kernel = harness.load_module("readers", "roofline_kernel")
    got = kernel.read(ctx, "ops_bytes_lfm2.paged_kernel_bytes",
                      "hbm_bytes_per_s", ["live_positions"],
                      "tpu_custom_call")
    assert got == pytest.approx(
        100 * 2 * ob.paged_kernel_bytes(CFG, 65000.0, 4) / 819e9 / 0.001)
    for reader, args in ((span_reader, (attrs,)),
                         (kernel, (["live_positions"], "tpu_custom_call"))):
        fn = "ops_bytes_lfm2.decode_step_bytes"
        assert reader.read({}, fn, "hbm_bytes_per_s", *args) is None
        assert reader.read(dict(ctx, trace_spans=spans[3:]), fn,
                           "hbm_bytes_per_s", *args) is None
    assert kernel.read(ctx, "ops_bytes_lfm2.paged_kernel_bytes",
                       "hbm_bytes_per_s", ["live_positions"],
                       "no such kernel") is None


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3400000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert out.stdout.count("in reused slot") == 2
    assert out.stdout.count("NOT") == 0


def test_the_check_lands_compared_prompts_in_reused_slots():
    """The set-up check's plan at the mix's own size: 64 fillers take the
    64 slots, seven of them (never two side by side, never the edge)
    finish first and together; the three reference prompts and their
    joiners follow and take those slots."""
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
    # filler i joins about step i: the early ones finish together, a few
    # steps behind the last filler, every other outlasts what follows
    ends = [i + n for i, (_, n, _) in enumerate(plan[:slots])]
    assert {ends[i] for i in early} == {slots + serve_state.SETTLE_STEPS}
    rest = [e for i, e in enumerate(ends) if i not in early]
    assert min(rest) >= slots + serve_state.SETTLE_STEPS + 7 + 9 + 7
    assert [len(plan[i][0]) for i in (slots, slots + 2, slots + 4)] \
        == MIX["reference_prompts"]
    assert all(len(p) <= 128 for p, _, k in plan if not isinstance(k, int))
    again = serve_state.check_plan(CFG, MIX, 4294967311)
    assert [(p, n) for p, n, _ in again] == [(p, n) for p, n, _ in plan]


@pytest.mark.parametrize("fault", [None, "a reused slot keeps its state"])
def test_the_check_catches_a_slot_that_is_not_reset(fault, monkeypatch):
    """``serve_state.reference_check`` at toy widths on eight slots: the
    compared requests land in reused slots between live neighbours and
    are the reference's; an engine whose prefill does not overwrite the
    slot's state (it writes the trash row instead) is not correct."""
    import harness
    import serve_state

    cell = harness.Cell(CELL, rehearse=True)
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    if fault:
        from paddle_tpu.serving import GenerationEngine

        real = GenerationEngine._run_fetching

        def stale(self, exe, prog, fetches, feed):
            if "slot" in feed and len(self._slots) > 2:
                feed = dict(feed, slot=feed["slot"] * 0 + self.num_slots)
            return real(self, exe, prog, fetches, feed)

        monkeypatch.setattr(GenerationEngine, "_run_fetching", stale)
    ok, scope = serve_state.reference_check(run, cell.cfg, cell.mix,
                                            3400000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "in reused slot" in line]
    assert len(lines) == 2
    assert any("NOT correct" in line for line in lines) == bool(fault)
    held = [line for line in said if "used and left" in line]
    assert len(held) == 2 and not any("NOT" in line for line in held)
    assert "prefills wrote a slot's state" in said[-1]


def test_a_slow_submit_does_not_decide_correct(monkeypatch):
    """The run the driver's check of PR 67 refused (seed 257746178,
    ``correct`` false on logits 2e-6 to 7e-6 of the range off): a compared
    request whose ``submit`` takes 20 ms.  ``serve_state.reference_check``
    reads the plan on the engine's clock and stays correct, and its
    readings go to ``run.check`` for the result line's last key."""
    import time

    import harness
    import serve_state
    from paddle_tpu.serving import GenerationEngine

    cell = harness.Cell(CELL, rehearse=True)
    real = GenerationEngine.submit

    def slow(self, prompt, *args, **kw):
        if kw.get("keep_logits"):
            time.sleep(0.02)
        return real(self, prompt, *args, **kw)

    monkeypatch.setattr(GenerationEngine, "submit", slow)
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    ok, _ = serve_state.reference_check(run, cell.cfg, cell.mix, 257746178)
    assert ok, said
    assert not any("NOT" in line for line in said)
    check = run.check
    assert check["plan_held"] and check["exact_tokens"]
    assert check["tolerance"] == cell.tolerance
    assert len(check["rel"]) == 2
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())
    assert set(check["router_off"]) == set(check["rel"])


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_lfm2.py``): the reference
    computed in bfloat16 throughout goes through the cell's own
    comparison (``serve_state.check_request``) in the program's place and
    comes out not correct, even at the toy widths.  The reading at
    published widths is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control_lfm2 import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 3400000019)
    assert len(got) == 2 and not all(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights, the page pool, the slot state and the temporaries of the
    decode program at the mix's 64 slots x 5120 and of its widest prefill
    rung fit one chip; the paged kernel (head 64 over a pool packed two
    heads a row), the prefill kernel and the grouped expert matmul are in
    the programs."""
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
    one = list(topo.devices)[:1]

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert len(caches) == 2          # one attention layer's K and V
    pool = main.global_block().var(caches[0])
    assert tuple(pool.shape) == (pages, 4, pt_, 128)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches[n].name for n in (
        "next_token", "expert_counts")], one, [shapes[n] for n in feeds])
    total = _report(f"LFM2 decode program: {slots} slots x "
                    f"{e['max_seq_len']}, {pages} pages", compiled)
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
            page_tokens=pt_, **model)
    shapes = {"input_ids": ((1, bucket), "int64"),
              "last_pos": ((1,), "int64"),
              "block_table": ((1, np_slot), "int32"),
              "prompt_len": ((1,), "int32"), "slot": ((1,), "int32")}
    assert "slot" in feeds
    compiled = _compile(main, feeds, [fetches["next_token"].name,
                                      fetches["expert_counts"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"LFM2 paged prefill: rung {bucket}", compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "ragged-dot" in text and "tpu_custom_call" in text

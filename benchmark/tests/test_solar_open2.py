"""The ``solar-open2-agentturns`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, a program that lacks the share (the
builder refuses before anything is built), a planted state fault and the
check's bfloat16 control at toy widths (both NOT correct), and
compile-only sizing of its decode program at 64 slots x 4864 and of its
widest prefill rung for a described TPU v5e (the topology is described
inside a fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_solar_open2.py -s
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
CELL = "solar-open2-agentturns"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import (  # noqa: E402
    TABLE, check_cell, check_cell_loads, resolved)



def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "solar-open2-250b.json")
MIX = _json("traffic", "agentturns-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the four cut."""
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "gqa_layers",
                              "n_routed_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["gqa_layers"],
            CFG["n_routed_experts"], CFG["vocab_size"]) \
        == (4, [0], 20, 24576)
    assert CFG["published"] == {
        "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
        "n_routed_experts": 320, "vocab_size": 196608}
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    share = CFG["expert_share"]
    assert (share["router_experts"], share["first"]) == (320, 0)
    assert (CFG["as_run"]["dtype"], CFG["as_run"]["attention_precision"]) \
        == ("float32", "highest")
    a = CFG["assumed"]
    assert (a["norm"], a["low_rank"], a["expert_bias_scale"], a["eos_id"]) \
        == ("pre", 128, 0.02, -1)
    assert len(a["why"]) >= 10 and "16 chips" in CFG["deployment"]
    assert CFG["check_tolerance"]["share_of_range"] == 2.0 ** -6
    assert CFG["source"].endswith("upstage/Solar-Open2-250B/blob/main/"
                                  "config.json")
    # the toy sizes cut widths and the share, never the pattern
    assert not {"num_hidden_layers", "gqa_layers"} & set(CFG["rehearse"])


def test_builder_reads_the_published_keys():
    import harness

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    kda = {"kind": "gated_delta", "key_heads": 64, "value_heads": 64,
           "key_dim": 128, "value_dim": 128, "conv": 4, "neg_eigval": True,
           "decay": "channel", "decay_rank": 128, "gate": "sigmoid",
           "gate_rank": 128}
    experts = {"experts": 320, "held": (0, 20), "top_k": 8, "width": 1280,
               "activation": "silu", "route_from": "normed",
               "score": "sigmoid", "expert_bias": True, "norm_topk": True,
               "route_scale": 1.0, "shared_width": 1280}
    common = {"window": None, "rope": False, "ffn": experts,
              "attn_gate": True, "attn_precision": "highest"}
    assert model["layer_pattern"] == [dict(common, mixer="attention")] \
        + [dict(common, mixer=kda)] * 3
    assert (model["hidden"], model["num_heads"], model["num_kv_heads"],
            model["head_dim"], model["intermediate"], model["tie_head"],
            model["rms_norm_eps"], model["vocab_size"]) \
        == (4096, 64, 8, 128, 0, False, 1e-5, 24576)
    assert "rope_base" not in model      # no layer rotates


def test_a_program_without_the_share_is_refused_before_anything_is_built(
        monkeypatch):
    """The parent's program: ``layers.moe_routed_ffn`` knows no ``held``.
    The builder says so and exits; it would otherwise allocate all 320
    experts."""
    import harness
    from paddle_tpu import layers

    builder = harness.load_module("builders", CFG["builder"])

    def old(x, router_x, num_experts, top_k, d_ff, **kw):
        raise AssertionError("nothing is built")

    monkeypatch.setattr(layers, "moe_routed_ffn", old)
    with pytest.raises(SystemExit, match="held"):
        builder.engine(CFG, MIX)


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert p.min() >= 128 and p.max() <= 4096 and 700 < sorted(p)[8] < 850
    assert o.min() >= 128 and o.max() <= 768 and 380 < sorted(o)[8] < 410
    print(f"\n[agentturns-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert (p.sum(), o.sum()) == (17630, 6679)
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["trace_s"],
            MIX["deadline_ms"]) == ("serve_share", "closed", 2, 16, 4, 8,
                                    240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 768, "sigma": 0.9, "min": 128,
         "max": 4096},
        {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128,
         "max": 768})
    e = MIX["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"],
            e["prefill_buckets"]) == (64, 4864, 16, [256, 512, 1024, 2048,
                                                     4096])
    assert not (e["prefill_chunk"] or e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    # rungs are whole pages and whole chunks of the scan
    assert all(b % 64 == 0 and b % e["page_tokens"] == 0
               for b in e["prefill_buckets"])
    assert MIX["reference_prompts"] == [200, 1500, 3500]
    assert MIX["warm_blocks"] * MIX["block"] >= e["num_slots"]


def test_counts_by_hand():
    import ops_bytes_solar_open2 as ob

    assert ob.kda_mixer_params(CFG) == 4096 * 24576 + 8192 * 4096 \
        + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 24576 * 4 \
        + 64 + 8192 + 128 == 137732288
    assert ob.attention_mixer_params(CFG) \
        == 4096 * 10240 + 2 * 8192 * 4096 == 109051904
    assert ob.expert_params(CFG) == 3 * 4096 * 1280 == 15728640
    assert ob.router_params(CFG) == 4097 * 320
    assert ob.held_pairs_per_token(CFG) == 0.5
    assert ob.kv_bytes_per_position(CFG, 4) == 2 * 8 * 128 * 4 == 8192
    assert ob.kda_state_bytes_per_slot(CFG, 4) == 64 * 128 * 128 * 4
    assert ob.conv_state_bytes_per_slot(CFG, 4) == 3 * 24576 * 4
    assert ob.paged_kernel_bytes(CFG, 64 * 1500.0, 4) == 8192 * 96000
    assert ob.kda_step_bytes(CFG, 64.0, 4) \
        == 2 * 3 * 64 * 64 * 128 * 128 * 4 == 1610612736
    assert resolved("state_slots_pct.pool", CELL)[1]["scale"] \
        == pytest.approx(100 / MIX["engine"]["num_slots"])
    assert ob.kda_chunk_bytes(CFG, 1000.0, 4) \
        == 4 * 3 * (64 * (5 * 128 + 1) * 1000 + 64 * 128 * 128)
    # no slot, nothing cached, no held expert touched: mixers, norms,
    # routers, the shared experts, the final norm and the head
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 0.0, 4)
    assert base == 4 * (3 * 137732288 + 109051904 + 4 * 2 * 4096
                        + 4 * (4097 * 320 + 15728640)
                        + 4096 + 4096 * 24576)
    full = ob.decode_step_bytes(CFG, 16.0, 64 * 1500.0, 64.0, 4)
    assert full - base == pytest.approx(
        4 * 64 * 4096 + 4 * 4 * 16 * 15728640 + 8192 * 96000 + 1610612736
        + 2 * 3 * 64 * 3 * 24576 * 4)
    # the ISSUE's "about 9.1 GB" a step
    assert 8.6e9 < full < 9.6e9
    n = 1000.0
    want = 2 * 4096 * 24576 + 2 * n * (
        3 * (137732288 - 24576 * 4 - 64 - 8192 - 128) + 109051904
        + 4 * (4096 * 320 + 1.5 * 15728640)) \
        + 3 * (2 * n * 4 * 24576 + 7 * n * 64 * 128 * 128) \
        + 4.0 * 128 * 64 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, 1000) == pytest.approx(want)


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "solar-open2-250b", "agentturns-pool")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = [c for c in bench["configs"]
               if c["name"] == "solar-open2-250b"]
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # one chip's share of the experts: the share's two entries, not the
    # two of a cell that holds them all
    assert check_cell(CELL) == TABLE[CELL][2]
    assert "experts, a share held" in TABLE[CELL][1] \
        and "experts, all held" not in TABLE[CELL][1]
    # the data files and the cell's own name readers and functions that
    # are there
    check_cell_loads(CELL)


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "4300000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert out.stdout.count("in reused slot") == 2
    assert out.stdout.count("NOT") == 0
    assert "slot-layers of delta state moved on" in out.stdout
    assert "were held here" in out.stdout
    # what the check read, each beside its limit, on the line itself
    check = line["check"]
    assert check["plan_held"] and check["exact_tokens"]
    assert sorted(check["rel"]) == ["100", "5"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())
    assert check["near_tie_margin"] == 0.002
    # toy share: 4 of 16 experts held, 3 a token
    assert 10 < check["pairs_held_pct"] < 45


@pytest.mark.parametrize("fault", [None, "a reused slot keeps its state",
                                   "the held range is off by one"])
def test_the_check_catches_a_fault(fault, monkeypatch):
    """``serve_share.reference_check`` at toy widths on eight slots: the
    compared requests land in reused slots between live neighbours and
    are the reference's; an engine whose prefill writes the trash row
    instead of the slot's, or an expert layer that takes the pairs of
    experts 5..8 for those of 4..7, is NOT correct."""
    import harness
    import serve_share

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
        from paddle_tpu.parallel import moe

        real_tokens = moe.moe_routed_tokens

        def shifted(*args, held_first=None, **kw):
            return real_tokens(*args, held_first=held_first + 1, **kw)

        monkeypatch.setattr(moe, "moe_routed_tokens", shifted)
    ok, scope = serve_share.reference_check(run, cell.cfg, cell.mix,
                                            4300000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "in reused slot" in line]
    assert len(lines) == 2
    assert any("NOT correct" in line for line in lines) == bool(fault)
    held = [line for line in said if "used and left" in line]
    assert len(held) == 2 and not any("NOT" in line for line in held)
    assert "were held here" in said[-1]
    assert run.check["tolerance"] == cell.tolerance


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_solar_open2.py``): the
    reference computed in bfloat16 throughout goes through the cell's own
    comparison (``serve_state.check_request``) in the program's place and
    comes out not correct, even at the toy widths.  The reading at
    published widths is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control_solar_open2 import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 4300000019)
    assert len(got) == 2 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights (the 20 held experts a layer, not 320), the page pool, both
    slot states and the temporaries of the decode program at the mix's 64
    slots x 4864 and of its widest prefill rung fit one chip; the paged
    kernel, the prefill attention kernel and the two delta-rule kernels
    are in the programs, every delta op with a decay a channel."""
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
    chan0 = stat_get("gated_delta_lowered_channel_decay")

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert len(caches) == 2          # the one softmax layer's K and V
    block = main.global_block()
    assert tuple(block.var(caches[0]).shape) == (pages, 8, pt_, 128)
    assert tuple(block.var("llama.delta_state_1").shape) \
        == (slots + 1, 64, 128, 128)
    assert tuple(block.var("llama.conv_state_1").shape) \
        == (slots + 1, 3, 24576)
    assert tuple(block.var("llama.blk0.moe.gate_up.w").shape) \
        == (20, 4096, 2560)
    assert tuple(block.var("llama.blk0.moe.router.w").shape) == (4096, 320)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"Solar-Open2 decode program: {slots} slots x "
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
    total = _report(f"Solar-Open2 paged prefill: rung {bucket}", compiled)
    text = compiled.as_text()
    assert total < 14.5e9               # the issue's line for this rung
    assert text.count("gated_delta_chunk") >= 3
    assert "tpu_custom_call" in text
    # six delta ops were lowered, every one to its kernel, every one with
    # a decay a channel
    assert stat_get("gated_delta_lowered_pallas") == pal0 + 6
    assert stat_get("gated_delta_lowered_reference") == ref0
    assert stat_get("gated_delta_lowered_channel_decay") == chan0 + 6

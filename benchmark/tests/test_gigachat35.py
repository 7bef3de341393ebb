"""The ``gigachat35-ragturns`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, a program that lacks the latent cache
kind (the builder refuses before anything is built), planted faults (a
stale latent page, a reused slot's state, a held range off by one) and the
check's bfloat16 control at toy widths (all NOT correct), and compile-only
sizing of its decode program at 32 slots x 2816 and of its widest prefill
rung for a described TPU v5e (the topology is described inside a fixture;
a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_gigachat35.py -s
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
CELL = "gigachat35-ragturns"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import (  # noqa: E402
    TABLE, check_cell, check_cell_loads, resolved)



def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "gigachat35-432b-a28b.json")
MIX = _json("traffic", "ragturns-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the six cut."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        row, = [r for r in map(json.loads, open(catalog))
                if r["name"] == "GigaChat3.5-432B-A28B"]
        assert CFG["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (CFG["published"] if k in CFG["reduced"]
                    else CFG)[k] == v, k
    assert CFG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "full_attention_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert [CFG[k] for k in CFG["reduced"]] == [5, 1, [1], 8, 16032, 0]
    assert CFG["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 3,
        "full_attention_layers": list(range(3, 40, 4)),
        "n_routed_experts": 256, "vocab_size": 128256,
        "num_nextn_predict_layers": 2}
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    # every width as published
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"], CFG["kv_lora_rank"],
            CFG["q_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"],
            CFG["num_attention_heads"], CFG["linear_num_key_heads"],
            CFG["linear_num_value_heads"], CFG["linear_key_head_dim"],
            CFG["linear_value_head_dim"], CFG["num_experts_per_tok"]) \
        == (7168, 18432, 2048, 512, 1536, 128, 64, 128, 64, 32, 64, 128,
            128, 8)
    share = CFG["expert_share"]
    assert (share["router_experts"], share["first"]) == (256, 0)
    run = CFG["as_run"]
    assert (run["dtype"], run["attention_precision"]) \
        == ("float32", "highest")
    assert run["latent_row"]["lanes"] == 640 \
        and run["latent_row"]["bytes"] == 2560
    a = CFG["assumed"]
    assert (a["expert_bias_scale"], a["eos_id"]) == (0.02, -1)
    assert len(a["why"]) >= 14 and "32 chips" in CFG["deployment"]
    assert CFG["source"].endswith("ai-sage/GigaChat3.5-432B-A28B/blob/main/"
                                  "config.json")
    # the toy sizes cut widths and the share, never the pattern
    assert not set(CFG["reduced"][:3]) & set(CFG["rehearse"])


def test_builder_reads_the_published_keys():
    import harness

    builder = harness.load_module("builders", CFG["builder"])
    model = builder.model_args(CFG)
    delta = {"kind": "gated_delta", "key_heads": 32, "value_heads": 64,
             "key_dim": 128, "value_dim": 128, "conv": 4,
             "gate": "sigmoid", "gate_scale": 2.0}
    experts = {"experts": 256, "held": (0, 8), "top_k": 8, "width": 2048,
               "activation": "silu", "route_from": "normed",
               "score": "sigmoid", "expert_bias": True, "norm_topk": True,
               "route_scale": 2.5, "shared_width": 2048}
    pattern = model["layer_pattern"]
    assert [lay["ffn"] == "dense" for lay in pattern] \
        == [True, False, False, False, False]
    assert all(lay["ffn"] == experts for lay in pattern[1:])
    assert [lay["mixer"] for lay in pattern] \
        == [delta, "attention", delta, delta, delta]
    mla = pattern[1]["mla"]
    assert {k: mla[k] for k in ("q_rank", "kv_rank", "nope_dim", "rope_dim",
                                "v_dim", "interleave")} \
        == {"q_rank": 1536, "kv_rank": 512, "nope_dim": 128, "rope_dim": 64,
            "v_dim": 128, "interleave": True}
    assert mla["yarn"] == {"factor": 8, "original_max": 32768,
                           "beta_fast": 32, "beta_slow": 1}
    assert mla["scale"] == pytest.approx(192 ** -0.5 * 1.2079441541679836
                                         ** 2)
    assert pattern[1]["attn_gate"] is True
    assert all(lay["swiglu_limit"] == 10.0 for lay in pattern)
    assert (model["hidden"], model["num_heads"], model["intermediate"],
            model["tie_head"], model["rms_norm_eps"], model["vocab_size"],
            model["norm"], model["rope_base"]) \
        == (7168, 64, 18432, False, 1e-6, 16032, "pre_post", 100000.0)


def test_a_program_without_latent_pages_is_refused_before_anything_is_built(
        monkeypatch):
    """The parent's program: the layer pattern knows no ``mla``.  The
    builder says so and exits before an engine, a device or a weight."""
    import importlib

    import harness

    llama = importlib.import_module("paddle_tpu.models.llama")
    builder = harness.load_module("builders", CFG["builder"])
    monkeypatch.setattr(llama, "DEFAULT_LAYER", {
        k: v for k, v in llama.DEFAULT_LAYER.items() if k != "mla"})
    with pytest.raises(SystemExit, match="latent"):
        builder.engine(CFG, MIX)


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert p.min() >= 128 and p.max() <= 2048 and 590 < sorted(p)[8] < 720
    assert o.min() >= 128 and o.max() <= 768 and 380 < sorted(o)[8] < 410
    print(f"\n[ragturns-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert (p.sum(), o.sum()) == (12860, 6679)
    rungs = MIX["engine"]["prefill_buckets"]
    padded = [min(b for b in rungs if b >= n) for n in p]
    assert sum(padded) == 16896
    assert [padded.count(b) for b in rungs] == [2, 4, 6, 4]
    chunks = sum(b // 64 for b in padded)
    assert (chunks, chunks - sum(-(-int(n) // 64) for n in p)) == (264, 55)
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["trace_s"],
            MIX["deadline_ms"]) == ("serve_share", "closed", 2, 16, 2, 8,
                                    240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 640, "sigma": 0.8, "min": 128,
         "max": 2048},
        {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128,
         "max": 768})
    e = MIX["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"],
            e["prefill_buckets"]) == (32, 2816, 16, [256, 512, 1024, 2048])
    assert not (e["prefill_chunk"] or e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    assert all(b % 64 == 0 and b % e["page_tokens"] == 0
               for b in e["prefill_buckets"])
    assert MIX["reference_prompts"] == [200, 900, 1900]
    assert MIX["warm_blocks"] * MIX["block"] >= e["num_slots"]
    assert MIX["users"] and MIX["why"] and MIX["rehearse"]


def test_counts_by_hand():
    import ops_bytes_gigachat35 as ob

    h = 7168
    assert ob.delta_dims(CFG) == (64, 128, 128, 16384)
    assert ob.delta_mixer_params(CFG) == h * 16384 + 2 * h * 8192 \
        + h * 128 + 16384 * 4 + 128 + 128 == 235864320
    assert ob.mla_mixer_params(CFG) == h * 1536 + 1536 + 1536 * 64 * 192 \
        + h * 576 + 512 + 512 * 64 * 256 + 2 * 8192 * h == 159844352
    assert ob.dense_params(CFG) == 3 * h * 18432 == 396361728
    assert ob.expert_params(CFG) == 3 * h * 2048 == 44040192
    assert ob.router_params(CFG) == (h + 1) * 256
    assert ob.held_pairs_per_token(CFG) == 0.25
    assert ob.latent_row_bytes(CFG, 4) == 2560
    assert ob.delta_state_bytes_per_slot(CFG, 4) == 64 * 128 * 128 * 4
    assert ob.conv_state_bytes_per_slot(CFG, 4) == 3 * 16384 * 4
    assert ob.mla_decode_bytes(CFG, 32 * 1000.0, 4) == 2560 * 32000
    assert ob.mla_decode_flops(CFG, 32 * 1000.0) \
        == 2 * 64 * (576 + 512) * 32000
    # 60 operations a cached byte at the unpadded row, 54 as run
    assert ob.mla_decode_flops(CFG, 1.0) / 2304 == pytest.approx(60.4, 0.01)
    assert ob.mla_prefill_flops(CFG, 1000.0) \
        == 2 * 64 * 320 * 1000 * 1001 / 2
    assert ob.delta_step_bytes(CFG, 32.0, 4) \
        == 2 * 4 * 32 * 64 * 128 * 128 * 4 == 1073741824
    assert ob.delta_chunk_bytes(CFG, 1000.0, 4) \
        == 4 * 4 * (64 * (4 * 128 + 2) * 1000 + 64 * 128 * 128)
    assert resolved("state_slots_pct.pool", CELL)[1]["scale"] \
        == pytest.approx(100 / MIX["engine"]["num_slots"])
    e = MIX["engine"]
    assert resolved("latent_fill_pct.pool", CELL)[1]["scale"] \
        == pytest.approx(100 / (e["num_slots"] * e["max_seq_len"]))
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 0.0, 4)
    assert base == 4 * (4 * 235864320 + 159844352 + 5 * 4 * h + 396361728
                        + 4 * ((h + 1) * 256 + 44040192)
                        + h + h * 16032)
    full = ob.decode_step_bytes(CFG, 5.0, 32 * 1000.0, 32.0, 4)
    assert full - base == pytest.approx(
        4 * 32 * h + 4 * 4 * 5 * 44040192 + 2560 * 32000 + 1073741824
        + 2 * 4 * 32 * 3 * 16384 * 4)
    # the ISSUE's "about 12 GB" a step
    assert 11.3e9 < full < 12.6e9
    n = 1000.0
    want = 2 * h * 16032 + 2 * n * (
        4 * (235864320 - 16384 * 4 - 256) + (159844352 - 2048) + 396361728
        + 4 * (h * 256 + 1.25 * 44040192)) \
        + 4 * (2 * n * 4 * 16384 + 6 * n * 64 * 128 * 128) \
        + 2 * 64 * 320 * n * (n + 1) / 2
    assert ob.prefill_flops(CFG, 1000) == pytest.approx(want)
    # 2 x 1,727 M parameters a row are run (the ISSUE reckoned "about
    # 3.0 GFLOP a row"; the mixers, the dense SwiGLU and 1.25 experts a
    # layer make 3.45, the recurrence and the attention the rest)
    assert 3.3e9 < ob.prefill_flops(CFG, 1000) / 1000 < 3.7e9


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "gigachat35-432b-a28b", "ragturns-pool")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = [c for c in bench["configs"]
               if c["name"] == "gigachat35-432b-a28b"]
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # one chip's share of the experts, and the one cell with latent pages
    assert check_cell(CELL) == TABLE[CELL][2]
    assert [c for c, row in TABLE.items() if "latent pages" in row[1]] \
        == [CELL]
    # the data files and the cell's own name readers and functions that
    # are there
    check_cell_loads(CELL)


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "4700000019", "--seconds", "2"],
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
    check = line["check"]
    assert check["plan_held"] and check["exact_tokens"]
    assert sorted(check["rel"]) == ["100", "5"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())
    # toy share: 4 of 16 experts held, 3 a token
    assert 10 < check["pairs_held_pct"] < 45


FAULTS = [None, "a stale latent page", "a reused slot keeps its state",
          "the held range is off by one"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_catches_a_fault(fault, monkeypatch):
    """``serve_share.reference_check`` at toy widths on eight slots: the
    compared requests land in reused slots between live neighbours and
    are the reference's; an engine whose prefill leaves the latent rows
    of the slot's last tenant where they lay (its rows go to the trash
    page), whose prefill writes the trash row instead of the slot's state,
    or an expert layer that takes the pairs of experts 5..8 for those of
    4..7, is NOT correct."""
    import harness
    import serve_share

    cell = harness.Cell(CELL, rehearse=True)
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    from paddle_tpu.serving import GenerationEngine

    real = GenerationEngine._run_fetching
    if fault == "a stale latent page":
        def stale(self, exe, prog, fetches, feed):
            if "slot" in feed and len(self._slots) > 2 \
                    and feed["prompt_len"][0] > 8:
                # the pages keep what they held: the prompt's first page
                # of rows goes to the trash page
                table = feed["block_table"].copy()
                table[0, 0] = 0
                feed = dict(feed, block_table=table)
            return real(self, exe, prog, fetches, feed)

        monkeypatch.setattr(GenerationEngine, "_run_fetching", stale)
    elif fault == "a reused slot keeps its state":
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
                                            4700000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "in reused slot" in line]
    assert len(lines) == 2
    assert any("NOT correct" in line for line in lines) == bool(fault)
    held = [line for line in said if "used and left" in line]
    assert len(held) == 2 and not any("NOT" in line for line in held)
    assert "were held here" in said[-1]
    assert run.check["tolerance"] == cell.tolerance


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_gigachat35.py``): the reference
    computed in bfloat16 throughout goes through the cell's own comparison
    in the program's place and comes out not correct, even at the toy
    widths.  The reading at published widths is taken on the chip."""
    import harness
    from bf16_control_gigachat35 import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 4700000019)
    assert len(got) == 2 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights (8 held experts a layer, not 256), the latent pool, both
    slot states and the temporaries of the decode program at the mix's 32
    slots x 2816 and of its widest prefill rung fit one chip under the
    issue's 15.6 GB; both latent kernels and the two delta-rule kernels
    are in the programs."""
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
    before = {k: stat_get(k) for k in (
        "gated_delta_lowered_reference", "gated_delta_lowered_pallas",
        "attention_lowered_latent_decode",
        "attention_lowered_latent_decode_reference",
        "attention_lowered_latent_prefill", "kv_pool_write_pages",
        "kv_pool_write_rows")}

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert caches == ["llama.pool_c_1"]   # ONE pool, the latent layer's
    block = main.global_block()
    assert tuple(block.var(caches[0]).shape) == (pages, 1, pt_, 640)
    assert tuple(block.var("llama.delta_state_0").shape) \
        == (slots + 1, 64, 128, 128)
    assert tuple(block.var("llama.conv_state_0").shape) \
        == (slots + 1, 3, 16384)
    assert tuple(block.var("llama.blk1.moe.gate_up.w").shape) \
        == (8, 7168, 4096)
    assert tuple(block.var("llama.blk1.moe.router.w").shape) == (7168, 256)
    assert tuple(block.var("llama.blk1.kv_b.w").shape) == (512, 64 * 256)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    decode = _report(f"GigaChat3.5 decode program: {slots} slots x "
                     f"{e['max_seq_len']}, {pages} pages", compiled)
    text = compiled.as_text()
    assert decode < 15.6e9
    assert "mla_decode_attention" in text
    assert text.count("gated_delta_step") >= 4

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
    rung = _report(f"GigaChat3.5 paged prefill: rung {bucket}", compiled)
    text = compiled.as_text()
    assert rung < 15.6e9                 # the issue's line
    assert "mla_prefill_attention" in text
    assert text.count("gated_delta_chunk") >= 4
    after = {k: stat_get(k) - v for k, v in before.items()}
    assert after == {
        "gated_delta_lowered_reference": 0, "gated_delta_lowered_pallas": 8,
        "attention_lowered_latent_decode": 1,
        "attention_lowered_latent_decode_reference": 0,
        "attention_lowered_latent_prefill": 1, "kv_pool_write_pages": 1,
        "kv_pool_write_rows": 0}

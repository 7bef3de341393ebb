"""The ``command-a-plus-ragdocs`` cell without a chip: its files and
arithmetic, a ``--rehearse`` run, a program that lacks the parallel block
or a chunk program over two page kinds (the builder refuses before
anything is built), planted faults (a window page released one chunk
early, a ``base`` off by one, a held range off by one, the shared mean
taken as a sum) and the check's bfloat16 control at toy widths (all NOT
correct), and compile-only sizing of its decode program at 10 slots x
12,800 and of its widest chunk rung for a described TPU v5e, which holds
no pool-sized copy, no ``[H, C, S]`` scores and no K / V repeated to the
query heads (the topology is described inside a fixture; a compile that
passes is not a chip run).

    python -m pytest benchmark/tests/test_command_a_plus.py -s
"""
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "command-a-plus-ragdocs"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import (  # noqa: E402
    TABLE, check_cell, check_cell_loads, resolved)



def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "command-a-plus-05-2026.json")
MIX = _json("traffic", "ragdocs-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the four cut."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        row, = [r for r in map(json.loads, open(catalog))
                if r["name"] == "command-a-plus-05-2026"]
        assert CFG["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (CFG["published"] if k in CFG["reduced"]
                    else CFG)[k] == v, k
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"]
    assert [CFG[k] for k in CFG["reduced"]] == [
        4, ["sliding_attention"] * 3 + ["full_attention"], 8, 32768]
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (32, 128, 262144)
    assert pub["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert CFG["vocab_size"] * 8 == pub["vocab_size"]
    # every width as published
    assert (CFG["hidden_size"], CFG["intermediate_size"], CFG["head_dim"],
            CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["num_experts_per_tok"], CFG["num_shared_experts"],
            CFG["sliding_window"], CFG["prefix_dense_intermediate_size"]) \
        == (4096, 4096, 128, 128, 8, 8, 4, 4096, 16384)
    share = CFG["expert_share"]
    assert (share["router_experts"], share["first"]) == (128, 0)
    run = CFG["as_run"]
    assert (run["dtype"], run["attention_precision"], run["vision_rows"]) \
        == ("float32", "highest", "not run")
    a = CFG["assumed"]
    assert a["eos_id"] == -1 and len(a["why"]) >= 12
    assert "16 chips" in CFG["deployment"]
    tol = CFG["check_tolerance"]
    assert 0 < tol["share_of_range"] <= 2.0 ** -7
    assert tol["near_tie_margin_share_of_router_range"] == 0.002
    assert CFG["builder"] == "command_a_plus_engine"
    # the toy sizes cut widths and the share, never the pattern
    assert not set(CFG["reduced"][:2]) & set(CFG["rehearse"])


def test_builder_reads_the_published_keys():
    import harness

    builder = harness.load_module("builders", CFG["builder"])
    model = builder.model_args(CFG)
    experts = {"experts": 128, "held": (0, 8), "top_k": 8, "width": 4096,
               "activation": "silu", "route_from": "normed",
               "score": "sigmoid", "expert_bias": False, "norm_topk": True,
               "shared_width": 16384, "shared_scale": 0.25}
    pattern = model["layer_pattern"]
    assert [(lay["window"], lay["rope"]) for lay in pattern] \
        == [(4096, True)] * 3 + [(None, False)]
    assert all(lay["ffn"] == experts and lay["rope_interleave"]
               and lay["attn_precision"] == "highest" for lay in pattern)
    assert (model["hidden"], model["num_heads"], model["num_kv_heads"],
            model["head_dim"], model["tie_head"], model["rms_norm_eps"],
            model["vocab_size"], model["norm"], model["norm_kind"],
            model["rope_base"], model["logit_scale"]) \
        == (4096, 128, 8, 128, True, 1e-5, 32768, "parallel", "layer",
            50000.0, 1.0)


@pytest.mark.parametrize("lacks", ["the parallel block",
                                   "a second block table"])
def test_a_program_without_the_mechanism_is_refused_before_anything_is_built(
        lacks, monkeypatch):
    """The parent's program: ``_norm_modes`` knows no "parallel" and the
    chunk program takes one block table.  The builder says so and exits
    before an engine, a device or a weight."""
    import importlib

    import harness

    llama = importlib.import_module("paddle_tpu.models.llama")
    builder = harness.load_module("builders", CFG["builder"])
    if lacks == "the parallel block":
        def modes(norm):
            if norm not in ("pre", "post", "pre_post"):
                raise ValueError(norm)
            return norm != "post", norm != "pre"

        monkeypatch.setattr(llama, "_norm_modes", modes)
    else:
        def one_table(chunk_len, max_seq_len, num_pages, page_tokens,
                      *args, **arch):
            raise AssertionError("never built")

        monkeypatch.setattr(llama, "_chunk_forward", one_table)
    with pytest.raises(SystemExit, match="cannot run"):
        builder.engine(CFG, MIX)


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    print(f"\n[ragdocs-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert list(p) == [318, 548, 746, 942, 1148, 1370, 1616, 1894, 2215,
                       2596, 3062, 3655, 4452, 5623, 7651, 12288]
    assert (p.sum(), o.sum(), o.min(), o.max()) == (50124, 3543, 63, 512)
    e = MIX["engine"]
    chunk, rungs = e["prefill_chunk"], e["prefill_buckets"]
    assert (chunk, rungs) == (1024, [256, 512, 1024])
    assert all(chunk % b == 0 and b % e["page_tokens"] == 0 for b in rungs)
    spans = [min(chunk, n - lo) for n in p for lo in range(0, n, chunk)]
    padded = [min(b for b in rungs if b >= n) for n in spans]
    assert (len(spans), sum(padded)) == (56, 53248)
    past = [n for n in p if n > CFG["sliding_window"]]
    assert len(past) == 4 and 0.59 < sum(past) / p.sum() < 0.61
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["deadline_ms"]) \
        == ("serve_chunks", "closed", 2, 16, 1, 240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 2048, "sigma": 1.0, "min": 256,
         "max": 12288},
        {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 48,
         "max": 512})
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"]) \
        == (10, 12800, 16)
    assert not (e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    assert MIX["reference_prompts"] == [200, 4090, 9000]
    assert MIX["users"] and MIX["why"] and MIX["rehearse"]
    # the rehearsal's grid is the cell's: four early slots among ten
    assert MIX["rehearse"]["engine"]["num_slots"] == e["num_slots"]


def test_counts_by_hand():
    import ops_bytes_command_a_plus as ob

    h = 4096
    assert ob.window_layer_count(CFG) == 3
    assert ob.attention_params(CFG) == h * (16384 + 2048) + 16384 * h \
        == 142606336
    assert ob.expert_params(CFG) == 3 * h * 4096 == 50331648
    assert ob.router_params(CFG) == h * 128
    assert ob.held_pairs_per_token(CFG) == 0.5
    assert ob.kv_bytes_per_position(CFG, 4) == 8192
    assert ob.pair_flops(CFG) == 65536
    assert ob.paged_kernel_bytes(CFG, 10 * 6000.0, 10 * 4096.0, 4) \
        == 8192 * (60000 + 3 * 40960)
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 0.0, 4)
    assert base == 4 * (h + h * 32768 + 4 * (
        h + 142606336 + h * 128 + 4 * 50331648))
    # the ISSUE's "5.51 outside the routed experts" and "the head 0.54"
    assert 6.0e9 < base < 6.1e9
    full = ob.decode_step_bytes(CFG, 3.9, 10 * 6000.0, 10 * 4096.0, 4)
    assert full - base == pytest.approx(
        4 * 4 * 3.9 * 50331648 + 8192 * (60000 + 3 * 40960))
    assert 10.0e9 < full < 11.0e9         # "about 10.2 GB" a step
    # a chunk inside the window, across its edge and past it
    assert ob.chunk_pairs(CFG, 1024, 0) == (1024 * 1025 // 2,) * 2
    full_p, win_p = ob.chunk_pairs(CFG, 1024, 3584)
    assert full_p == 1024 * 3584 + 1024 * 1025 // 2
    assert win_p == 512 * 3584 + 512 * 513 // 2 + 512 * 4096
    assert ob.chunk_pairs(CFG, 1024, 11264) \
        == (1024 * 11264 + 1024 * 1025 // 2, 1024 * 4096)
    assert ob.chunk_pairs(CFG, 808, 8192)[1] == 808 * 4096
    n = 1024
    want = 4 * 2.0 * n * (142606336 + h * 128 + 4.5 * 50331648) \
        + 65536.0 * (full_p + 3 * win_p)
    assert ob.chunk_flops(CFG, n, 3584) == pytest.approx(want)
    # 2 x 369.6 M parameters a row a layer: the ISSUE's "0.74 GFLOP"
    assert 2.9e9 < ob.chunk_flops(CFG, 1, 0) < 3.0e9
    assert ob.chunk_attention_flops(CFG, 1000.0, 4) == 65536000.0
    # what the engine's span says is what the count says
    from paddle_tpu.serving import GenerationEngine

    class Probe:
        _window_layers, _state_layers = [0, 1, 2], []
        window, model = 4096, {"num_layers": 4}

    for tokens, at in ((1024, 0), (1024, 3584), (808, 8192), (5, 4094)):
        f, w = ob.chunk_pairs(CFG, tokens, at)
        assert GenerationEngine._chunk_pairs(Probe, at, tokens) == f + 3 * w


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "command-a-plus-05-2026", "ragdocs-pool")
    # (by count and place at its PR; a later cell comes behind it)
    assert bench["workloads"].index(cell) == 10
    assert "prefill_mean_ms.pool" in cell["why"] \
        or "chunk module" in cell["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = [c for c in bench["configs"]
               if c["name"] == "command-a-plus-05-2026"]
    assert bench["configs"].index(config) == 8
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # (as the expert siblings: ``moe_experts_touched_pct.pool`` divides by
    # ``num_experts``, here the experts HELD; ``attention_kernel_share_pct
    # .pool`` counts every Mosaic call that is no ragged-dot); the one
    # cell that prefills in chunks, and so none of whole prompts
    assert check_cell(CELL) == TABLE[CELL][2]
    assert "experts, all held" not in TABLE[CELL][1]
    assert [c for c, row in TABLE.items() if "chunked prefill" in row[1]] \
        == [CELL] and "whole-prompt prefill" not in TABLE[CELL][1]
    # the data files and the cell's own name readers and functions that
    # are there
    check_cell_loads(CELL)


def test_new_readers_find_nothing_where_there_is_nothing():
    """On a program without chunk spans (the parent's), or without a
    trace, the readers this PR brings return None and do not raise."""
    import harness

    chunks = harness.load_module("readers", "roofline_chunks")
    share = harness.load_module("readers", "module_busy_share")
    assert chunks.read({}, "ops_bytes_command_a_plus.chunk_flops",
                       "flops_per_s") is None
    assert share.read({}, "prefill") is None
    trace = {"modules": {"a": [(0.0, 0.5), (1.0, 1.5)],
                         "b": [(0.5, 0.9)]}, "busy_s": 1.3,
             "to_monotonic": 0.0}

    class Run:
        peaks = {"flops_per_s": 197e12}

    ctx = {"trace": trace, "trace_spans": [], "cfg": CFG, "run": Run}
    assert chunks.read(ctx, "ops_bytes_command_a_plus.chunk_flops",
                       "flops_per_s") is None
    assert share.read(ctx, "prefill") == pytest.approx(100 * 0.4 / 1.3)

    class Span:
        name, start = "generation/prefill_chunk", 0.4
        attrs = {"tokens": 1024, "base": 0}

    import ops_bytes_command_a_plus as ob

    ctx["trace_spans"] = [Span]
    assert chunks.read(ctx, "ops_bytes_command_a_plus.chunk_flops",
                       "flops_per_s") == pytest.approx(
        100 * ob.chunk_flops(CFG, 1024, 0) / 197e12 / 0.4)


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "5100000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert line["counts"]["prefill_spans"] == 0      # every prompt in chunks
    assert out.stdout.count("in reused slot") == 3
    assert out.stdout.count("NOT") == 0
    assert "chunk(s) of other prompts" in out.stdout
    assert "window pages let go while prompts came in" in out.stdout
    check = line["check"]
    assert check["plan_held"] and check["chunks_between"] \
        and check["exact_tokens"]
    assert sorted(check["rel"]) == ["30", "6", "90"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())
    # toy share: 4 of 16 experts held, 3 a token
    assert 10 < check["pairs_held_pct"] < 45


FAULTS = [None, "a window page released one chunk early",
          "a base off by one", "the held range is off by one",
          "the shared mean taken as a sum"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_catches_a_fault(fault, monkeypatch):
    """``serve_chunks.reference_check`` at toy widths on ten slots: the
    compared requests land in reused slots between live neighbours, other
    prompts' chunks go between their own, and they are the reference's; an
    engine that lets go of the oldest window page a chunk still admits,
    that runs a chunk one position off its base, an expert layer that
    takes the pairs of experts 5..8 for those of 4..7, or a program that
    adds the four shared experts up where it should average them, is NOT
    correct."""
    import harness
    import serve_chunks

    cell = harness.Cell(CELL, rehearse=True)
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    from paddle_tpu.serving import GenerationEngine

    real = GenerationEngine._chunk_feed
    if fault == "a window page released one chunk early":
        def early(self, ids, base, n, slot):
            feed = real(self, ids, base, n, slot)
            table = feed["block_table_window"]
            if slot is not None and base >= self.window:
                table = table.copy()
                table[0, np.flatnonzero(table[0])[0]] = 0
                feed = dict(feed, block_table_window=table)
            return feed

        monkeypatch.setattr(GenerationEngine, "_chunk_feed", early)
    elif fault == "a base off by one":
        def shifted_base(self, ids, base, n, slot):
            feed = real(self, ids, base, n, slot)
            if slot is not None and base > 0:
                feed = dict(feed, base=feed["base"] + 1)
            return feed

        monkeypatch.setattr(GenerationEngine, "_chunk_feed", shifted_base)
    elif fault == "the held range is off by one":
        from paddle_tpu.parallel import moe

        real_tokens = moe.moe_routed_tokens

        def shifted(*args, held_first=None, **kw):
            return real_tokens(*args, held_first=held_first + 1, **kw)

        monkeypatch.setattr(moe, "moe_routed_tokens", shifted)
    elif fault:
        builder = cell.builder()
        pattern = builder.layer_pattern

        def summed(cfg):
            return [dict(lay, ffn=dict(lay["ffn"], shared_scale=1.0))
                    for lay in pattern(cfg)]

        monkeypatch.setattr(builder, "layer_pattern", summed)
    ok, scope = serve_chunks.reference_check(run, cell.cfg, cell.mix,
                                             5100000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "in reused slot" in line]
    assert len(lines) == 3
    assert any("NOT correct" in line for line in lines) == bool(fault)
    held = [line for line in said if "used and left" in line
            or "chunk(s) of other prompts" in line]
    assert len(held) == 5 and not any("NOT" in line for line in held)
    assert "were held here" in said[-1]
    assert run.check["tolerance"] == cell.tolerance


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_command_a_plus.py``): the
    reference computed in bfloat16 throughout goes through the cell's own
    comparison in the program's place and comes out not correct on every
    prompt, even at the toy widths.  The reading at published widths is
    taken on the chip."""
    import harness
    from bf16_control_command_a_plus import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 5100000019)
    assert len(got) == 3 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights (8 held experts a layer, not 128), both page pools and the
    temporaries of the decode program at the mix's 10 slots x 12,800 and of
    its widest chunk rung fit one chip under the issue's 15.6 GB.  The
    chunk program holds the Mosaic kernel ``chunk_attention`` once a layer,
    writes its K/V page by page, and keeps no pool-sized copy, no ``[H, C,
    S]`` scores and no K / V repeated to the 128 query heads among its
    temporaries."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import (build_llama_decode,
                                         build_llama_prefill_chunk)
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.decode_ops import pool_shape

    import harness
    from test_compile_only import _compile, _report

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    e = MIX["engine"]
    slots, pt_, chunk = e["num_slots"], e["page_tokens"], e["prefill_chunk"]
    np_slot = e["max_seq_len"] // pt_
    pages = slots * np_slot + 1
    wpages = slots * (CFG["sliding_window"] // pt_ + 1) + 1 + chunk // pt_
    assert (pages, wpages) == (8001, 2635)
    one = list(topo.devices)[:1]
    keys = ("attention_lowered_chunk_pallas",
            "attention_lowered_chunk_reference",
            "attention_lowered_paged_decode",
            "attention_lowered_paged_decode_reference",
            "kv_pool_write_pages", "kv_pool_write_rows")
    before = {k: stat_get(k) for k in keys}

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, num_window_pages=wpages,
            **model)
    assert len(caches) == 8
    block = main.global_block()
    assert tuple(block.var("llama.pool_k_0").shape) == (wpages, 8, pt_, 128)
    assert tuple(block.var("llama.pool_k_3").shape) == (pages, 8, pt_, 128)
    assert tuple(block.var("llama.blk0.moe.gate_up.w").shape) \
        == (8, 4096, 8192)
    assert tuple(block.var("llama.blk0.moe.router.w").shape) == (4096, 128)
    assert tuple(block.var("llama.blk0.moe.shared_gate_up.w").shape) \
        == (4096, 32768)
    assert tuple(block.var("llama.blk0.qkv.w").shape) == (4096, 18432)
    assert not [n for n in block.vars if n.endswith(("ln2", "head.w"))]
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32"),
              "block_tables_window": ((slots, np_slot), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name,
                                      fetches["expert_counts"].name], one,
                        [shapes[n] for n in feeds])
    decode = _report(f"Command A+ decode program: {slots} slots x "
                     f"{e['max_seq_len']}, {pages} + 3 x {wpages} pages",
                     compiled)
    assert decode < 15.6e9
    assert compiled.as_text().count("paged_decode_attention") >= 4

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_prefill_chunk(
            chunk, e["max_seq_len"], pages, pt_, name="llama",
            num_window_pages=wpages, page_aligned=True, **model)
    assert feeds == ["chunk_ids", "base", "block_table", "chunk_len",
                     "block_table_window", "last_off"]
    shapes = {"chunk_ids": ((1, chunk), "int64"), "base": ((1,), "int32"),
              "block_table": ((1, np_slot), "int32"),
              "chunk_len": ((1,), "int32"),
              "block_table_window": ((1, np_slot), "int32"),
              "last_off": ((1,), "int64")}
    compiled = _compile(main, feeds, [fetches["next_token"].name,
                                      fetches["expert_counts"].name], one,
                        [shapes[n] for n in feeds])
    rung = _report(f"Command A+ chunk program: rung {chunk}", compiled)
    assert rung < 15.6e9                 # the issue's line
    text = compiled.as_text()
    assert text.count("chunk_attention") >= 4
    # no temporary as large as the scores or as K / V repeated to the query
    # heads: ALL the temporaries together are under either, and under the
    # full kind's pool; and no copy of anything with a pool's element
    # count, of either kind (the compiler re-lays a pool under a bitcast
    # shape too)
    temp = compiled.memory_analysis().temp_size_in_bytes
    pools = {math.prod(pool_shape(n, 8, pt_, 128)) for n in (pages, wpages)}
    assert temp < max(pools) * 4, (temp, pools)
    assert temp < 128 * e["max_seq_len"] * 128 * 4    # K to 128 heads
    assert temp < 128 * chunk * e["max_seq_len"] * 4  # [H, C, S]
    copied = re.findall(r"= f32\[([\d,]+)\]\{[^}]*\} copy\(", text)
    big = [d for d in copied if math.prod(map(int, d.split(","))) in pools]
    assert not big, big
    after = {k: stat_get(k) - v for k, v in before.items()}
    assert after == {
        "attention_lowered_chunk_pallas": 4,
        "attention_lowered_chunk_reference": 0,
        "attention_lowered_paged_decode": 4,
        "attention_lowered_paged_decode_reference": 0,
        "kv_pool_write_pages": 8, "kv_pool_write_rows": 0}

"""The ``deepseek-v2-docqa`` cell without a chip: its files and the
arithmetic of the cut, a ``--rehearse`` run, a program that lacks grouped
selection or a chunk over latent pages (the builder refuses before
anything is built), planted faults (a stale latent page behind a chunk, a
held group off by one, routing over all experts without the group step)
and the check's bfloat16 control at toy widths (all NOT correct), and
compile-only sizing of its decode program at 10 slots x 12,800 and of its
1024 chunk rung for a described TPU v5e, which holds no temporary that
grows with context x heads (the topology is described inside a fixture; a
compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_deepseek_v2.py -s
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
CELL = "deepseek-v2-docqa"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import GROUPS, check_cell, check_cell_loads  # noqa: E402

# the cell's row, as ``test_manifest.TABLE`` has the others': its groups
# (of "latent pages" all but the single-shot prefill kernel's entry, which
# this cell never runs) and the values a traced run reports
ROW = ("served_tokens_per_s", ["closed loop", "experts",
                               "experts, a share held", "step on its span",
                               "latent pages", "chunked prefill"], 40)
NOT_RUN = "mla_prefill_roofline.pool"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "deepseek-v2.json")
MIX = _json("traffic", "docqa-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the three cut."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        row, = [r for r in map(json.loads, open(catalog))
                if r["name"] == "DeepSeek-V2"]
        assert CFG["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (CFG["published"] if k in CFG["reduced"]
                    else CFG)[k] == v, k
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert [CFG[k] for k in CFG["reduced"]] == [5, 20, 12800]
    assert CFG["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    # every width as published
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"], CFG["num_attention_heads"],
            CFG["q_lora_rank"], CFG["kv_lora_rank"],
            CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"],
            CFG["v_head_dim"], CFG["num_experts_per_tok"],
            CFG["n_shared_experts"], CFG["n_group"], CFG["topk_group"],
            CFG["routed_scaling_factor"]) \
        == (5120, 12288, 1536, 128, 1536, 512, 128, 64, 128, 6, 2, 8, 3, 16)
    share = CFG["expert_share"]
    assert (share["router_experts"], share["first"]) == (160, 0)
    # one WHOLE group held
    assert CFG["n_routed_experts"] == share["router_experts"] // CFG["n_group"]
    run = CFG["as_run"]
    assert (run["dtype"], run["attention_precision"],
            run["latent_row"]["lanes"], run["latent_row"]["bytes"]) \
        == ("float32", "highest", 640, 2560)
    a = CFG["assumed"]
    assert a["eos_id"] == -1 and len(a["why"]) >= 10
    assert a["weights_seed"] == 5600000003
    assert "8 chips" in CFG["deployment"]
    tol = CFG["check_tolerance"]
    assert 0 < tol["share_of_range"] <= 2.0 ** -7
    assert tol["near_tie_margin_share_of_router_range"] == 0.002
    assert CFG["builder"] == "deepseek_v2_engine"


def test_the_arithmetic_of_the_cut():
    """3,145 M parameters = 12.58 GB of float32 weights, and 1.64 GB of
    latent pool, as the configuration's ``deployment`` says."""
    import ops_bytes_deepseek_v2 as ob

    h = 5120
    mla = ob.mla_mixer_params(CFG)
    assert mla == h * 1536 + 1536 + 1536 * 128 * 192 + h * 576 + 512 \
        + 512 * 128 * 256 + 128 * 128 * h
    assert round(mla / 1e6, 1) == 149.2
    assert round(ob.dense_params(CFG) / 1e6, 1) == 188.7
    assert round(ob.expert_params(CFG) / 1e6, 2) == 23.59
    assert ob.router_params(CFG) == h * 160
    layer0 = mla + 2 * h + ob.dense_params(CFG)
    expert_layer = mla + 2 * h + ob.router_params(CFG) \
        + (20 + 2) * ob.expert_params(CFG)
    total = layer0 + 4 * expert_layer + 2 * h * 12800 + h
    assert (round(layer0 / 1e6, 1), round(expert_layer / 1e6, 1)) \
        == (338.0, 669.1)
    assert round(total / 1e6) == 3145 and round(total * 4 / 1e9, 2) == 12.58
    # 160 experts a layer whole: no chip holds one
    assert 160 * ob.expert_params(CFG) * 4 > 15.0e9
    e = MIX["engine"]
    pages = e["num_slots"] * e["max_seq_len"] // e["page_tokens"] + 1
    pool = 5 * pages * e["page_tokens"] * ob.latent_row_bytes(CFG, 4)
    assert pages == 8001 and round(pool / 1e9, 2) == 1.64
    # 128 heads of K and V would be 164 KB a token a layer
    assert 128 * (192 + 128) * 4 == 163840


def test_builder_reads_the_published_keys():
    import harness

    builder = harness.load_module("builders", CFG["builder"])
    model = builder.model_args(CFG)
    experts = {"experts": 160, "held": (0, 20), "top_k": 6, "width": 1536,
               "activation": "silu", "route_from": "normed",
               "score": "softmax", "norm_topk": False, "route_scale": 16.0,
               "n_group": 8, "topk_group": 3, "shared_width": 3072}
    pattern = model["layer_pattern"]
    assert [lay["ffn"] for lay in pattern] == ["dense"] + [experts] * 4
    mla = pattern[0]["mla"]
    assert all(lay["mla"] == mla and lay["window"] is None
               for lay in pattern)
    assert (mla["q_rank"], mla["kv_rank"], mla["nope_dim"], mla["rope_dim"],
            mla["v_dim"], mla["interleave"]) == (1536, 512, 128, 64, 128,
                                                 True)
    assert mla["yarn"] == {"factor": 40, "original_max": 4096,
                           "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                           "mscale_all_dim": 0.707} and "scale" not in mla
    assert (model["hidden"], model["num_heads"], model["intermediate"],
            model["tie_head"], model["rms_norm_eps"], model["vocab_size"],
            model["rope_base"], model["num_layers"]) \
        == (5120, 128, 12288, False, 1e-6, 12800, 10000.0, 5)


@pytest.mark.parametrize("lacks", ["grouped selection",
                                   "a chunk over latent pages"])
def test_a_program_without_the_mechanism_is_refused_before_anything_is_built(
        lacks, monkeypatch):
    """The parent's program: ``route_top_k`` knows no groups and
    ``_mla_mixer`` takes one row a slot.  The builder says so and exits
    before an engine, a device or a weight."""
    import importlib

    import harness

    builder = harness.load_module("builders", CFG["builder"])
    if lacks == "grouped selection":
        moe = importlib.import_module("paddle_tpu.parallel.moe")

        def plain(router_x, router_w, top_k, score="softmax"):
            raise AssertionError("never called")

        monkeypatch.setattr(moe, "route_top_k", plain)
    else:
        llama = importlib.import_module("paddle_tpu.models.llama")

        def one_row(h, seq_len, hidden, num_heads, layer, p, eps, rope_base,
                    attn_impl, kv_cache=None, want_row=False):
            raise AssertionError("never built")

        monkeypatch.setattr(llama, "_mla_mixer", one_row)
    with pytest.raises(SystemExit, match="cannot run deepseek-v2"):
        builder.engine(CFG, MIX)


def test_the_weights_are_held_to_the_configurations_seed():
    """Whatever ``--seed`` drew, an engine the builder makes on a scope
    holds ``assumed.weights_seed``'s matrices (the group this chip holds is
    then as well liked in every run); vectors keep their constants; the
    draw is made once a scope."""
    import harness
    import serve_blocks

    cell = harness.Cell(CELL, rehearse=True)
    builder = cell.builder()
    drawn = []
    for seed in (5600000041, 5600000043):
        scope = serve_blocks.seeded_scope(builder, cell.cfg, cell.mix, seed)
        before = np.asarray(scope.find_var("llama.blk1.moe.router.w"))
        builder.engine(cell.cfg, cell.mix, scope=scope).close()
        after = np.asarray(scope.find_var("llama.blk1.moe.router.w"))
        assert np.abs(after - before).max() > 0.01
        assert scope._weights_seed_drawn == cell.cfg["assumed"]["weights_seed"]
        builder.engine(cell.cfg, cell.mix, scope=scope).close()
        assert np.array_equal(
            after, np.asarray(scope.find_var("llama.blk1.moe.router.w")))
        assert np.all(np.asarray(scope.find_var("llama.blk1.ln1")) == 1)
        drawn.append((before, after,
                      np.asarray(scope.find_var("llama.embed"))))
    assert np.abs(drawn[0][0] - drawn[1][0]).max() > 0.01
    assert np.allclose(drawn[0][1], drawn[1][1], rtol=0.05, atol=1e-3)
    assert np.allclose(drawn[0][2], drawn[1][2], rtol=0.05, atol=1e-3)


def test_mix_is_the_issues():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    print(f"\n[docqa-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert list(p) == [575, 938, 1238, 1527, 1824, 2139, 2481, 2863, 3297,
                       3803, 4412, 5173, 6179, 7624, 10060, 12288]
    assert (p.sum(), o.sum(), o.min(), o.max()) == (66421, 3543, 63, 512)
    e = MIX["engine"]
    chunk, rungs = e["prefill_chunk"], e["prefill_buckets"]
    assert (chunk, rungs) == (1024, [256, 512, 1024])
    assert all(chunk % b == 0 and b % e["page_tokens"] == 0 for b in rungs)
    spans = [min(chunk, n - lo) for n in p for lo in range(0, n, chunk)]
    padded = [min(b for b in rungs if b >= n) for n in spans]
    assert (len(spans), sum(padded)) == (73, 68864)
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["deadline_ms"]) \
        == ("serve_chunks", "closed", 2, 16, 1, 240000)
    assert (MIX["prompt_len"], MIX["output_len"]) == (
        {"dist": "lognormal", "median": 3072, "sigma": 0.9, "min": 512,
         "max": 12288},
        {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 48,
         "max": 512})
    # ragdocs-pool's answers: the two chunked cells differ in their pages
    assert MIX["output_len"] == _json("traffic",
                                      "ragdocs-pool.json")["output_len"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"]) \
        == (10, 12800, 16)
    assert not (e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    assert MIX["reference_prompts"] == [300, 2500, 9000]
    assert MIX["users"] and MIX["why"] and MIX["rehearse"]
    assert MIX["per_layer_args"]["latent_fill_pct.pool"]["scale"] \
        == 100 / (10 * 12800)
    assert MIX["rehearse"]["engine"]["num_slots"] == e["num_slots"]


def test_counts_by_hand():
    import ops_bytes_deepseek_v2 as ob

    assert ob.held_pairs_per_token(CFG) == 0.75
    assert ob.latent_row_bytes(CFG, 4) == 2560
    assert ob.pair_flops(CFG) == 81920
    assert ob.mla_decode_bytes(CFG, 1000.0, 4) == 5 * 2560 * 1000.0
    assert ob.mla_decode_flops(CFG, 1.0) == 5 * 128 * 2176.0
    base = ob.decode_step_bytes(CFG, 0.0, 0.0, 4)
    h, expert = 5120, ob.expert_params(CFG)
    assert base == 4 * (h + h * 12800 + 5 * (2 * h + ob.mla_mixer_params(CFG))
                        + ob.dense_params(CFG)
                        + 4 * (h * 160 + 2 * expert))
    full = ob.decode_step_bytes(CFG, 2.5, 60000.0, 4)
    assert full - base == pytest.approx(4 * 4 * 2.5 * expert
                                        + 5 * 2560 * 60000.0)
    assert ob.chunk_pairs(1024, 0) == 1024 * 1025 // 2
    assert ob.chunk_pairs(808, 8192) == 808 * 8192 + 808 * 809 // 2
    matmul = 5 * (ob.mla_mixer_params(CFG) - 1536 - 512) \
        + ob.dense_params(CFG) + 4 * (h * 160 + 2.75 * expert)
    assert ob.chunk_flops(CFG, 1024, 3072) == pytest.approx(
        2.0 * 1024 * matmul + 81920.0 * 5 * ob.chunk_pairs(1024, 3072))
    # 2 x 1.2 G parameters a row: 2.4 GFLOP before attention
    assert 2.3e9 < ob.chunk_flops(CFG, 1, 0) < 2.5e9
    assert ob.chunk_attention_flops(CFG, 1000.0, 4) == 81920000.0
    # what the engine's span says is what the count says
    from paddle_tpu.serving import GenerationEngine

    class Probe:
        _window_layers, _state_layers = [], []
        window, model = None, {"num_layers": 5}

    for tokens, at in ((1024, 0), (1024, 3072), (808, 8192)):
        assert GenerationEngine._chunk_pairs(Probe, at, tokens) \
            == 5 * ob.chunk_pairs(tokens, at)


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "deepseek-v2", "docqa-pool")
    # (by count and place at its PR; a later cell comes behind it)
    assert bench["workloads"].index(cell) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = [c for c in bench["configs"] if c["name"] == "deepseek-v2"]
    assert bench["configs"].index(config) == 9
    assert config["source"] == CFG["source"] \
        and config["reduced"] == CFG["reduced"]
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    assert len(bench["per_layer"]) == 97          # the cell added no entry
    # the cell joins its groups' entries, but the single-shot latent
    # prefill kernel's, which its timed path never runs
    groups = dict(GROUPS, **{"latent pages": [
        n for n in GROUPS["latent pages"] if n != NOT_RUN]})
    import test_manifest

    saved = test_manifest.GROUPS
    test_manifest.GROUPS = groups
    try:
        assert check_cell(CELL, ROW) == ROW[2]
    finally:
        test_manifest.GROUPS = saved
    # the data files and the cell's own name readers and functions that
    # are there
    check_cell_loads(CELL)


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "5600000019", "--seconds", "2"],
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
    check = line["check"]
    assert check["plan_held"] and check["chunks_between"] \
        and check["exact_tokens"]
    assert sorted(check["rel"]) == ["30", "6", "90"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())


FAULTS = [None, "a stale latent page behind a chunk",
          "the held group is off by one",
          "routing over all experts without the group step"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_catches_a_fault(fault, monkeypatch):
    """``serve_chunks.reference_check`` at toy widths on ten slots: the
    compared requests land in reused slots between live neighbours, other
    prompts' chunks go between their own, and they are the reference's; an
    engine whose chunk reads the trash page where the slot's first page
    should be, an expert layer that takes the pairs of the NEXT group for
    those of the held one, or a router that picks the largest of all its
    experts with no group step, is NOT correct."""
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
    if fault == "a stale latent page behind a chunk":
        def stale(self, ids, base, n, slot):
            feed = real(self, ids, base, n, slot)
            if slot is not None and base > 0:
                table = feed["block_table"].copy()
                table[0, 0] = 0          # the trash page's rows
                feed = dict(feed, block_table=table)
            return feed

        monkeypatch.setattr(GenerationEngine, "_chunk_feed", stale)
    elif fault == "the held group is off by one":
        from paddle_tpu.parallel import moe

        real_tokens = moe.moe_routed_tokens
        per = cell.cfg["n_routed_experts"]

        def shifted(*args, held_first=None, **kw):
            return real_tokens(*args, held_first=held_first + per, **kw)

        monkeypatch.setattr(moe, "moe_routed_tokens", shifted)
    elif fault:
        builder = cell.builder()
        pattern = builder.layer_pattern

        def ungrouped(cfg):
            return [lay if lay["ffn"] == "dense" else dict(lay, ffn=dict(
                lay["ffn"], n_group=1, topk_group=1))
                for lay in pattern(cfg)]

        monkeypatch.setattr(builder, "layer_pattern", ungrouped)
    ok, scope = serve_chunks.reference_check(run, cell.cfg, cell.mix,
                                             5600000033)
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
    """The check's control (``bf16_control_deepseek_v2.py``): the
    reference computed in bfloat16 throughout goes through the cell's own
    comparison in the program's place and comes out not correct on every
    prompt, even at the toy widths.  The reading at published widths is
    taken on the chip."""
    import harness
    from bf16_control_deepseek_v2 import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 5600000019)
    assert len(got) == 3 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights (one group of 20 experts a layer, not 160), the five latent
    pools and the temporaries of the decode program at the mix's 10 slots
    x 12,800 and of its 1024 chunk rung fit one chip under the issue's
    15.6 GB.  The chunk program holds the Mosaic kernel
    ``mla_chunk_attention`` once a layer, writes its rows page by page, and
    no temporary grows with context x heads: ALL its temporaries together
    are under one layer's expanded keys and values of 12,288 rows."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import (build_llama_decode,
                                         build_llama_prefill_chunk)
    from paddle_tpu.monitor import stat_get

    import harness
    from test_compile_only import _compile, _report

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    e = MIX["engine"]
    slots, pt_, chunk = e["num_slots"], e["page_tokens"], e["prefill_chunk"]
    np_slot = e["max_seq_len"] // pt_
    pages = slots * np_slot + 1
    one = list(topo.devices)[:1]
    keys = ("attention_lowered_latent_chunk",
            "attention_lowered_latent_chunk_reference",
            "attention_lowered_latent_decode",
            "attention_lowered_latent_decode_reference",
            "kv_pool_write_pages", "kv_pool_write_rows")
    before = {k: stat_get(k) for k in keys}

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert caches == [f"llama.pool_c_{i}" for i in range(5)]
    block = main.global_block()
    assert tuple(block.var("llama.pool_c_0").shape) == (pages, 1, pt_, 640)
    assert tuple(block.var("llama.blk1.moe.gate_up.w").shape) \
        == (20, 5120, 3072)
    assert tuple(block.var("llama.blk1.moe.router.w").shape) == (5120, 160)
    assert tuple(block.var("llama.blk1.moe.shared_gate_up.w").shape) \
        == (5120, 6144)
    assert tuple(block.var("llama.blk0.gate_up.w").shape) == (5120, 24576)
    assert tuple(block.var("llama.blk0.kv_b.w").shape) == (512, 128 * 256)
    assert tuple(block.var("llama.head.w").shape) == (5120, 12800)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [
        fetches[n].name for n in ("next_token", "expert_counts",
                                  "expert_group_rows")], one,
        [shapes[n] for n in feeds])
    decode = _report(f"DeepSeek-V2 decode program: {slots} slots x "
                     f"{e['max_seq_len']}, 5 x {pages} latent pages",
                     compiled)
    assert decode < 15.6e9
    assert compiled.as_text().count("mla_decode_attention") >= 5

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_prefill_chunk(
            chunk, e["max_seq_len"], pages, pt_, name="llama",
            page_aligned=True, **model)
    assert feeds == ["chunk_ids", "base", "block_table", "chunk_len",
                     "last_off"]
    shapes = {"chunk_ids": ((1, chunk), "int64"), "base": ((1,), "int32"),
              "block_table": ((1, np_slot), "int32"),
              "chunk_len": ((1,), "int32"), "last_off": ((1,), "int64")}
    compiled = _compile(main, feeds, [
        fetches[n].name for n in ("next_token", "expert_counts",
                                  "expert_group_rows")], one,
        [shapes[n] for n in feeds])
    rung = _report(f"DeepSeek-V2 chunk program: rung {chunk}", compiled)
    assert rung < 15.6e9                 # the issue's line
    assert compiled.as_text().count("mla_chunk_attention") >= 5
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 128 * 12288 * (192 + 128) * 4, temp
    after = {k: stat_get(k) - v for k, v in before.items()}
    assert after == {
        "attention_lowered_latent_chunk": 5,
        "attention_lowered_latent_chunk_reference": 0,
        "attention_lowered_latent_decode": 5,
        "attention_lowered_latent_decode_reference": 0,
        "kv_pool_write_pages": 5, "kv_pool_write_rows": 0}

"""The ``longcat-flash-agentchat`` cell without a chip: its files and the
cut's arithmetic (``reduced`` against ``published``, vocabulary x 8, heads
x 8, experts x 64), the counts of ``ops_bytes_longcat_flash`` by hand
(identity picks no bytes and no FLOPs, two latent sublayers and two dense
SwiGLUs a layer, the router at 768), a ``--rehearse`` run, the check's plan
at 32 slots (compared prompts land in reused slots, the 700-token prompt in
two chunks), the check's bfloat16 control at toy widths (NOT correct), and
compile-only sizing of its decode program at 32 slots x 1280 and of its
512 chunk rung for a described TPU v5e (the topology is described inside a
fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_longcat_flash.py -s
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
CELL = "longcat-flash-agentchat"

import test_manifest as manifest  # noqa: E402
from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import check_cell, check_cell_loads  # noqa: E402

# the entries the cells since PR 59 brought are groups of their own, and
# the cell's row is here until a ``benchmark`` PR puts them in
# ``test_manifest`` (PERF.md section 7)
manifest.GROUPS["state space"] = [
    "ssm_step_roofline.pool", "ssm_chunk_roofline.pool",
    "ssm_kernel_share_pct.pool"]
manifest.GROUPS["experts in a latent row"] = [
    "expert_kernel_roofline.pool", "moe_rows_per_held_expert.pool"]
NEW = ["moe_zero_pairs_pct.pool", "shortcut_branch_share_pct.pool"]
manifest.GROUPS["identity experts"] = NEW
ROW = ("served_tokens_per_s", [
    "closed loop", "experts", "experts, a share held", "step on its span",
    "latent pages", "chunked prefill", "identity experts"],
    22 + 2 + 3 + 1 + 4 + 5 + 2 + 4)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "longcat-flash-chat.json")
MIX = _json("traffic", "agentchat-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the four cut."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide on this machine")
    with open(catalog) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "LongCat-Flash-Chat"]
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == sorted(CFG["published"]) \
        == sorted(["num_layers", "num_attention_heads", "n_routed_experts",
                   "vocab_size"])
    assert [CFG[k] for k in CFG["reduced"]] == [4, 8, 8, 16384]


def test_the_cuts_arithmetic_is_its_files():
    """ISSUE 66's sizing, from the file's own keys: no width cut, the
    deployment's 64 chips, 13.69 GB of float32 weights."""
    import ops_bytes_longcat_flash as ob

    pub = CFG["published"]
    assert CFG["vocab_size"] * 8 == pub["vocab_size"]
    assert CFG["num_attention_heads"] * 8 == pub["num_attention_heads"]
    assert CFG["n_routed_experts"] * 64 == pub["n_routed_experts"]
    share = CFG["expert_share"]
    assert share["router_experts"] == 768 \
        == pub["n_routed_experts"] + CFG["zero_expert_num"]
    assert share["zero_experts"] == CFG["zero_expert_num"] == 256
    assert share["first"] + CFG["n_routed_experts"] <= 512
    assert CFG["num_experts_per_tok"] == CFG["moe_topk"] == 12
    mla, dense = ob.mla_mixer_params(CFG), ob.dense_params(CFG)
    assert round(mla / 1e6, 2) == 22.68           # 8 of 64 heads
    assert round(ob.mla_mixer_params(dict(CFG, num_attention_heads=64))
                 / 1e6, 2) == 90.57               # whole heads
    assert round(2 * dense / 1e6, 1) == 453.0
    assert round(ob.expert_params(CFG) / 1e6, 2) == 37.75
    assert round(ob.router_params(CFG) / 1e6, 2) == 4.72
    layer = ob.layer_params_outside_experts(CFG) + 8 * ob.expert_params(CFG)
    assert round(layer / 1e6, 1) == 805.1
    assert round(ob.weight_params(CFG) / 1e6, 1) == 3421.6
    assert round(4 * ob.weight_params(CFG) / 1e9, 2) == 13.69
    # whole heads at the guide's other floors: no chip holds it in float32
    whole = 4 * (layer + 2 * (90.57e6 - mla)) + 201.3e6
    assert 4 * whole > 15.8e9
    e = MIX["engine"]
    pages = e["num_slots"] * e["max_seq_len"] // e["page_tokens"] + 1
    pools = 2 * CFG["num_layers"] * pages * e["page_tokens"] \
        * ob.latent_row_bytes(CFG, 4)
    assert round(pools / 1e9, 2) == 0.84
    assert ob.latent_row_bytes(CFG, 4) == CFG["as_run"]["latent_row"]["bytes"]
    # rows a held expert a decode step: half a row here, 32 in the deployment
    assert e["num_slots"] * ob.held_pairs_per_token(CFG) / 8 == 0.5
    assert e["max_seq_len"] == MIX["prompt_len"]["max"] \
        + MIX["output_len"]["max"]
    assert MIX["per_layer_args"]["latent_fill_pct.pool"]["scale"] \
        == 100 / (e["num_slots"] * e["max_seq_len"])
    assert CFG["per_layer_args"]["moe_rows_per_held_expert.pool"]["scale"] \
        == 1 / CFG["num_layers"]


def test_counts_by_hand():
    import ops_bytes_longcat_flash as ob

    h, v = 6144, 16384
    mla = h * 1536 + 1536 + 1536 * 8 * 192 + h * 576 + 512 \
        + 512 * 8 * 256 + 8 * 128 * h
    outside = 2 * (mla + 3 * h * 12288 + 2 * h) + h * 768 + 768
    expert = 3 * h * 2048
    # a step that touched 3.5 held experts a layer over 20,000 live rows
    want = 4 * (h + h * v + 4 * (outside + 3.5 * expert)) \
        + 2560 * 8 * 20000
    assert ob.decode_step_bytes(CFG, 3.5, 20000, 4) == want
    # an identity pick reads nothing: no touched expert, the same bytes
    # whatever share of the picks were identity
    assert ob.decode_step_bytes(CFG, 0, 0, 4) \
        == 4 * (h + h * v + 4 * outside)
    assert round(ob.decode_step_bytes(CFG, 3.5, 20000, 4) / 1e9, 1) == 11.0
    assert ob.mla_decode_bytes(CFG, 20000, 4) == 2560 * 8 * 20000
    assert ob.mla_decode_flops(CFG, 20000) == 2.0 * 8 * (576 + 512) * 8 \
        * 20000
    assert ob.pair_flops(CFG) == 5120
    assert ob.chunk_pairs(512, 0) == 512 * 513 // 2
    assert ob.chunk_pairs(188, 512) == 188 * 512 + 188 * 189 // 2
    matrices = mla - 1536 - 512
    per_row = 4 * (2 * (matrices + 3 * h * 12288) + h * 768
                   + 0.125 * expert)
    assert ob.chunk_flops(CFG, 300, 0) \
        == 2.0 * 300 * per_row + 5120.0 * 8 * (300 * 301 // 2)
    assert ob.chunk_attention_flops(CFG, 1000.0, 4) == 5120000.0
    assert ob.held_pairs_per_token(CFG) == 0.125


def test_mix_is_the_issues():
    e = MIX["engine"]
    assert (MIX["driver"], MIX["loop"], MIX["workers_per_slot"],
            MIX["block"], MIX["warm_blocks"], MIX["trace_s"]) \
        == ("serve_chunks", "closed", 2, 16, 4, 6)
    assert MIX["blocks"] >= 32 and "rate_rps" not in MIX
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"]) \
        == (32, 1280, 16)
    assert (e["prefill_chunk"], e["prefill_buckets"], e["prefix_reuse"],
            e["speculate"]) == (512, [256, 512], False, False)
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.8, "min": 32, "max": 768}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.5, "min": 128, "max": 512}
    assert MIX["reference_prompts"] == [60, 300, 700]
    import traffic

    prompts = traffic.lengths(MIX["prompt_len"], 16)
    answers = traffic.lengths(MIX["output_len"], 16)
    assert (int(prompts.sum()), int(answers.sum())) == (4011, 4479)
    chunks = [min(512, n - lo) for n in prompts for lo in range(0, n, 512)]
    assert len(chunks) == 18
    assert sum(256 if c <= 256 else 512 for c in chunks) == 6144


def test_cell_is_declared_with_its_metrics():
    groups = dict(manifest.GROUPS)
    # (its timed path never builds the single-shot latent prefill kernel;
    # of the latent expert cell's group it reports the rows a held expert
    # multiplies, which reads the same over any held share)
    groups["latent pages"] = [n for n in groups["latent pages"]
                              if n != "mla_prefill_roofline.pool"]
    groups["experts, a share held"] = groups["experts, a share held"] \
        + ["moe_rows_per_held_expert.pool"]
    manifest.GROUPS, kept = groups, manifest.GROUPS
    try:
        assert check_cell(CELL, ROW) == ROW[2] == 43
    finally:
        manifest.GROUPS = kept
    check_cell_loads(CELL)
    bench = _json("..", "BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="longcat-flash-chat",
                        traffic="agentchat-pool", chips=1)
    assert "1/64" in cell["why"] and "1/8 heads" in cell["why"]
    assert len(bench["per_layer"]) == 104 <= 128
    assert [m["name"] for m in bench["per_layer"][-2:]] == NEW
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for name in NEW:
        spec = _json("metrics", name + ".json")
        assert len(spec["why"]) > 100


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "6600000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert out.stdout.count("in reused slot") == 3
    assert out.stdout.count("NOT") == 0
    check = line["check"]
    assert check["plan_held"] and check["chunks_between"] \
        and check["exact_tokens"]
    assert sorted(check["rel"], key=int) == ["6", "14", "40"]
    assert all(0 <= r <= check["tolerance"] for r in check["rel"].values())
    assert all(t <= n for t, n in zip(check["taken"].values(),
                                      check["near_ties"].values()))


def test_the_check_lands_compared_prompts_in_reused_slots():
    """``serve_chunks.check_plan`` at the mix's own size: 32 fillers take
    the 32 slots, seven of them (never two side by side, never the edge)
    finish first; the three reference prompts, the longest first, and
    joiners of a chunk and a half follow; every chunk takes one of the two
    rungs."""
    import serve_chunks

    slots = MIX["engine"]["num_slots"]
    plan = serve_chunks.check_plan(CFG, MIX, 4294967311)
    kinds = [k for _, _, k in plan]
    assert kinds[:slots].count("early") == 7
    assert kinds[slots:] == [2, 1, 0] + ["joiner"] * 5
    early = [i for i, k in enumerate(kinds[:slots]) if k == "early"]
    assert early[0] >= 1 and early[-1] <= slots - 2
    assert all(b - a > 1 for a, b in zip(early, early[1:]))
    assert [len(plan[slots + i][0]) for i in range(3)] == [700, 300, 60]
    assert [len(p) for p, _, k in plan[slots + 3:slots + 6]] == [768] * 3
    assert serve_chunks.chunk_rungs(MIX, [len(p) for p, _, _ in plan]) \
        == [256, 512]
    assert serve_chunks.n_chunks(700, 512) == 2
    assert max(len(p) + n for p, n, _ in plan) \
        <= MIX["engine"]["max_seq_len"]


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_longcat_flash.py``): the
    reference computed in bfloat16 throughout goes through the cell's own
    comparison (``serve_state.check_request``) in the program's place and
    comes out not correct on every prompt, even at the toy widths.  The
    reading at published widths is taken on the chip (PERF.md section
    6)."""
    import harness
    from bf16_control_longcat_flash import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 6600000019)
    assert len(got) == 3 and not any(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_the_parents_program_is_refused_by_name(monkeypatch):
    """``require_program`` names what each of the three modules lacks."""
    import importlib

    import harness

    builder = harness.load_module("builders", CFG["builder"])
    llama = importlib.import_module("paddle_tpu.models.llama")
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    builder.require_program()
    monkeypatch.setattr(llama, "DEFAULT_LAYER",
                        {k: v for k, v in llama.DEFAULT_LAYER.items()
                         if k not in ("branch", "join")})
    monkeypatch.delattr(moe, "_softmax_biased")
    with pytest.raises(SystemExit) as e:
        builder.require_program()
    assert "route_top_k" in str(e.value) and "'branch'" in str(e.value)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights (8 of 512 experts a layer, 8 of 64 heads), the eight latent
    pools and the temporaries of the decode program at the mix's 32 slots x
    1280 and of its 512 chunk rung fit one chip under the issue's 15.3 GB.
    Both programs hold a latent kernel once a SUBLAYER and the grouped
    kernel of the held experts, whose [6144, 128] blocks stay inside the
    scoped VMEM."""
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
            "grouped_matmul_lowered_ragged_dot")
    before = {k: stat_get(k) for k in keys}

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, **model)
    assert caches == [f"llama.pool_c_{i}" for i in range(8)]
    block = main.global_block()
    assert tuple(block.var("llama.pool_c_0").shape) == (pages, 1, pt_, 640)
    assert tuple(block.var("llama.blk0.moe.gate_up.w").shape) \
        == (8, 6144, 4096)
    assert tuple(block.var("llama.blk0.moe.router.w").shape) == (6144, 768)
    assert tuple(block.var("llama.blk0.moe.expert_bias").shape) == (768,)
    assert tuple(block.var("llama.blk1.gate_up.w").shape) == (6144, 24576)
    assert tuple(block.var("llama.blk1.kv_b.w").shape) == (512, 8 * 256)
    assert tuple(block.var("llama.head.w").shape) == (6144, 16384)
    assert not block.has_var("llama.blk1.moe.router.w")
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [
        fetches[n].name for n in ("next_token", "expert_counts")], one,
        [shapes[n] for n in feeds])
    decode = _report(f"LongCat-Flash decode program: {slots} slots x "
                     f"{e['max_seq_len']}, 8 x {pages} latent pages",
                     compiled)
    assert decode < 15.3e9
    text = compiled.as_text()
    assert text.count("mla_decode_attention") >= 8
    assert "grouped_matmul_ragged-dot" in text and "shortcut_branch" in text

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_prefill_chunk(
            chunk, e["max_seq_len"], pages, pt_, name="llama",
            page_aligned=True, **model)
    shapes = {"chunk_ids": ((1, chunk), "int64"), "base": ((1,), "int32"),
              "block_table": ((1, np_slot), "int32"),
              "chunk_len": ((1,), "int32"), "last_off": ((1,), "int64")}
    compiled = _compile(main, feeds, [
        fetches[n].name for n in ("next_token", "expert_counts")], one,
        [shapes[n] for n in feeds])
    rung = _report(f"LongCat-Flash chunk program: rung {chunk}", compiled)
    assert rung < 15.3e9                 # the issue's line
    assert compiled.as_text().count("mla_chunk_attention") >= 8
    after = {k: stat_get(k) - v for k, v in before.items()}
    assert after == {
        "attention_lowered_latent_chunk": 8,
        "attention_lowered_latent_chunk_reference": 0,
        "attention_lowered_latent_decode": 8,
        "attention_lowered_latent_decode_reference": 0,
        "grouped_matmul_lowered_ragged_dot": 0}

"""The ``sdar30b-blockgen`` cell without a chip: its files and arithmetic,
a ``--rehearse`` run, the check's bfloat16 control at toy widths, and
compile-only sizing of its pass program at 48 slots x 2048 and of its
widest prefill rung for a described TPU v5e (the topology is described
inside a fixture; a compile that passes is not a chip run).

    python -m pytest benchmark/tests/test_sdar.py -s
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
CELL = "sdar30b-blockgen"

from test_compile_only import as_tpu, topo  # noqa: E402,F401 (fixtures)
from test_manifest import TABLE, check_cell  # noqa: E402


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "sdar-30b-a3b-chat.json")
MIX = _json("traffic", "blockgen-pool.json")


def test_configuration_keeps_every_published_key():
    """The catalog row's ``config``, every key, but the depth."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 4
    assert CFG["published"] == {"num_hidden_layers": 48}
    assert (CFG["as_run"]["dtype"], CFG["as_run"]["attention_precision"]) \
        == ("float32", "highest")
    gen = CFG["assumed"]["generation"]
    assert (gen["block_length"], gen["mask_token_id"], gen["schedule"],
            gen["passes"], gen["qk_norm"], gen["logit_shift"],
            gen["eos_id"]) == (4, 151669, "static", 2, True, 0, -1)
    assert len(CFG["assumed"]["why"]) >= 8 and "12 pipeline stages" \
        in CFG["deployment"]


def test_builder_reads_the_published_keys():
    import harness

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    assert model["layer_pattern"] == [{
        "window": None, "rope": True, "attn_precision": "highest",
        "ffn": {"experts": 128, "top_k": 8, "width": 768,
                "activation": "silu", "route_from": "normed"}}]
    assert model["block_diffusion"] == {"block": 4, "passes": 2,
                                        "mask_id": 151669}
    assert (model["hidden"], model["num_heads"], model["num_kv_heads"],
            model["head_dim"], model["qk_norm"], model["rope_base"]) \
        == (2048, 32, 4, 128, True, 1e6)


def test_mix_is_the_issues_block():
    import traffic

    p = traffic.lengths(MIX["prompt_len"], MIX["block"])
    o = traffic.lengths(MIX["output_len"], MIX["block"])
    assert p.min() >= 32 and p.max() <= 1024 and 140 < sorted(p)[8] < 185
    assert o.min() >= 192 and o.max() <= 1024 and 470 < sorted(o)[8] < 560
    print(f"\n[blockgen-pool] a block: prompts {list(p)} = {p.sum()}, "
          f"answers {list(o)} = {o.sum()}")
    assert (MIX["workers_per_slot"], MIX["block"], MIX["warm_blocks"],
            MIX["blocks"], MIX["block_length"], MIX["passes"],
            MIX["trace_s"]) == (2, 16, 3, 24, 4, 2, 8)
    e = MIX["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["page_tokens"],
            e["prefill_buckets"]) == (48, 2048, 16, [128, 256, 512, 1024])
    assert not (e["prefill_chunk"] or e["prefix_reuse"] or e["speculate"])
    assert p.max() + o.max() <= e["max_seq_len"]
    assert MIX["block_length"] \
        == CFG["assumed"]["generation"]["block_length"]
    assert [n % 4 for n in MIX["reference_prompts"]] == [1, 2, 0]


def test_counts_by_hand():
    import ops_bytes_sdar as ob

    # a layer: QKV 2048 x 5120, output 4096 x 2048, router 2048 x 128
    assert ob.attention_params(CFG) == 10485760 + 8388608 + 262144
    assert ob.expert_params(CFG) == 3 * 2048 * 768 == 4718592
    assert ob.norm_params(CFG) == 2 * 2048 + 2 * 128
    assert ob.kv_bytes_per_position(CFG, 4) == 4096
    # nothing routed, nothing cached, no row: attention, norms, the head
    base = ob.pass_bytes(CFG, 0.0, 0.0, 0.0, 4)
    assert base == 4 * (4 * (19136512 + 4352) + 2048 + 2048 * 151936)
    # every expert, 48 slots 700 positions deep, 192 rows
    full = ob.pass_bytes(CFG, 128.0, 48 * 700.0, 192.0, 4)
    assert full - base == 4 * (4 * 128 * 4718592 + 192 * 2048) \
        + 4096 * 4 * 48 * 700
    # the ISSUE's "about 11.2 GB" and 0.55 GB of K/V at that depth
    assert 11.5e9 < full < 11.9e9
    # prefill: QKV in every layer; attention (block-causal pairs), output,
    # router and 8 experts a token in all but the last; no head
    n = 1000
    qkv = 2.0 * 10485760
    rest = 2.0 * (8388608 + 262144 + 8 * 4718592)
    pairs = 16 * 250 * 251 / 2
    assert ob.prefill_flops(CFG, n) == pytest.approx(
        4 * qkv * n + 3 * (rest * n + 4.0 * 128 * 32 * pairs))


def test_cell_is_declared_with_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, "sdar-30b-a3b-chat", "blockgen-pool")
    gate, = [m for m in bench["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert CELL in gate["workloads"] and gate["bound"] == 0.06
    # the entries of its groups, each moving the gate, and the start-up
    # account's four: as many values as on its newest ledger line
    assert check_cell(CELL) == TABLE[CELL][2]


def test_new_readers_read_spans_and_leave_out_what_is_not_there():
    """``span_attr_ratio`` over decode-step spans: tokens over
    slot-passes, the commit share; a program without such attributes (the
    parent's, one token a step) gives nothing, and nothing is raised."""
    import harness

    class Span:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

    ratio = harness.load_module("readers", "span_attr_ratio")
    step = "generation/decode_step"
    spans = [Span(step, passes_denoise=32, passes_commit=16,
                  tokens_committed=64),
             Span(step, passes_denoise=30, passes_commit=18,
                  tokens_committed=64),
             Span(step, active=3), Span("generation/iteration")]
    den = ["passes_denoise", "passes_commit"]
    assert ratio.read({"spans": spans}, step, "tokens_committed", den) \
        == pytest.approx(128 / 96)
    assert ratio.read({"spans": spans}, step, "passes_commit", den,
                      scale=100.0) == pytest.approx(100 * 34 / 96)
    assert ratio.read({"spans": spans[2:]}, step, "passes_commit", den) \
        is None
    blocks = harness.load_module("readers", "roofline_blocks")
    assert blocks.read({}, "ops_bytes_sdar.pass_bytes",
                       "hbm_bytes_per_s") is None


def test_rehearsal_reaches_its_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3200000019", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    # three prompts (tails 1, 2, 0), every pass of three blocks each
    assert out.stdout.count("reference check: prompt") == 3
    assert out.stdout.count("NOT correct") == 0


def test_the_check_fills_the_timed_grid():
    """The set-up check's requests at the mix's own size: the reference
    prompts go in behind fillers that hold every other slot until they
    are through, the last on the last slot, with fillers behind it for
    the slots that one filler in five frees while they run."""
    import serve_blocks

    slots = MIX["engine"]["num_slots"]
    plan = serve_blocks.check_plan(CFG, MIX, 4294967311)
    compared = [i for i, (_, _, c) in enumerate(plan) if c]
    assert compared == [slots - 7, slots - 4, slots - 1]
    assert [len(plan[i][0]) for i in compared] == MIX["reference_prompts"]
    assert [plan[i][1] for i in compared] == [11, 10, 12]
    assert len(plan) == slots + slots // 8
    per_block = MIX["passes"] + 1
    through = slots - 1 + per_block * MIX["check_blocks"]
    # request i joins about pass i and runs about 3 passes a block
    ends = [i + per_block * -(-(len(p) % 4 + n) // 4)
            for i, (p, n, c) in enumerate(plan[:slots]) if not c]
    assert sum(e >= through for e in ends) >= 0.6 * len(ends)
    assert any(slots - 3 <= e < through for e in ends)
    assert all(n >= 1 and 32 <= len(p) <= 1000 for p, n, _ in plan)
    again = serve_blocks.check_plan(CFG, MIX, 4294967311)
    assert [(p, n) for p, n, _ in again] == [(p, n) for p, n, _ in plan]


@pytest.mark.parametrize("fault", [None, "reference reads another block"])
def test_the_check_compares_passes_of_a_full_grid(fault, monkeypatch):
    """``serve_blocks.reference_check`` at toy widths on six slots: the
    compared requests' passes come from a grid whose other slots are
    live in other phases, and they are the reference's; a reference that
    is shown another token in a commit pass's block makes it not
    correct."""
    import harness
    import serve_blocks

    cell = harness.Cell(CELL, rehearse=True)
    cell.mix["engine"]["num_slots"] = 6
    said = []

    class Run:
        pass

    run = Run()
    run.cell, run.say = cell, said.append
    if fault:
        jitted = serve_blocks.jitted_forward

        def other_block(ref, cfg):
            forward = jitted(ref, cfg)

            def shown(params, ids, masked, rows, prog, covers):
                ids = ids.copy()
                ids[rows[-1]] = (ids[rows[-1]] + 1) % cfg["vocab_size"]
                return forward(params, ids, masked, rows, prog, covers)
            return shown

        monkeypatch.setattr(serve_blocks, "jitted_forward", other_block)
    ok, scope = serve_blocks.reference_check(run, cell.cfg, cell.mix,
                                             3200000033)
    assert ok == (fault is None) and scope is not None
    lines = [line for line in said if "reference check: prompt" in line]
    assert len(lines) == 3
    assert [("NOT correct" in line) for line in lines] \
        == [bool(fault)] * 3
    grid = said[-1]
    assert "grid of 6 slots" in grid and "(0 of them not exactly" in grid
    live = float(grid.split(" slots live a pass")[0].split()[-1])
    assert live > 3.0, grid          # with the ramps at both ends


def test_bfloat16_throughout_fails_the_check():
    """The check's control (``bf16_control_sdar.py``): the reference
    computed in bfloat16 throughout goes through the cell's own
    comparison (``serve_blocks.check_request``) in the program's place
    and comes out not correct on at least one prompt, by its logits
    (over ``share_of_range``), even at the toy widths.  The reading at
    published widths is taken on the chip (PERF.md section 6)."""
    import harness
    from bf16_control_sdar import readings

    cell = harness.Cell(CELL, rehearse=True)
    got = readings(cell, 3200000019)
    assert len(got) == 3 and not all(fine for _, fine, _ in got)
    assert all(fine == (rel <= cell.tolerance) for _, fine, rel in got)


def test_programs_compile_and_fit(topo, as_tpu):  # noqa: F811
    """Weights, the page pool and the temporaries of the pass program at
    the mix's 48 slots x 2048 and of its widest prefill rung fit one
    chip; the R-row paged kernel, the block-causal prefill kernel and the
    grouped expert matmul are in the programs."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import (build_llama_decode,
                                         build_llama_prefill)

    import harness
    from test_compile_only import _compile, _report

    model = harness.load_module("builders", CFG["builder"]).model_args(CFG)
    bd = model.pop("block_diffusion")
    e = MIX["engine"]
    slots, pt_ = e["num_slots"], e["page_tokens"]
    np_slot = e["max_seq_len"] // pt_
    pages = slots * np_slot + 1
    one = list(topo.devices)[:1]
    B = bd["block"]

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches, _ = build_llama_decode(
            slots, e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=pt_, block=B,
            mask_id=bd["mask_id"], **model)
    shapes = {"tokens": ((slots, B), "int64"),
              "masked": ((slots, B), "int32"),
              "quota": ((slots,), "int32"), "fresh": ((slots,), "int32"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches[n].name for n in (
        "tokens", "masked", "expert_counts")], one,
        [shapes[n] for n in feeds])
    total = _report(f"SDAR pass program: {slots} slots x "
                    f"{e['max_seq_len']}, block {B}, {pages} pages",
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
            page_tokens=pt_, mask_block=B, **model)
    shapes = {"input_ids": ((1, bucket), "int64"),
              "block_table": ((1, np_slot), "int32"),
              "prompt_len": ((1,), "int32")}
    assert "last_pos" not in feeds
    compiled = _compile(main, feeds, [fetches["rows_written"].name,
                                      fetches["expert_counts"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"SDAR paged block-causal prefill: rung {bucket}",
                    compiled)
    text = compiled.as_text()
    assert total < 0.95 * HBM_BYTES
    assert "ragged-dot" in text and "tpu_custom_call" in text

"""``BENCHMARK.json`` against the files under ``benchmark/`` among the
tier-1 tests (PR 41; PR 38 left it to "a later PR of another kind"): the
pure-JSON checks of ``benchmark/tests/test_manifest.py`` are collected
here as they stand (entries against data files, every entry's
``workloads``, each cell's count of values), and the cells that PR 41, PR
43, PR 47 and PR 51 added are pinned beside them, by name: each one's own entries, the
shared ``.pool`` entries that list it, its configuration's cut and its
mix; so is the start-up account's `.setup` family that PR 53 added to
every cell.  The cell PR 56 added joined families and brought no entry
(PR 55's rule): it is pinned by its row, as ``test_manifest.TABLE`` has
the others'.  The cell PR 59 added joined families likewise and brought
three entries for what no cell had (the state-space kernels): a group of
their own, put on the collected module here.  The cell PR 63 added did
the same, with two entries for what no cell had (the grouped kernel's
share of its roofline over every program that calls it, the rows a touched
held expert multiplies) and one reader.  The cell PR 66 added did the
same, with two entries (the identity picks' share of the routed picks, the
carried expert branch's share of the device's time) and no reader.  No JAX
is imported and no engine started.
"""
import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_manifest",
    os.path.join(BENCH, "tests", "test_manifest.py"))
manifest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(manifest)

# PR 42 added one entry to the dp4 cell (`collective_exposed_all_pct.train`,
# the exposed share with XLA:TPU's asynchronous collective fusions counted);
# the benchmark's own table of counts is a `benchmark` PR's to edit, so the
# count the collected check holds the cell to is raised here
manifest.REPORTS["bert-base-seq512-dp4"] += 1

# PR 53 added the start-up account's four `.setup` entries, which every
# cell reports and which move `setup_s`.  The collected checks count a
# cell's values and compare its shared families with ``POOL``, the
# families that move the cell's own gate: they go on looking at those, and
# the `.setup` family is pinned by a check of its own, below
SETUP = {"setup_import_s.setup": ("startup_part", ["startup/import"]),
         "setup_trace_lower_s.setup": (
             "startup_part", ["compile/trace", "compile/lower"]),
         "setup_backend_s.setup": ("startup_part", ["compile/backend"]),
         "setup_unaccounted_s.setup": ("startup_unaccounted", None)}
_reported_by = manifest.reported_by


def _reported_by_gate(cell):
    own, shared = _reported_by(cell)
    return own, [name for name in shared if name not in SETUP]


manifest.reported_by = _reported_by_gate

# PR 59 added three entries, the state-space kernels' (``ssm_*``), that only
# its cell reports: a group of their own, so that the collected check that
# every entry is in one group holds (the benchmark's own table of groups is
# a `benchmark` PR's to edit)
SSM = ["ssm_step_roofline.pool", "ssm_chunk_roofline.pool",
       "ssm_kernel_share_pct.pool"]
manifest.GROUPS["state space"] = SSM
# PR 63 likewise, two entries that only its cell reports
LATENT_EXPERTS = ["expert_kernel_roofline.pool",
                  "moe_rows_per_held_expert.pool"]
manifest.GROUPS["experts in a latent row"] = LATENT_EXPERTS
# PR 66 likewise, two entries that only its cell reports
IDENTITY = ["moe_zero_pairs_pct.pool", "shortcut_branch_share_pct.pool"]
manifest.GROUPS["identity experts"] = IDENTITY
# entries behind `.setup`
AFTER_SETUP = len(SSM) + len(LATENT_EXPERTS) + len(IDENTITY)

# the benchmark's own checks, collected here under their own names
globals().update({name: fn for name, fn in vars(manifest).items()
                  if name.startswith("test_")})

# PR 68: the engine's own rule serves Mistral's rehearsal engine in bfloat16
# (``models/llama.py`` ``serving_dtype``), and the benchmark's check of the
# same name holds every rehearsal engine to float32 in all three classes
# (``benchmark/tests/as_run_checks.py``, a `benchmark` PR's to edit).  The
# check is taken here under its own name with its own cases, expecting of
# each configuration what the rule builds.
_as_run = manifest.as_run_checks
BFLOAT16_BY_THE_RULE = ["mistral-7b-v0.1"]


@pytest.mark.parametrize("config", list(_as_run.FLOAT32))
def test_as_run_the_keep_list_matches_the_engines_arrays(config):
    """Every pattern of ``keeps_float32`` matches an array of the engine a
    rehearsal-size builder makes; the engine is observed in the dtype the
    program's rule builds (Mistral: bfloat16 weights and pages, no state;
    the others float32 in all three classes) and held to that entry."""
    import fnmatch

    harness = _as_run.harness
    cell, gen = _as_run._engine_read_only(config)
    names = [n[len(gen.name) + 1:] for n in gen.scope.local_var_names()
             if n.startswith(gen.name + ".")]
    for pattern in harness.kept_patterns(cell.cfg):
        assert any(fnmatch.fnmatchcase(n, pattern) for n in names), pattern
    low = config in BFLOAT16_BY_THE_RULE
    dtype = "bfloat16" if low else "float32"
    assert gen.dtype == dtype
    assert cell.observed == {
        "weights": dtype, "pages": dtype,
        "state": "float32" if gen.state_names else None}
    assert cell.admitted and not cell.observed_problems
    share, margin, _ = _as_run.FLOAT32[config]
    tol = cell.cfg["check_tolerance"]
    whole = harness.load_json("configs", config + ".json")
    if low:
        # held to the file's bfloat16 entry (the toy widths' own, where
        # the rehearsal group has one)
        entry = whole.get("rehearse", {}).get("check_tolerance", {}).get(
            "bfloat16") or whole["check_tolerance"]["bfloat16"]
        assert cell.tolerance == entry["share_of_range"] > share
    elif "check_tolerance" not in cell.cfg.get("rehearse", {}):
        assert cell.tolerance == share \
            and tol.get(_as_run.MARGIN) == margin


# the cells that model_config PRs added since the merge (PR 41, 43, 47, 51),
# in the order they were added: the cell's configuration and mix, its own
# entries, the shared families that list it beside ``POOL``, what its
# configuration cuts, and its mix's driver and reference rungs
OLMO, SOLAR = "olmo-hybrid7b-longdoc", "solar-open2-agentturns"
GIGA = "gigachat35-ragturns"
CMDA = "command-a-plus-ragdocs"
ADDED = {
    OLMO: {
        "config": "olmo-hybrid-7b", "mix": "longdoc-pool",
        "own": ["decode_step_roofline.olmo", "prefill_roofline.olmo",
                "paged_kernel_roofline.olmo", "gdn_step_roofline.olmo",
                "gdn_chunk_roofline.olmo", "gdn_kernel_share_pct.olmo",
                "state_slots_pct.olmo", "scan_pad_pct.olmo"],
        "experts": [], "reduced": ["num_hidden_layers", "layer_types"],
        "driver": "serve_delta", "rungs": [512, 2048, 6144]},
    SOLAR: {
        "config": "solar-open2-250b", "mix": "agentturns-pool",
        "own": ["decode_step_roofline.solar", "prefill_roofline.solar",
                "paged_kernel_roofline.solar", "kda_step_roofline.solar",
                "kda_chunk_roofline.solar", "kda_kernel_share_pct.solar",
                "state_slots_pct.solar", "scan_pad_pct.solar",
                "moe_held_touched_pct.solar", "moe_pairs_held_pct.solar"],
        # of the expert cells' families, those whose reader and arguments
        # mean the same over a share of the experts
        "experts": ["moe_expert_load_max_over_mean.pool",
                    "expert_matmul_share_pct.pool"],
        "reduced": ["num_hidden_layers", "gqa_layers", "n_routed_experts",
                    "vocab_size"],
        "driver": "serve_share", "rungs": [256, 2048, 4096]},
    GIGA: {
        "config": "gigachat35-432b-a28b", "mix": "ragturns-pool",
        "own": ["decode_step_roofline.giga", "prefill_roofline.giga",
                "mla_decode_bytes_roofline.giga",
                "mla_decode_flops_roofline.giga",
                "mla_prefill_roofline.giga", "mla_kernel_share_pct.giga",
                "gdn_step_roofline.giga", "gdn_chunk_roofline.giga",
                "gdn_kernel_share_pct.giga", "state_slots_pct.giga",
                "scan_pad_pct.giga", "moe_pairs_held_pct.giga",
                "moe_held_touched_pct.giga", "latent_fill_pct.giga"],
        "experts": ["moe_expert_load_max_over_mean.pool",
                    "expert_matmul_share_pct.pool"],
        "reduced": ["num_hidden_layers", "first_k_dense_replace",
                    "full_attention_layers", "n_routed_experts",
                    "vocab_size", "num_nextn_predict_layers"],
        "driver": "serve_share", "rungs": [256, 1024, 2048]},
    CMDA: {
        "config": "command-a-plus-05-2026", "mix": "ragdocs-pool",
        "own": ["decode_step_roofline.cmda", "prefill_roofline.cmda",
                "chunk_attention_roofline.cmda",
                "paged_kernel_roofline.cmda",
                "chunk_attention_share_pct.cmda",
                "chunk_share_of_busy_pct.cmda",
                "kv_window_pages_saved_pct.cmda",
                "window_released_in_prefill_pct.cmda", "chunk_pad_pct.cmda",
                "moe_pairs_held_pct.cmda", "moe_held_touched_pct.cmda"],
        "experts": ["moe_expert_load_max_over_mean.pool",
                    "expert_matmul_share_pct.pool"],
        "reduced": ["num_hidden_layers", "layer_types", "num_experts",
                    "vocab_size"],
        # the first cell that prefills in chunks: the rungs are those of
        # each reference prompt's LAST chunk
        "driver": "serve_chunks", "rungs": [256, 1024, 1024],
        "chunk": 1024},
}


# the cell PR 56 added, after the merge of PR 55: it joined families and
# brought its arguments in its own files, so it has a row (the end-to-end
# metric it moves, its groups, its count of values) and no entry of its
# own.  Of "latent pages" it reports all but the single-shot latent
# prefill kernel's share, which its timed path never runs
DSV2 = "deepseek-v2-docqa"
DSV2_NOT_RUN = "mla_prefill_roofline.pool"
DSV2_ROW = ("served_tokens_per_s", [
    "closed loop", "experts", "experts, a share held", "step on its span",
    "latent pages", "chunked prefill"], 40)
# the cell PR 59 added: it joined families as PR 56's did, and its row
# names the group of the three entries it brought
GRANITE = "granite4h-micro-manychats"
GRANITE_ROW = ("served_tokens_per_s", [
    "closed loop", "whole-prompt prefill", "step on its span",
    "paged decode kernel", "slot state", "state space"], 34)
# the cell PR 63 added: families joined, and the group of its two entries
NEMO = "nemotron3-super-agentfleet"
NEMO_ROW = ("served_tokens_per_s", [
    "closed loop", "experts", "experts, a share held",
    "whole-prompt prefill", "step on its span", "paged decode kernel",
    "slot state", "state space", "experts in a latent row"], 40)
# the cell PR 66 added: families joined (the latent expert cell's group of
# rows a held expert multiplies among them), and the group of its two entries
LONGCAT = "longcat-flash-agentchat"
LONGCAT_ROW = ("served_tokens_per_s", [
    "closed loop", "experts", "experts, a share held", "step on its span",
    "latent pages", "chunked prefill", "identity experts"], 43)
JOINED = {DSV2: {"config": "deepseek-v2", "mix": "docqa-pool",
                 "reduced": ["num_hidden_layers", "n_routed_experts",
                             "vocab_size"],
                 "driver": "serve_chunks", "rungs": [512, 512, 1024],
                 "chunk": 1024},
          GRANITE: {"config": "granite-4.0-h-micro",
                    "mix": "manychats-pool",
                    "reduced": ["num_hidden_layers", "layer_types"],
                    "driver": "serve_delta", "rungs": [128, 512, 1024]},
          NEMO: {"config": "nemotron3-super-120b-a12b",
                 "mix": "agentfleet-pool",
                 "reduced": ["num_hidden_layers", "hybrid_override_pattern",
                             "n_routed_experts", "vocab_size",
                             "num_nextn_predict_layers"],
                 "driver": "serve_share", "rungs": [256, 256, 512]},
          LONGCAT: {"config": "longcat-flash-chat",
                    "mix": "agentchat-pool",
                    "reduced": ["num_layers", "num_attention_heads",
                                "n_routed_experts", "vocab_size"],
                    "driver": "serve_chunks", "rungs": [256, 512, 256],
                    "chunk": 512}}
CELLS_AT_PR54 = 11


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_the_benchmark_has_thirteen_configurations_and_fifteen_cells():
    spec = manifest.SPEC
    assert [c["name"] for c in spec["configs"]] == [
        "bert-base-mlm", "mistral-7b-v0.1", "smallthinker-21b-a3b",
        "sdar-30b-a3b-chat", "lfm2-24b-a2b"] \
        + [a["config"] for a in ADDED.values()] \
        + [a["config"] for a in JOINED.values()]
    assert manifest.CELLS == [
        "bert-base-seq512", "mistral7b-chat", "mistral7b-longprompt",
        "bert-base-seq512-dp4", "smallthinker21b-mixedlen",
        "sdar30b-blockgen", "lfm2-24b-longanswer"] + list(ADDED) \
        + list(JOINED)
    assert (len(spec["configs"]), len(manifest.CELLS)) == (13, 15)
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] \
        == ["bert-base-seq512-dp4"]
    for cell, joined in JOINED.items():
        new, = [w for w in spec["workloads"] if w["name"] == cell]
        assert (new["chips"], new["config"], new["traffic"]) \
            == (1, joined["config"], joined["mix"])
    assert spec["workloads"][-1] == new
    # a cell that joins families adds no entry; PR 59's brought three for
    # what no cell had, at the end, PR 63's two and PR 66's two behind them
    assert len(manifest.ENTRIES) == 97 + AFTER_SETUP == 104
    assert [m["name"] for m in manifest.ENTRIES][-AFTER_SETUP:] \
        == SSM + LATENT_EXPERTS + IDENTITY
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200, c["name"]
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert os.path.exists(os.path.join(
            BENCH, "reference", c["name"] + ".py"))
    for w in spec["workloads"]:
        mix = _json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH, mix["driver"] + ".py"))
        assert len(w["why"]) <= 200
    # 79 entries at the merge, each added cell's own, the dp4 cell's one
    # entry of PR 42 and the start-up account's four of PR 53
    assert len(manifest.PER_LAYER) \
        == 79 + sum(len(a["own"]) for a in ADDED.values()) + 1 \
        + len(SETUP) == 127


@pytest.mark.parametrize("name", list(SETUP))
def test_every_cell_reports_the_start_up_account(name):
    """The `.setup` family (PR 53): four entries at the end of
    ``per_layer``, each listing every cell in the manifest's order (the
    eleven of PR 54 under the names of PR 54, all twelve now) and moving
    ``setup_s``, each with its data file and a reader that reads the
    program's kept spans by name."""
    names = [m["name"] for m in manifest.PER_LAYER]
    assert names[-len(SETUP):] == list(SETUP)
    entry, = [m for m in manifest.PER_LAYER if m["name"] == name]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "start-up",
                     "moves": "setup_s",
                     "workloads": manifest.CELLS[:CELLS_AT_PR54]}
    assert manifest.BY_NAME[name] == dict(entry, workloads=manifest.CELLS)
    assert [m["name"] for m in manifest.ENTRIES][
        -len(SETUP) - AFTER_SETUP:-AFTER_SETUP] == list(SETUP)
    gate, = [m for m in manifest.SPEC["end_to_end"]
             if m["name"] == "setup_s"]
    assert "workloads" not in gate and gate["bound"] == 0.1
    reader, spans = SETUP[name]
    spec = _json("metrics", name + ".json")
    assert spec["reader"] == reader and len(spec["why"]) > 40
    assert spec["args"] == ({} if spans is None else {"spans": spans})
    assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))
    # the spans it names are ones the program makes
    made = open(os.path.join(REPO, "paddle_tpu", "compile_cache.py")).read() \
        + open(os.path.join(REPO, "paddle_tpu", "__init__.py")).read()
    for span in spans or ():
        assert f'"{span}"' in made, span
    # and every cell still reports, beside them, what moves its own gate
    for cell in manifest.CELLS[:CELLS_AT_PR54]:
        own, shared = _reported_by(cell)
        assert name in shared and name not in own
    for cell in manifest.CELLS:
        assert name in manifest.entries_of(cell)


@pytest.mark.parametrize("cell", list(ADDED))
def test_an_added_cell_reports_its_own_and_the_shared_entries(cell):
    added = ADDED[cell]
    own, shared = manifest.reported_by(cell)
    assert sorted(own) == sorted(added["own"])
    assert sorted(shared) == sorted(manifest.POOL + added["experts"])
    # a dense decoder reports none of the experts' families; a share of
    # the experts not the one that divides by ``num_experts``
    assert manifest.TOUCHED not in shared
    by_name = {m["name"]: m for m in manifest.PER_LAYER}
    later = list(ADDED)[list(ADDED).index(cell) + 1:]
    for name in own + shared:
        assert by_name[name]["moves"] == "served_tokens_per_s"
        # appended in the order the cells came: only later cells follow
        lists = by_name[name]["workloads"]
        assert set(lists[lists.index(cell) + 1:]) <= set(later), name
    gate, = [m for m in manifest.SPEC["end_to_end"]
             if m["name"] == "served_tokens_per_s"]
    assert gate["workloads"][-len(ADDED) - len(JOINED):] \
        == list(ADDED) + list(JOINED)
    assert gate["bound"] == 0.06
    # its own entries lie together, in the order its PR gave them
    names = [m["name"] for m in manifest.PER_LAYER]
    at = names.index(added["own"][0])
    assert names[at:at + len(added["own"])] == added["own"]


@pytest.mark.parametrize("name,reader,reads", [
    ("decode_step_roofline.olmo", "roofline_span",
     ["live_positions", "state_slots"]),
    ("paged_kernel_roofline.olmo", "roofline_kernel",
     "^%?paged_decode_attention"),
    ("gdn_step_roofline.olmo", "roofline_kernel", "^%?gated_delta_step"),
    ("gdn_chunk_roofline.olmo", "roofline_kernel_prefill",
     "^%?gated_delta_chunk"),
    ("gdn_kernel_share_pct.olmo", "trace_op_share", "^%?gated_delta"),
    ("state_slots_pct.olmo", "span_attr_mean", "state_slots"),
    ("scan_pad_pct.olmo", "span_attr_ratio", "scan_pad_chunks"),
    ("prefill_roofline.olmo", "roofline", "prefill"),
    ("decode_step_roofline.solar", "roofline_span",
     ["experts_held_touched", "live_positions", "state_slots"]),
    ("paged_kernel_roofline.solar", "roofline_kernel",
     "^%?paged_decode_attention"),
    ("kda_step_roofline.solar", "roofline_kernel", "^%?gated_delta_step"),
    ("kda_chunk_roofline.solar", "roofline_kernel_prefill",
     "^%?gated_delta_chunk"),
    ("kda_kernel_share_pct.solar", "trace_op_share", "^%?gated_delta"),
    ("state_slots_pct.solar", "span_attr_mean", "state_slots"),
    ("scan_pad_pct.solar", "span_attr_ratio", "scan_pad_chunks"),
    ("prefill_roofline.solar", "roofline", "prefill"),
    ("moe_held_touched_pct.solar", "span_attr_mean",
     "experts_held_touched"),
    ("moe_pairs_held_pct.solar", "span_attr_ratio", "pairs_held"),
    ("decode_step_roofline.giga", "roofline_span",
     ["experts_held_touched", "latent_positions", "state_slots"]),
    ("prefill_roofline.giga", "roofline", "prefill"),
    ("mla_decode_bytes_roofline.giga", "roofline_kernel",
     "^%?mla_decode_attention"),
    ("mla_decode_flops_roofline.giga", "roofline_kernel",
     "^%?mla_decode_attention"),
    ("mla_prefill_roofline.giga", "roofline_kernel_prefill",
     "^%?mla_prefill_attention"),
    ("mla_kernel_share_pct.giga", "trace_op_share",
     "^%?mla_(decode|prefill)_attention"),
    ("gdn_step_roofline.giga", "roofline_kernel", "^%?gated_delta_step"),
    ("gdn_chunk_roofline.giga", "roofline_kernel_prefill",
     "^%?gated_delta_chunk"),
    ("gdn_kernel_share_pct.giga", "trace_op_share", "^%?gated_delta"),
    ("state_slots_pct.giga", "span_attr_mean", "state_slots"),
    ("scan_pad_pct.giga", "span_attr_ratio", "scan_pad_chunks"),
    ("moe_pairs_held_pct.giga", "span_attr_ratio", "pairs_held"),
    ("moe_held_touched_pct.giga", "span_attr_mean",
     "experts_held_touched"),
    ("latent_fill_pct.giga", "span_attr_mean", "latent_positions"),
    ("decode_step_roofline.cmda", "roofline_span",
     ["experts_held_touched", "live_positions", "live_positions_window"]),
    ("prefill_roofline.cmda", "roofline_chunks",
     "ops_bytes_command_a_plus.chunk_flops"),
    ("chunk_attention_roofline.cmda", "roofline_kernel_prefill",
     "^%?chunk_attention"),
    ("paged_kernel_roofline.cmda", "roofline_kernel",
     "^%?paged_decode_attention"),
    ("chunk_attention_share_pct.cmda", "trace_op_share",
     "^%?chunk_attention"),
    ("chunk_share_of_busy_pct.cmda", "module_busy_share", "prefill"),
    ("kv_window_pages_saved_pct.cmda", "kv_pages_saved",
     "ops_bytes_command_a_plus.window_layer_count"),
    ("window_released_in_prefill_pct.cmda", "span_attr_ratio",
     "window_pages_released"),
    ("chunk_pad_pct.cmda", "span_attr_ratio", "pad_rows"),
    ("moe_pairs_held_pct.cmda", "span_attr_ratio", "pairs_held"),
    ("moe_held_touched_pct.cmda", "span_attr_mean",
     "experts_held_touched"),
])
def test_each_new_metric_reads_a_span_a_counter_or_a_named_kernel(
        name, reader, reads):
    """What a new per-layer metric reads is in the program: the span
    attribute by its name in ``serving/generation.py``, the kernel by the
    ``name=`` of its ``pallas_call``."""
    spec = _json("metrics", name + ".json")
    assert spec["reader"] == reader
    args = spec["args"]
    assert reads in (args.get("attrs"), args.get("pattern"),
                     args.get("attr"), args.get("num"), args.get("per"),
                     args.get("fn"), args.get("which"))
    with open(os.path.join(REPO, "paddle_tpu", "serving",
                           "generation.py")) as f:
        engine = f.read()
    with open(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                           "gated_delta.py")) as f:
        kernels = f.read()
    for attr in ("state_slots", "live_positions", "scan_tokens",
                 "scan_chunks", "scan_pad_chunks", "pairs_routed",
                 "pairs_held", "experts_held_touched"):
        assert attr + "=" in engine
    assert 'name="gated_delta_step"' in kernels
    assert 'name="gated_delta_chunk"' in kernels
    if name.endswith(".giga"):
        with open(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                               "latent_attention.py")) as f:
            latent = f.read()
        assert 'name="mla_decode_attention"' in latent
        assert 'name="mla_prefill_attention"' in latent
        assert 'attrs["latent_positions"]' in engine
        assert '"latent_rows_written"' in engine
        if "attr" in args and reader == "roofline_kernel_prefill":
            assert args["attr"] in ("latent_rows_written", "scan_tokens")
    if name.endswith(".cmda"):
        with open(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                               "flash_attention.py")) as f:
            assert 'name="chunk_attention"' in f.read()
        for attr in ("attended_pairs=", '"window_pages_released"',
                     '"window_pages_mapped"', '"window_pages_held"',
                     "pad_rows=", "live_positions_window="):
            assert attr in engine, attr
        if reader in ("roofline_kernel_prefill", "span_attr_ratio") \
                and "prefill_chunk" in str(args.get("span")):
            assert args["span"] == "generation/prefill_chunk"
        if "fn" in args:
            module, _, fn = args["fn"].rpartition(".")
            with open(os.path.join(BENCH, module + ".py")) as f:
                assert f"def {fn}(" in f.read()


@pytest.mark.parametrize("cell", list(ADDED))
def test_an_added_configuration_cuts_what_it_says_and_no_width(cell):
    added = ADDED[cell]
    cfg = _json("configs", added["config"] + ".json")
    entry, = [c for c in manifest.SPEC["configs"]
              if c["name"] == added["config"]]
    assert entry["reduced"] == cfg["reduced"] == added["reduced"]
    assert entry["source"] == cfg["source"]
    if cell == CMDA:
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["intermediate_size"], cfg["num_experts_per_tok"],
                cfg["num_shared_experts"], cfg["sliding_window"],
                cfg["layer_norm_eps"], cfg["rms_norm_eps"],
                cfg["rope_theta"], cfg["logit_scale"]) \
            == (4096, 128, 8, 128, 4096, 8, 4, 4096, 1e-5, None, 50000, 1)
        # ``reduced`` against ``published``: the guide's floors (one whole
        # period, 8 experts held, an eighth of the vocabulary)
        period = ["sliding_attention"] * 3 + ["full_attention"]
        assert cfg["published"] == {
            "num_hidden_layers": 32, "layer_types": period * 8,
            "num_experts": 128, "vocab_size": 262144}
        assert [cfg[k] for k in added["reduced"]] == [4, period, 8, 32768]
        assert cfg["expert_share"] == dict(
            cfg["expert_share"], router_experts=128, first=0)
        assert cfg["vocab_size"] * 8 == 262144
        assert (cfg["use_parallel_block"], cfg["tie_word_embeddings"],
                cfg["position_embedding_type"],
                cfg["shared_expert_combination_strategy"]) \
            == (True, True, "rope_gptj", "average")
    elif cell == GIGA:
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["kv_lora_rank"], cfg["q_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
                cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                cfg["swiglu_limit"], cfg["routed_scaling_factor"]) \
            == (7168, 64, 512, 1536, 128, 64, 128, 18432, 2048, 8, 32, 64,
                128, 128, 10, 2.5)
        # ``reduced`` against ``published``: the guide's floors (a leading
        # dense layer and a whole period, 8 experts held, an eighth of the
        # vocabulary), the drafting heads gone with the depth
        assert cfg["published"] == {
            "num_hidden_layers": 40, "first_k_dense_replace": 3,
            "full_attention_layers": list(range(3, 40, 4)),
            "n_routed_experts": 256, "vocab_size": 128256,
            "num_nextn_predict_layers": 2}
        assert [cfg[k] for k in added["reduced"]] \
            == [5, 1, [1], 8, 16032, 0]
        assert cfg["expert_share"] == dict(
            cfg["expert_share"], router_experts=256, first=0)
        assert cfg["vocab_size"] * 8 == 128256
        assert cfg["as_run"]["latent_row"]["lanes"] == 640
    elif cell == OLMO:
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["intermediate_size"], cfg["vocab_size"],
                cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                cfg["linear_num_value_heads"],
                cfg["linear_conv_kernel_dim"]) \
            == (3840, 30, 11008, 100352, 96, 192, 30, 4)
        assert cfg["layer_types"] == cfg["published"]["layer_types"][:4]
        assert cfg["published"]["num_hidden_layers"] == 32
    else:
        lin = cfg["linear_attn_config"]
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
                cfg["n_shared_experts"], lin["num_heads"], lin["head_dim"],
                lin["short_conv_kernel_size"], cfg["rms_norm_eps"]) \
            == (4096, 64, 8, 128, 1280, 8, 1, 64, 128, 4, 1e-5)
        # the guide's floors: a whole period, 8 experts or more held, an
        # eighth of the vocabulary or more
        assert cfg["gqa_layers"] == cfg["published"]["gqa_layers"][:1]
        assert cfg["published"] == {
            "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
            "n_routed_experts": 320, "vocab_size": 196608}
        assert cfg["n_routed_experts"] == 20 >= 8
        assert cfg["expert_share"]["router_experts"] == 320
        assert cfg["vocab_size"] * 8 == 196608
    assert cfg["num_hidden_layers"] == (5 if cell == GIGA else 4)
    for key in ("assumed", "as_run", "deployment", "check_tolerance",
                "rehearse", "builder"):
        assert key in cfg
    assert len(cfg["check_tolerance"]["why"]) > 200


@pytest.mark.parametrize("cell", list(ADDED) + list(JOINED))
def test_an_added_mix_is_closed_loop_over_whole_chunks_and_pages(cell):
    added = dict(ADDED, **JOINED)[cell]
    mix = _json("traffic", added["mix"] + ".json")
    e = mix["engine"]
    assert (mix["driver"], mix["loop"], mix["workers_per_slot"],
            mix["block"]) == (added["driver"], "closed", 2, 16)
    assert all(b % 64 == 0 and b % e["page_tokens"] == 0
               for b in e["prefill_buckets"])
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= e["max_seq_len"]
    assert not (e["prefix_reuse"] or e["speculate"])
    chunk = added.get("chunk", 0)
    assert e["prefill_chunk"] == chunk
    rungs = sorted(e["prefill_buckets"])
    if chunk:
        # whole pages, and chunk rungs that divide the chunk
        assert chunk % e["page_tokens"] == 0 and rungs[-1] == chunk
        assert all(chunk % b == 0 for b in rungs)
    assert [min(b for b in rungs if b >= ((n - 1) % chunk + 1 if chunk
                                          else n))
            for n in mix["reference_prompts"]] == added["rungs"]


# -- PR 56: a cell that joined families and brought no entry ----------------

def _groups_less_not_run():
    return dict(manifest.GROUPS, **{"latent pages": [
        n for n in manifest.GROUPS["latent pages"] if n != DSV2_NOT_RUN]})


def test_the_joined_cell_reports_its_groups_and_forty_values(monkeypatch):
    """Its row: the 22 of the closed loop, the experts' two, a held
    share's two, the step on its span, four of the five of latent pages,
    the five of chunked prefill and the four of the start-up account."""
    monkeypatch.setattr(manifest, "GROUPS", _groups_less_not_run())
    assert manifest.check_cell(DSV2, DSV2_ROW) == DSV2_ROW[2] == 40
    assert DSV2 not in manifest.BY_NAME[DSV2_NOT_RUN]["workloads"]
    # every entry lists it last, or last but for the cell that came after
    # it: the cells stand in the order they came
    for name in manifest.entries_of(DSV2):
        lists = manifest.BY_NAME[name]["workloads"]
        assert lists[lists.index(DSV2) + 1:] in (
            [], [GRANITE], [NEMO], [GRANITE, NEMO], [LONGCAT],
            [NEMO, LONGCAT], [GRANITE, NEMO, LONGCAT]), name
    assert DSV2 not in manifest.TABLE         # (a ``benchmark`` PR's to add)


@pytest.mark.parametrize("name,reader,key,reads", [
    ("decode_step_roofline.pool", "roofline_span", "attrs",
     ["experts_held_touched", "latent_positions"]),
    ("decode_step_roofline.pool", "roofline_span", "fn",
     "ops_bytes_deepseek_v2.decode_step_bytes"),
    ("mla_decode_bytes_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_deepseek_v2.mla_decode_bytes"),
    ("mla_decode_flops_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_deepseek_v2.mla_decode_flops"),
    ("mla_decode_flops_roofline.pool", "roofline_kernel", "pattern",
     "^%?mla_decode_attention"),
    ("mla_kernel_share_pct.pool", "trace_op_share", "pattern",
     "^%?mla_(decode|chunk)_attention"),
    ("latent_fill_pct.pool", "span_attr_mean", "scale", 100 / (10 * 12800)),
    ("prefill_chunk_roofline.pool", "roofline_chunks", "fn",
     "ops_bytes_deepseek_v2.chunk_flops"),
    ("chunk_attention_roofline.pool", "roofline_kernel_prefill", "fn",
     "ops_bytes_deepseek_v2.chunk_attention_flops"),
    ("chunk_attention_roofline.pool", "roofline_kernel_prefill", "pattern",
     "^%?mla_chunk_attention"),
    ("chunk_attention_roofline.pool", "roofline_kernel_prefill", "attr",
     "attended_pairs"),
    ("chunk_attention_share_pct.pool", "trace_op_share", "pattern",
     "^%?mla_chunk_attention"),
    ("chunk_pad_pct.pool", "span_attr_ratio", "num", "pad_rows"),
    ("moe_held_touched_pct.pool", "span_attr_mean", "per",
     "n_routed_experts"),
    ("moe_pairs_held_pct.pool", "span_attr_ratio", "num", "pairs_held"),
])
def test_the_joined_cell_hands_each_family_its_own_arguments(
        name, reader, key, reads):
    """What the cell's own files give a family's reader, resolved as the
    harness resolves it, and that the program makes it: the kernels by the
    ``name=`` of their ``pallas_call``, the attributes by their names in
    ``serving/generation.py``."""
    got_reader, args = manifest.check_arguments(name, DSV2)
    assert got_reader == reader and args[key] == reads
    with open(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                           "latent_attention.py")) as f:
        latent = f.read()
    assert 'name="mla_chunk_attention"' in latent
    assert 'name="mla_decode_attention"' in latent
    with open(os.path.join(REPO, "paddle_tpu", "serving",
                           "generation.py")) as f:
        engine = f.read()
    for attr in ('"latent_rows_written"', '"latent_rows_attended"',
                 '"latent_rows_expanded"', "attended_pairs=",
                 '"rows_group_held"', '"moe_rows_group_held"'):
        assert attr in engine, attr
    # a sibling's arguments are its own still
    assert manifest.resolved("mla_kernel_share_pct.pool", GIGA)[1] \
        == {"pattern": "^%?mla_(decode|prefill)_attention"}
    assert manifest.resolved("chunk_attention_roofline.pool", CMDA)[1][
        "pattern"] == "^%?chunk_attention"


def test_the_joined_configuration_cuts_what_it_says_and_no_width():
    joined = JOINED[DSV2]
    cfg = _json("configs", joined["config"] + ".json")
    entry, = [c for c in manifest.SPEC["configs"]
              if c["name"] == joined["config"]]
    assert entry["reduced"] == cfg["reduced"] == joined["reduced"]
    assert entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["q_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["n_group"], cfg["topk_group"],
            cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
            cfg["topk_method"], cfg["first_k_dense_replace"]) \
        == (5120, 128, 512, 1536, 128, 64, 128, 12288, 1536, 6, 2, 8, 3, 16,
            False, "group_limited_greedy", 1)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # ``reduced`` against ``published``: the one leading dense layer and
    # four expert layers, ONE WHOLE GROUP of the 8 held, an eighth of the
    # vocabulary
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert [cfg[k] for k in joined["reduced"]] == [5, 20, 12800]
    assert cfg["expert_share"] == dict(
        cfg["expert_share"], router_experts=160, first=0)
    assert cfg["n_routed_experts"] * cfg["n_group"] == 160
    assert cfg["vocab_size"] * 8 == 12800 * 8 == 102400
    assert cfg["as_run"]["latent_row"]["lanes"] == 640
    for key in ("assumed", "as_run", "deployment", "check_tolerance",
                "rehearse", "builder", "per_layer_args"):
        assert key in cfg
    assert len(cfg["check_tolerance"]["why"]) > 200
    assert os.path.exists(os.path.join(
        BENCH, "builders", cfg["builder"] + ".py"))


# -- PR 59: a cell that joined families and brought three entries ------------

def test_the_fixture_is_the_whole_parent_and_52_names_went():
    """The collected check of this name, at this PR's counts: the three
    entries PR 59 added came on top of the 22 that PR 55's merge made
    (the collected function holds 97 entries; the benchmark's own file is
    a `benchmark` PR's to edit, PERF.md section 7)."""
    at_pr54 = manifest.AT_PR54
    assert len(at_pr54) == 127
    assert len({r["old"] for r in at_pr54}) == 127
    went = {r["old"] for r in at_pr54} - set(manifest.BY_NAME)
    came = set(manifest.BY_NAME) - {r["old"] for r in at_pr54}
    assert (len(went), len(came), len(manifest.ENTRIES)) \
        == (52, 22 + AFTER_SETUP, 104)
    assert came >= set(SSM + LATENT_EXPERTS + IDENTITY)
    for r in at_pr54:
        assert set(r["cells"]) \
            <= set(manifest.BY_NAME[r["new"]]["workloads"]), r


def test_the_state_space_cell_reports_its_groups_and_thirty_four_values():
    """Its row: the 22 of the closed loop, the whole-prompt prefill, the
    step on its span, the paged decode kernel, slot state with the scan's
    padding (four), the three of the state-space kernels and the four of
    the start-up account.  No group of the expert path: the model routes
    over no experts."""
    groups = dict(manifest.GROUPS)
    # (``scan_pad_pct.pool`` sits in the delta rule's group; this cell's
    # scan is the state-space layers', and it reports that one entry of it)
    groups["slot state"] = groups["slot state"] + ["scan_pad_pct.pool"]
    manifest.GROUPS, kept = groups, manifest.GROUPS
    try:
        assert manifest.check_cell(GRANITE, GRANITE_ROW) \
            == GRANITE_ROW[2] == 34
    finally:
        manifest.GROUPS = kept
    for name in manifest.entries_of(GRANITE):
        lists = manifest.BY_NAME[name]["workloads"]
        assert lists[lists.index(GRANITE) + 1:] in (
            [], [NEMO], [LONGCAT], [NEMO, LONGCAT]), name
    assert GRANITE not in manifest.TABLE       # (a ``benchmark`` PR's to add)
    for name in SSM:
        assert manifest.BY_NAME[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": "served_tokens_per_s", "workloads": [GRANITE, NEMO]}
    # nothing of the delta rule's or the experts' is claimed
    for name in ("delta_step_roofline.pool", "delta_kernel_share_pct.pool",
                 "expert_matmul_share_pct.pool",
                 "attention_kernel_share_pct.pool"):
        assert GRANITE not in manifest.BY_NAME[name]["workloads"], name


@pytest.mark.parametrize("name,reader,key,reads", [
    ("decode_step_roofline.pool", "roofline_span", "attrs",
     ["live_positions", "state_slots"]),
    ("decode_step_roofline.pool", "roofline_span", "fn",
     "ops_bytes_granite_hybrid.decode_step_bytes"),
    ("prefill_roofline.pool", "roofline", "fn",
     "ops_bytes_granite_hybrid.prefill_flops"),
    ("paged_kernel_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_granite_hybrid.paged_kernel_bytes"),
    ("paged_kernel_roofline.pool", "roofline_kernel", "pattern",
     "^%?paged_decode_attention"),
    ("state_slots_pct.pool", "span_attr_mean", "scale", 100 / 128),
    ("scan_pad_pct.pool", "span_attr_ratio", "num", "scan_pad_chunks"),
    ("ssm_step_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_granite_hybrid.ssm_step_bytes"),
    ("ssm_step_roofline.pool", "roofline_kernel", "pattern",
     "^%?ssd_step"),
    ("ssm_step_roofline.pool", "roofline_kernel", "attrs", ["state_slots"]),
    ("ssm_chunk_roofline.pool", "roofline_kernel_prefill", "fn",
     "ops_bytes_granite_hybrid.ssm_chunk_bytes"),
    ("ssm_chunk_roofline.pool", "roofline_kernel_prefill", "pattern",
     "^%?ssd_chunk"),
    ("ssm_chunk_roofline.pool", "roofline_kernel_prefill", "attr",
     "scan_tokens"),
    ("ssm_kernel_share_pct.pool", "trace_op_share", "pattern", "^%?ssd_"),
])
def test_the_state_space_cell_hands_each_family_its_own_arguments(
        name, reader, key, reads):
    """What the cell's own files give a family's reader, resolved as the
    harness resolves it, and that the program makes it: the kernels by the
    ``name=`` of their ``pallas_call``, the attributes by their names in
    ``serving/generation.py``."""
    got_reader, args = manifest.check_arguments(name, GRANITE)
    assert got_reader == reader and args[key] == reads
    with open(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                           "ssd.py")) as f:
        kernels = f.read()
    assert 'name="ssd_step"' in kernels and 'name="ssd_chunk"' in kernels
    with open(os.path.join(REPO, "paddle_tpu", "serving",
                           "generation.py")) as f:
        engine = f.read()
    for attr in ("state_slots=", "live_positions=", "scan_tokens=",
                 "scan_chunks=", "scan_pad_chunks=", '"ssm_state_steps"'):
        assert attr in engine, attr
    if "fn" in args:
        module, _, fn = args["fn"].rpartition(".")
        with open(os.path.join(BENCH, module + ".py")) as f:
            assert f"def {fn}(" in f.read()
    # a sibling's arguments are its own still
    assert manifest.resolved("state_slots_pct.pool", OLMO)[1]["scale"] \
        == 100 / 28
    assert manifest.resolved("decode_step_roofline.pool", OLMO)[1]["fn"] \
        == "ops_bytes_olmo_hybrid.decode_step_bytes"


def test_the_state_space_configuration_cuts_depth_and_no_width():
    joined = JOINED[GRANITE]
    cfg = _json("configs", joined["config"] + ".json")
    entry, = [c for c in manifest.SPEC["configs"]
              if c["name"] == joined["config"]]
    assert entry["reduced"] == cfg["reduced"] == joined["reduced"]
    assert entry["source"] == cfg["source"]
    assert manifest.SPEC["configs"][-3] == entry
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"],
            cfg["mamba_d_conv"], cfg["mamba_expand"], cfg["mamba_conv_bias"],
            cfg["num_local_experts"], cfg["num_experts_per_tok"],
            cfg["rms_norm_eps"], cfg["tie_word_embeddings"],
            cfg["position_embedding_type"]) \
        == (2048, 32, 8, 8192, 8192, 64, 64, 128, 1, 4, 2, True, 0, 0, 1e-5,
            True, "nope")
    # the family's four multipliers, as published
    assert (cfg["embedding_multiplier"], cfg["attention_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"]) \
        == (12, 0.015625, 0.22, 8)
    # ``reduced`` against ``published``: depth alone, one WHOLE period of
    # the pattern (nine state-space layers to one of attention; the
    # issue's last resort: two did not fit the run's time limit), every
    # row of the vocabulary
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "layer_types": period * 4}
    assert [cfg[k] for k in joined["reduced"]] == [10, period]
    assert cfg["vocab_size"] == 100352
    assert cfg["as_run"]["dtype"] == "float32"
    for key in ("assumed", "as_run", "deployment", "check_tolerance",
                "rehearse", "builder", "per_layer_args"):
        assert key in cfg
    assert len(cfg["check_tolerance"]["why"]) > 200
    assert os.path.exists(os.path.join(
        BENCH, "builders", cfg["builder"] + ".py"))
    mix = _json("traffic", joined["mix"] + ".json")
    assert (mix["engine"]["num_slots"], mix["engine"]["max_seq_len"],
            mix["warm_blocks"] * mix["block"]) == (128, 1792, 128)


# -- PR 63: a cell that joined families and brought two entries --------------

def test_the_latent_expert_cell_reports_its_groups_and_forty_values():
    """Its row: the 22 of the closed loop, the experts' two, the held
    share's two, the whole-prompt prefill, the step on its span, the paged
    decode kernel, slot state with the scan's padding, the three of the
    state-space kernels, its own two and the four of the start-up
    account.  No group of the delta rule, of latent pages or of chunks."""
    groups = dict(manifest.GROUPS)
    groups["slot state"] = groups["slot state"] + ["scan_pad_pct.pool"]
    manifest.GROUPS, kept = groups, manifest.GROUPS
    try:
        assert manifest.check_cell(NEMO, NEMO_ROW) == NEMO_ROW[2] == 40
    finally:
        manifest.GROUPS = kept
    for name in manifest.entries_of(NEMO):
        lists = manifest.BY_NAME[name]["workloads"]
        assert lists[lists.index(NEMO) + 1:] in ([], [LONGCAT]), name
    assert NEMO not in manifest.TABLE          # (a ``benchmark`` PR's to add)
    kernel, rows = (manifest.BY_NAME[n] for n in LATENT_EXPERTS)
    assert kernel == {
        "name": LATENT_EXPERTS[0], "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "served_tokens_per_s", "workloads": [NEMO]}
    assert rows == {
        "name": LATENT_EXPERTS[1], "unit": "rows", "better": "higher",
        "source": "program_span", "layer": "expert path",
        "moves": "served_tokens_per_s", "workloads": [NEMO, LONGCAT]}
    for name in ("delta_step_roofline.pool", "mla_kernel_share_pct.pool",
                 "prefill_chunk_roofline.pool", manifest.TOUCHED,
                 "attention_kernel_share_pct.pool"):
        assert NEMO not in manifest.BY_NAME[name]["workloads"], name
    manifest.check_cell_loads(NEMO)


@pytest.mark.parametrize("name,reader,key,reads", [
    ("decode_step_roofline.pool", "roofline_span", "attrs",
     ["experts_held_touched", "live_positions", "state_slots"]),
    ("decode_step_roofline.pool", "roofline_span", "fn",
     "ops_bytes_nemotron_h.decode_step_bytes"),
    ("prefill_roofline.pool", "roofline", "fn",
     "ops_bytes_nemotron_h.prefill_flops"),
    ("paged_kernel_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_nemotron_h.paged_kernel_bytes"),
    ("state_slots_pct.pool", "span_attr_mean", "scale", 100 / 128),
    ("scan_pad_pct.pool", "span_attr_ratio", "num", "scan_pad_chunks"),
    ("ssm_step_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_nemotron_h.ssm_step_bytes"),
    ("ssm_step_roofline.pool", "roofline_kernel", "pattern", "^%?ssd_step"),
    ("ssm_chunk_roofline.pool", "roofline_kernel_prefill", "fn",
     "ops_bytes_nemotron_h.ssm_chunk_bytes"),
    ("ssm_kernel_share_pct.pool", "trace_op_share", "pattern", "^%?ssd_"),
    ("moe_held_touched_pct.pool", "span_attr_mean", "per",
     "n_routed_experts"),
    ("moe_pairs_held_pct.pool", "span_attr_ratio", "den", ["pairs_routed"]),
    ("expert_matmul_share_pct.pool", "trace_op_share", "pattern",
     "ragged-dot"),
    ("expert_kernel_roofline.pool", "roofline_kernel_calls", "fn",
     "ops_bytes_nemotron_h.expert_kernel_bytes"),
    ("expert_kernel_roofline.pool", "roofline_kernel_calls", "attrs",
     ["experts_held_touched", "pairs_held"]),
    ("expert_kernel_roofline.pool", "roofline_kernel_calls", "pattern",
     "^%?grouped_matmul_ragged-dot"),
    ("moe_rows_per_held_expert.pool", "span_attr_ratio", "num",
     "pairs_held"),
    ("moe_rows_per_held_expert.pool", "span_attr_ratio", "den",
     ["experts_held_touched"]),
    ("moe_rows_per_held_expert.pool", "span_attr_ratio", "scale", 0.2),
])
def test_the_latent_expert_cell_hands_each_family_its_own_arguments(
        name, reader, key, reads):
    """What the cell's own files give a family's reader, resolved as the
    harness resolves it, and that the program makes it: the kernels by the
    ``name=`` of their ``pallas_call``, the attributes by their names in
    ``serving/generation.py``."""
    import re

    got_reader, args = manifest.check_arguments(name, NEMO)
    assert got_reader == reader and args[key] == reads
    with open(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                           "grouped_matmul.py")) as f:
        assert 'name="grouped_matmul_ragged-dot"' in f.read()
    with open(os.path.join(REPO, "paddle_tpu", "serving",
                           "generation.py")) as f:
        engine = f.read()
    for attr in ("state_slots=", "live_positions=", "pairs_held=",
                 "pairs_routed=", "experts_held_touched=", "scan_chunks="):
        assert attr in engine, attr
    if key == "pattern" and "ragged-dot" in reads and reads != "ragged-dot":
        # the trace's text of such a call (my chip runs, PR 63): the decode
        # program's and a rung's bear the same name, so the seconds are the
        # kernel's in the whole trace, and the reader adds the prefills'
        # bytes to the steps': the prefill's fetch span carries the counts
        for rows in (320, 576, 1024):
            assert re.search(reads, (
                f"%grouped_matmul_ragged-dot.41 = f32[{rows},1024]"
                "{1,0:T(8,128)S(1)} custom-call(s32[]{:T(128)} %get-tuple"))
        spec = _json("metrics", name + ".json")
        assert "same calls" in spec["why"] and "same names" in spec["why"]
        assert '"pad_pairs_left_out", "experts_held_touched")' in engine
    # a sibling's arguments are its own still
    assert manifest.resolved("decode_step_roofline.pool", GRANITE)[1]["fn"] \
        == "ops_bytes_granite_hybrid.decode_step_bytes"
    assert manifest.resolved("moe_held_touched_pct.pool", SOLAR)[1]["per"] \
        == "n_routed_experts"


def test_the_latent_expert_configuration_cuts_what_it_says_and_no_width():
    joined = JOINED[NEMO]
    cfg = _json("configs", joined["config"] + ".json")
    entry, = [c for c in manifest.SPEC["configs"]
              if c["name"] == joined["config"]]
    assert entry["reduced"] == cfg["reduced"] == joined["reduced"]
    assert entry["source"] == cfg["source"]
    assert manifest.SPEC["configs"][-2] == entry
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["expand"], cfg["moe_latent_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["mlp_hidden_act"],
            cfg["layer_norm_epsilon"], cfg["tie_word_embeddings"]) \
        == (4096, 32, 2, 128, 128, 64, 128, 8, 4, 2, 1024, 2688, 5376, 1, 22,
            5, "relu2", 1e-5, False)
    published = cfg["published"]
    assert list(published) == joined["reduced"]
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"], published["num_nextn_predict_layers"]) \
        == (88, 512, 131072, 1)
    pattern = published["hybrid_override_pattern"]
    assert len(pattern) == 88 and set(pattern) == set("M*E")
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (40, 40, 8)
    # the cut: the first eleven letters, one whole period; 32 of the
    # router's 512 held; an eighth of the vocabulary; no drafting module
    assert cfg["hybrid_override_pattern"] == pattern[:11] == "MEMEMEM*EME"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) \
        == (11, 32, 131072 // 8, 0)
    assert cfg["expert_share"]["router_experts"] == 512
    assert cfg["expert_share"]["first"] == 0
    assert cfg["as_run"]["dtype"] == "float32"
    for key in ("assumed", "as_run", "deployment", "check_tolerance",
                "rehearse", "builder", "per_layer_args"):
        assert key in cfg
    assert len(cfg["check_tolerance"]["why"]) > 200
    assert "TO FILL" not in json.dumps(cfg)
    assert os.path.exists(os.path.join(
        BENCH, "builders", cfg["builder"] + ".py"))
    mix = _json("traffic", joined["mix"] + ".json")
    assert mix["driver"] == joined["driver"]
    assert "TO FILL" not in json.dumps(mix)
    assert (mix["engine"]["num_slots"], mix["engine"]["max_seq_len"],
            mix["engine"]["prefill_buckets"], mix["reference_prompts"]) \
        == (128, 1280, sorted(set(joined["rungs"])), [40, 200, 450])
    # the issue's traffic as named; of its ladder, the rung alone went
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.7, "min": 32, "max": 512}
    assert mix["warm_blocks"] * mix["block"] == 128
    assert mix["per_layer_args"]["state_slots_pct.pool"]["scale"] \
        == 100 / mix["engine"]["num_slots"]


@pytest.mark.parametrize("case", ["steps and prefills", "steps alone",
                                  "prefill spans of an earlier commit",
                                  "no such kernel", "no trace"])
def test_a_kernels_roofline_over_every_program_that_calls_it(case):
    """``roofline_kernel_calls``: the steps' bytes and the prefills' over
    the seconds of all the kernel's calls; where the prefill's fetch spans
    lack the counts (the parent's) there is nothing to read, and nothing
    is raised."""
    import sys
    import types

    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import harness
    import ops_bytes_nemotron_h as ob

    cfg = _json("configs", "nemotron3-super-120b-a12b.json")
    reader, args = harness.Cell(NEMO).reader_of("expert_kernel_roofline.pool")
    read = harness.load_module("readers", reader).read

    def span(name, start, **attrs):
        return types.SimpleNamespace(name=name, start=start, attrs=attrs)

    run = types.SimpleNamespace(peaks={"hbm_bytes_per_s": 819e9},
                                trace_t0=100.0, trace_t1=101.0)
    trace = {"to_monotonic": 100.0,
             "modules": {"decode": [(0.0, 0.02), (0.03, 0.05), (0.5, 0.52)],
                         "p256": [(0.06, 0.09), (0.3, 0.33)],
                         "tiny": [(0.4, 0.4001)]},
             "op_seconds": {"grouped_matmul_ragged-dot.40": 0.03,
                            "grouped_matmul_ragged-dot.41": 0.02,
                            "fusion.2": 0.2},
             "op_text": {"grouped_matmul_ragged-dot.40":
                         "%grouped_matmul_ragged-dot.40 = f32[320,2688]",
                         "grouped_matmul_ragged-dot.41":
                         "%grouped_matmul_ragged-dot.41 = f32[576,1024]",
                         "fusion.2": "%fusion"}}
    steps = [span("generation/decode_step", 100.01, pairs_held=850,
                  experts_held_touched=30.0),
             span("generation/decode_step", 100.04, pairs_held=910,
                  experts_held_touched=31.0),
             # (before the traced seconds: not a traced step)
             span("generation/decode_step", 99.0, pairs_held=1,
                  experts_held_touched=1.0)]
    fetches = [span("generation/prefill_fetch", 100.07, pairs_held=1700,
                    pairs_routed=27000, experts_held_touched=32.0),
               span("generation/prefill_fetch", 100.31, pairs_held=1500,
                    pairs_routed=24000, experts_held_touched=31.6)]
    ctx = {"run": run, "cfg": cfg, "trace": trace,
           "trace_spans": steps + fetches}
    step = 3 * ob.expert_kernel_bytes(cfg, 30.5, 880.0, 4)
    if case == "steps and prefills":
        want = step + 2 * ob.expert_kernel_bytes(cfg, 31.8, 1600.0, 4)
    elif case == "steps alone":
        trace["modules"].pop("p256")
        want = step
    elif case == "prefill spans of an earlier commit":
        for s in fetches:
            del s.attrs["experts_held_touched"]
        want = None
    elif case == "no such kernel":
        trace["op_seconds"] = {"fusion.2": 0.2}
        want = None
    else:
        ctx, want = {}, None
    got = read(ctx, **args)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(100 * want / 819e9 / 0.05)
        assert 0 < got < 100


# -- PR 42: the exposed share of collectives, asynchronous ones counted -----

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_reader_" + name,
        os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_dp4_cell_reports_both_exposed_shares():
    by_name = {m["name"]: m for m in manifest.PER_LAYER}
    old = by_name["collective_exposed_pct.train"]
    new = by_name["collective_exposed_all_pct.train"]
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert new[key] == old[key], key
    assert _json("metrics", new["name"] + ".json")["reader"] \
        == "collective_exposed_all"


@pytest.mark.parametrize("ops,exposed_s,want", [
    # the parent's trace: synchronous all-reduces only, nothing to add
    ({"all-reduce.159": 0.0523, "fusion.12": 0.9}, 0.1009, None),
    # the change's: the start / done fusions run alone on the core
    ({"all-reduce.671": 0.0196, "async-collective-start.5": 0.002,
      "async-collective-done.5": 0.0036, "fusion.2960": 0.9}, 0.0228,
     100.0 * (0.0228 + 0.0056) / 1.4467),
    # a compute fusion that carries a step of a collective is compute
    ({"fusion.7": 0.5, "async-collective-done": 0.001}, 0.0,
     100.0 * 0.001 / 1.4467),
])
def test_the_exposed_share_counts_asynchronous_fusions(ops, exposed_s, want):
    read = _reader("collective_exposed_all").read
    trace = {"window_s": 1.4467, "collective_s": exposed_s,
             "collective_exposed_s": exposed_s, "op_seconds": ops}
    got = read({"trace": trace})
    assert got is None if want is None else got == pytest.approx(want)
    assert read({"trace": None}) is None
    # the accepted reader sees the synchronous part alone
    assert _reader("collective_exposed").read({"trace": trace}) == (
        pytest.approx(100.0 * exposed_s / 1.4467) if exposed_s else None)


# -- PR 66: a cell that joined families and brought two entries --------------

def test_the_shortcut_cell_reports_its_groups_and_forty_three_values(
        monkeypatch):
    """Its row: ``deepseek-v2-docqa``'s forty (the 22 of the closed loop,
    the experts' two, a held share's two, the step on its span, four of the
    five of latent pages, the five of chunked prefill, the four of the
    start-up account), the rows a touched held expert multiplies and its
    own two.  No group of slot state, of the paged decode kernel or of a
    whole-prompt prefill."""
    groups = _groups_less_not_run()
    groups["experts, a share held"] = groups["experts, a share held"] \
        + [LATENT_EXPERTS[1]]
    monkeypatch.setattr(manifest, "GROUPS", groups)
    assert manifest.check_cell(LONGCAT, LONGCAT_ROW) == LONGCAT_ROW[2] == 43
    for name in manifest.entries_of(LONGCAT):
        assert manifest.BY_NAME[name]["workloads"][-1] == LONGCAT, name
    assert LONGCAT not in manifest.TABLE       # (a ``benchmark`` PR's to add)
    zero, branch = (manifest.BY_NAME[n] for n in IDENTITY)
    assert zero == {
        "name": IDENTITY[0], "unit": "%", "better": "higher",
        "source": "program_span", "layer": "expert path",
        "moves": "served_tokens_per_s", "workloads": [LONGCAT]}
    assert branch == {
        "name": IDENTITY[1], "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "served_tokens_per_s", "workloads": [LONGCAT]}
    assert set(manifest.entries_of(LONGCAT)) \
        == set(manifest.entries_of(DSV2)) | set(IDENTITY) \
        | {LATENT_EXPERTS[1]}
    for name in (DSV2_NOT_RUN, "delta_step_roofline.pool",
                 "paged_kernel_roofline.pool", "prefill_roofline.pool",
                 "ssm_step_roofline.pool", "expert_kernel_roofline.pool",
                 manifest.TOUCHED):
        assert LONGCAT not in manifest.BY_NAME[name]["workloads"], name
    manifest.check_cell_loads(LONGCAT)


@pytest.mark.parametrize("name,reader,key,reads", [
    ("decode_step_roofline.pool", "roofline_span", "attrs",
     ["experts_held_touched", "latent_positions"]),
    ("decode_step_roofline.pool", "roofline_span", "fn",
     "ops_bytes_longcat_flash.decode_step_bytes"),
    ("mla_decode_bytes_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_longcat_flash.mla_decode_bytes"),
    ("mla_decode_flops_roofline.pool", "roofline_kernel", "fn",
     "ops_bytes_longcat_flash.mla_decode_flops"),
    ("mla_kernel_share_pct.pool", "trace_op_share", "pattern",
     "^%?mla_(decode|chunk)_attention"),
    ("prefill_chunk_roofline.pool", "roofline_chunks", "fn",
     "ops_bytes_longcat_flash.chunk_flops"),
    ("chunk_attention_roofline.pool", "roofline_kernel_prefill", "fn",
     "ops_bytes_longcat_flash.chunk_attention_flops"),
    ("chunk_attention_roofline.pool", "roofline_kernel_prefill", "pattern",
     "^%?mla_chunk_attention"),
    ("chunk_attention_share_pct.pool", "trace_op_share", "pattern",
     "^%?mla_chunk_attention"),
    ("moe_held_touched_pct.pool", "span_attr_mean", "per",
     "n_routed_experts"),
    ("moe_rows_per_held_expert.pool", "span_attr_ratio", "scale", 0.25),
    ("latent_fill_pct.pool", "span_attr_mean", "scale", 100 / (32 * 1280)),
    ("moe_zero_pairs_pct.pool", "span_attr_ratio", "num", "pairs_zero"),
    ("moe_zero_pairs_pct.pool", "span_attr_ratio", "den", ["pairs_routed"]),
    ("moe_zero_pairs_pct.pool", "span_attr_ratio", "span",
     "generation/decode_step"),
    ("shortcut_branch_share_pct.pool", "trace_op_share", "pattern",
     "^%?while[.\\d]* = \\(.*f32\\[8,6144,4096\\]|[fs]32\\[\\d+,768\\]"),
])
def test_the_shortcut_cells_arguments_come_from_its_own_files(
        name, reader, key, reads):
    got_reader, args = manifest.check_arguments(name, LONGCAT)
    assert got_reader == reader and args[key] == reads


def test_the_shortcut_configuration_cuts_what_it_says_and_no_width():
    joined = JOINED[LONGCAT]
    cfg = _json("configs", joined["config"] + ".json")
    entry = manifest.SPEC["configs"][-1]
    assert entry["name"] == joined["config"]
    assert entry["reduced"] == cfg["reduced"] == joined["reduced"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/" \
           "blob/main/config.json"
    # every published width, the router's 768 outputs and its 12 picks
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["qk_nope_head_dim"], cfg["moe_topk"],
            cfg["zero_expert_num"], cfg["routed_scaling_factor"],
            cfg["rms_norm_eps"], cfg["rope_theta"],
            cfg["max_position_embeddings"]) \
        == (6144, 12288, 2048, 512, 1536, 64, 128, 128, 12, 256, 6, 1e-5,
            10000000, 131072)
    assert cfg["expert_share"]["router_experts"] == 768 \
        == cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]
    assert cfg["published"] == {"num_layers": 28, "num_attention_heads": 64,
                                "n_routed_experts": 512,
                                "vocab_size": 131072}
    assert [cfg[k] for k in joined["reduced"]] == [4, 8, 8, 16384]
    # the floors of the guide, and the deployment's 64 chips
    assert cfg["vocab_size"] * 8 == 131072 \
        and cfg["num_attention_heads"] * 8 == 64 \
        and cfg["n_routed_experts"] * 64 == 512
    for key in ("assumed", "as_run", "deployment", "check_tolerance",
                "rehearse", "builder", "expert_share", "per_layer_args"):
        assert key in cfg
    assert len(cfg["check_tolerance"]["why"]) > 200
    assert "64" in cfg["deployment"]
    assert os.path.exists(os.path.join(
        BENCH, "builders", cfg["builder"] + ".py"))


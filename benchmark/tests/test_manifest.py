"""``BENCHMARK.json``'s ``per_layer`` against the files under
``benchmark/metrics`` and the cells' own files, and what each cell
reports.  Pure JSON and text: no JAX is imported and no engine started
(``harness`` imports numpy only), so the file also runs among the
repository's tier-1 tests as it stands (``tests/test_benchmark_manifest.py``
collects every ``test_*`` here).

    python -m pytest benchmark/tests/test_manifest.py -q

``per_layer`` holds one entry a family, not one a cell (PR 38 for what
every closed-loop cell reads alike, PR 55 for what they read with
arguments of their own): an entry's data file holds the reader and the
arguments every cell shares, and what differs by cell is in the cell's
own configuration or mix file under ``per_layer_args`` (``harness.py``'s
header).  A later cell appends its name to the ``workloads`` of the
entries its mechanisms report (``GROUPS``) and brings its arguments in its
own new files; ``TABLE`` pins, a cell, the groups it reports and the count
on its newest ledger line, for this file, the cells' own test files and
tier-1 alike.
"""
import ast
import functools
import json
import os
import re
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (numpy only)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
ENTRIES = SPEC["per_layer"]
BY_NAME = {m["name"]: m for m in ENTRIES}

# -- the one table of pins ---------------------------------------------------

# every cell, moving ``setup_s`` (PR 53); all other entries move the
# cell's own gate
SETUP = ["setup_import_s.setup", "setup_trace_lower_s.setup",
         "setup_backend_s.setup", "setup_unaccounted_s.setup"]
# shared by every closed-loop serving cell: 22
POOL = ["ttft_closed_p50_ms"] + [f + ".pool" for f in (
    "decode_step_mean_ms", "prefill_mean_ms", "compiles_in_window",
    "device_idle_pct", "hbm_peak_gb", "iter_host_ms",
    "executor_run_host_ms", "decode_feeds_ms", "book_tokens_ms",
    "queue_wait_p50_ms", "decode_ahead_pct", "idle_decode_host_pct",
    "idle_prefill_host_pct", "idle_unattributed_pct",
    "device_starved_pct", "device_idle_known_pct", "device_idle_slack_pct",
    "iter_offcpu_ms", "iter_unnamed_ms", "stream_cpu_pct", "pass_max_ms")]
TOUCHED = "moe_experts_touched_pct.pool"
# what a cell reports, by the mechanism that gives a reader something to
# read; a cell's row in ``TABLE`` is a list of these
GROUPS = {
    "train": [f + ".train" for f in (
        "step_mean_ms", "input_wait_ms", "compiles_in_window", "mfu_pct",
        "device_idle_pct", "hbm_peak_gb")],
    "train, one chip": ["attention_share_pct.train"],
    "train, a mesh": ["collective_exposed_pct.train",
                      "collective_exposed_all_pct.train"],
    "open loop": [
        "loadgen_late_p99_ms", "front_ttft_overhead_ms", "ttft_open_p50_ms",
        "slot_occupancy_pct.chat", "decode_pool_fill_pct.chat",
        "ttft_p95_ms.chat", "itl_p50_ms.chat", "itl_p95_ms.chat",
        "stalled_gap_share_pct.chat", "decode_step_roofline.chat",
        "idle_no_work_pct.chat"] + [f + ".chat" for f in (
            "prefill_mean_ms", "decode_step_mean_ms", "compiles_in_window",
            "device_idle_pct", "hbm_peak_gb", "iter_host_ms",
            "decode_feeds_ms", "book_tokens_ms", "queue_wait_p50_ms",
            "executor_run_host_ms", "idle_decode_host_pct",
            "idle_prefill_host_pct", "idle_unattributed_pct",
            "decode_ahead_pct", "device_starved_pct",
            "device_idle_known_pct", "device_idle_slack_pct",
            "iter_offcpu_ms", "iter_unnamed_ms", "stream_cpu_pct",
            "pass_max_ms")],
    "closed loop": POOL,
    # a router over experts: its load and the grouped matmul's share
    "experts": ["moe_expert_load_max_over_mean.pool",
                "expert_matmul_share_pct.pool"],
    # ... all of them held on this chip
    "experts, all held": ["attention_kernel_share_pct.pool", TOUCHED],
    # ... one chip's share of them held (``moe_routed_tokens(held_first=)``)
    "experts, a share held": ["moe_pairs_held_pct.pool",
                              "moe_held_touched_pct.pool"],
    # whole-prompt prefill programs, one a rung
    "whole-prompt prefill": ["prefill_roofline.pool"],
    # a decode step that writes what it did onto its span
    "step on its span": ["decode_step_roofline.pool"],
    "paged decode kernel": ["paged_kernel_roofline.pool"],
    # slot state that is not pages (convolution, delta rule)
    "slot state": ["state_slots_pct.pool"],
    "delta rule": ["delta_step_roofline.pool", "delta_chunk_roofline.pool",
                   "delta_kernel_share_pct.pool", "scan_pad_pct.pool"],
    "latent pages": ["mla_decode_bytes_roofline.pool",
                     "mla_decode_flops_roofline.pool",
                     "mla_prefill_roofline.pool", "mla_kernel_share_pct.pool",
                     "latent_fill_pct.pool"],
    "window page pool": ["kv_window_pages_saved_pct.pool"],
    "chunked prefill": ["prefill_chunk_roofline.pool",
                        "chunk_attention_roofline.pool",
                        "chunk_attention_share_pct.pool",
                        "chunk_share_of_busy_pct.pool", "chunk_pad_pct.pool"],
    "chunked prefill over window pages": [
        "window_released_in_prefill_pct.pool"],
    # read with a reader of the cell's own (left as they were)
    "routed step": ["decode_step_roofline.mix"],
    "block diffusion": ["tokens_per_pass.blk", "commit_pass_share_pct.blk",
                        "block_step_roofline.blk"],
    # what one later cell alone reports (PR 59, PR 63, PR 66; tier-1 puts
    # the same three on the collected module, which is the same again)
    "state space": ["ssm_step_roofline.pool", "ssm_chunk_roofline.pool",
                    "ssm_kernel_share_pct.pool"],
    "experts in a latent row": ["expert_kernel_roofline.pool",
                                "moe_rows_per_held_expert.pool"],
    "identity experts": ["moe_zero_pairs_pct.pool",
                         "shortcut_branch_share_pct.pool"],
}
# the entries whose reader takes arguments from the cell's own files
FAMILIES = [name for group in (
    "experts, all held", "experts, a share held", "whole-prompt prefill",
    "step on its span", "paged decode kernel", "slot state", "delta rule",
    "latent pages", "window page pool", "chunked prefill",
    "chunked prefill over window pages") for name in GROUPS[group]
    if name != "attention_kernel_share_pct.pool"]
_SERVE = ["closed loop", "experts"]
# cell -> (the end-to-end metric its entries move, its groups, the
# per-layer values on its newest ledger line (ledger, PR 54): the groups'
# entries and the four of ``SETUP``)
TABLE = {
    "bert-base-seq512": ("train_tokens_per_s_per_chip",
                         ["train", "train, one chip"], 11),
    "mistral7b-chat": ("itl_p99_ms", ["open loop"], 36),
    "mistral7b-longprompt": ("served_tokens_per_s",
                             ["closed loop", "whole-prompt prefill"], 27),
    "bert-base-seq512-dp4": ("train_tokens_per_s_per_chip",
                             ["train", "train, a mesh"], 12),
    "smallthinker21b-mixedlen": ("served_tokens_per_s", _SERVE + [
        "experts, all held", "window page pool", "whole-prompt prefill",
        "routed step"], 33),
    "sdar30b-blockgen": ("served_tokens_per_s", _SERVE + [
        "experts, all held", "whole-prompt prefill", "block diffusion"], 34),
    "lfm2-24b-longanswer": ("served_tokens_per_s", _SERVE + [
        "experts, all held", "whole-prompt prefill", "step on its span",
        "paged decode kernel", "slot state"], 34),
    "olmo-hybrid7b-longdoc": ("served_tokens_per_s", [
        "closed loop", "whole-prompt prefill", "step on its span",
        "paged decode kernel", "slot state", "delta rule"], 34),
    "solar-open2-agentturns": ("served_tokens_per_s", _SERVE + [
        "experts, a share held", "whole-prompt prefill", "step on its span",
        "paged decode kernel", "slot state", "delta rule"], 38),
    "gigachat35-ragturns": ("served_tokens_per_s", _SERVE + [
        "experts, a share held", "whole-prompt prefill", "step on its span",
        "slot state", "delta rule", "latent pages"], 42),
    "command-a-plus-ragdocs": ("served_tokens_per_s", _SERVE + [
        "experts, a share held", "step on its span", "paged decode kernel",
        "window page pool", "chunked prefill",
        "chunked prefill over window pages"], 39),
}


def entries_of(cell):
    """The names of the entries that list ``cell``, in the manifest's
    order (``harness.Cell.metrics`` selects by the same membership)."""
    return [m["name"] for m in ENTRIES if cell in m["workloads"]]


def check_cell(cell, row=None):
    """``cell`` reports the entries of its groups and the start-up
    account, no other; all but that account move its gate.  Returns the
    number of values.  A later cell's own test hands in its ``row``, as
    ``TABLE`` has them."""
    gate, listed, _ = row or TABLE[cell]
    want = [name for g in listed for name in GROUPS[g]] + SETUP
    assert len(set(want)) == len(want)
    got = entries_of(cell)
    assert sorted(got) == sorted(want), set(got) ^ set(want)
    for name in got:
        assert BY_NAME[name]["moves"] == (
            "setup_s" if name in SETUP else gate), name
    moved, = [m for m in SPEC["end_to_end"] if m["name"] == gate]
    assert cell in moved.get("workloads", CELLS)
    return len(got)


# -- what a reader takes, from its source -------------------------------------

def signature(reader):
    """``(required, allowed)`` argument names of ``readers/<reader>.py``'s
    ``read`` beside ``ctx``, from its text: nothing is imported."""
    with open(os.path.join(BENCH, "readers", reader + ".py")) as f:
        tree = ast.parse(f.read())
    read, = [n for n in tree.body
             if isinstance(n, ast.FunctionDef) and n.name == "read"]
    names = [a.arg for a in read.args.args]
    assert names[0] == "ctx" and not read.args.kwonlyargs
    bare = len(names) - len(read.args.defaults)
    return set(names[1:bare]), set(names[1:])


_cell = functools.lru_cache(maxsize=None)(harness.Cell)


def resolved(name, cell):
    """``(reader, arguments)`` as ``harness.Run.read_per_layer`` hands
    them over in ``cell``, through the harness's own function."""
    return _cell(cell).reader_of(name)


def check_arguments(name, cell):
    """The cell's files give entry ``name``'s reader every argument it
    needs and none it does not take; a function it names is defined, a
    configuration key it divides by is a number."""
    reader, args = resolved(name, cell)
    required, allowed = signature(reader)
    assert required <= set(args) <= allowed, (
        f"{name} in {cell}: {reader}.read needs {sorted(required)}, the "
        f"files give {sorted(args)}: per_layer_args[{name!r}] of the "
        f"cell's configuration or mix")
    if "fn" in args:
        module, _, fn = args["fn"].rpartition(".")
        with open(os.path.join(BENCH, module + ".py")) as f:
            assert f"def {fn}(" in f.read(), args["fn"]
    if reader == "span_attr_mean" and args.get("per") is not None:
        assert isinstance(_cell(cell).cfg[args["per"]], (int, float)), args
    return reader, args


def check_cell_loads(cell):
    """Every reader of ``cell`` loads and every function its arguments
    name resolves (numpy is imported, no JAX); a share of a roofline is a
    percentage taken from the device's trace."""
    for name in entries_of(cell):
        reader, args = check_arguments(name, cell)
        harness.load_module("readers", reader)
        if "fn" in args:
            assert callable(harness.resolve(args["fn"])), args["fn"]
        if "_roofline." in name:
            assert (BY_NAME[name]["unit"], BY_NAME[name]["source"]) \
                == ("%", "device_trace"), name


# -- the checks ----------------------------------------------------------------

def test_there_is_room_for_the_next_cells_entries():
    """79 of 128 when PR 38 had folded what the closed-loop cells read
    alike, 127 at PR 54, 97 when PR 55 had folded what they read with
    arguments of their own; later cells add.  No entry's name holds a
    configuration's or a cell's short name, or a kernel's by a model."""
    assert len(ENTRIES) <= 128
    names = [m["name"] for m in ENTRIES]
    assert len(set(names)) == len(names)
    for tag in (".lfm", ".olmo", ".solar", ".giga", ".cmda", ".long",
                "gdn_", "kda_"):
        assert not [n for n in names if tag in n], tag
    assert (len(POOL), len(SETUP), len(FAMILIES)) == (22, 4, 23)
    # every entry is in one group, or of the start-up account
    assert sorted(n for g in GROUPS.values() for n in g) \
        == sorted(n for n in names if n not in SETUP)


def test_every_entry_lists_the_cells_that_report_it():
    """An entry without ``workloads`` would be reported by any later cell
    that reports the metric it moves; the cells stand in the order they
    came."""
    moved = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in ENTRIES:
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) <= set(CELLS), m
        assert len(set(m["workloads"])) == len(m["workloads"]), m
        assert m["workloads"] == sorted(m["workloads"], key=CELLS.index), m
        for cell in m["workloads"]:
            assert cell in moved[m["moves"]].get("workloads", CELLS), m


def test_data_files_are_exactly_the_entries():
    """One file an entry, and beside them, unread by the harness, the
    parent's files of the ``AT_PR54`` names that tier-1 still opens by
    path."""
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH, "metrics")) if f.endswith(".json")}
    assert files == set(BY_NAME) | set(KEPT_FOR_TIER1)
    assert len(os.listdir(os.path.join(BENCH, "metrics"))) == len(files)
    assert not set(BY_NAME) & set(KEPT_FOR_TIER1)


def test_every_data_file_agrees_with_its_entry_and_names_a_reader():
    for m in ENTRIES:
        spec = harness.load_json("metrics", m["name"] + ".json")
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), m["name"]
        if m["name"] in FAMILIES:
            # says what it measures and where a cell's arguments come from
            assert len(spec["why"]) > 60, m["name"]


def test_no_entry_is_a_copy_of_another():
    """One entry a family: no two entries that move the same metric have
    the same reader and the same shared arguments.  A copy a cell is how
    ``per_layer`` filled up twice (128 at PR 37, 127 at PR 54)."""
    seen = {}
    for m in ENTRIES:
        spec = harness.load_json("metrics", m["name"] + ".json")
        key = (m["moves"], spec["reader"],
               json.dumps(spec.get("args", {}), sort_keys=True))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_the_cells_own_arguments_name_entries_that_list_them():
    """A ``per_layer_args`` key that no entry of the cell answers to is a
    misspelt name: its arguments would reach no reader."""
    for w in SPEC["workloads"]:
        for kind, name in (("configs", w["config"]),
                           ("traffic", w["traffic"])):
            own = harness.load_json(kind, name + ".json").get(
                harness.ARGS_KEY, {})
            users = [c["name"] for c in SPEC["workloads"]
                     if c["config" if kind == "configs" else "traffic"]
                     == name]
            for entry in own:
                assert set(users) & set(BY_NAME[entry]["workloads"]), (
                    name, entry)


@pytest.mark.parametrize("cell", list(TABLE))
def test_a_cell_reports_its_groups_and_as_many_values_as_its_ledger_line(
        cell):
    assert check_cell(cell) == TABLE[cell][2]


def test_the_table_lists_every_cell_of_this_pr():
    assert list(TABLE) == CELLS[:len(TABLE)] and len(TABLE) == 11


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_s_files_give_every_reader_its_arguments(cell):
    """Every (entry, cell) the harness will read, resolved as the harness
    resolves it: a cell that joined a family and brought no ``fn`` fails
    here, not as a silent "nothing to read" on the chip."""
    for name in entries_of(cell):
        check_arguments(name, cell)


with open(os.path.join(ROOT, "paddle_tpu", "serving", "generation.py")) as _f:
    _ENGINE = _f.read()
_KERNELS = []
for _name in os.listdir(os.path.join(ROOT, "paddle_tpu", "ops", "pallas")):
    if _name.endswith(".py"):
        with open(os.path.join(ROOT, "paddle_tpu", "ops", "pallas",
                               _name)) as _f:
            _KERNELS += re.findall(r'name="([\w-]+)"', _f.read())


@pytest.mark.parametrize("name,cell", [
    (name, cell) for name in FAMILIES for cell in BY_NAME[name]["workloads"]],
    ids=lambda v: v)
def test_a_family_hands_each_cell_what_the_program_makes(name, cell):
    """What a family's reader reads in a cell is in the program: a span
    attribute by its name in ``serving/generation.py``, a kernel by the
    ``name=`` of its ``pallas_call``, a span by its name."""
    reader, args = check_arguments(name, cell)
    attrs = list(args.get("attrs", ())) + list(args.get("den", ())) \
        + [args[k] for k in ("attr", "num") if k in args]
    # attributes a reader takes by a fixed name
    attrs += {"kv_pages_saved": ["pages_live_full", "pages_live_window"],
              "roofline": ["tokens"],
              "roofline_chunks": ["tokens", "base"]}.get(reader, [])
    for attr in attrs:
        assert re.search(r"\b%s\b" % re.escape(attr), _ENGINE), attr
    if "pattern" in args:
        assert [k for k in _KERNELS if re.search(args["pattern"], k)], args
    span = args.get("span")
    if span is not None:
        assert '"%s"' % span in _ENGINE, span
    if reader in ("roofline_span", "roofline_kernel", "roofline_kernel_prefill",
                  "roofline", "roofline_chunks"):
        assert args["peak"] in harness.load_json("peaks.json")["TPU v5 lite"]
    assert attrs or "pattern" in args or reader == "module_busy_share"


# -- nothing a cell reports changed but its name (PR 55) -----------------------

with open(os.path.join(BENCH, "tests", "fixtures",
                       "per_layer_at_pr54.json")) as _f:
    AT_PR54 = json.load(_f)["rows"]


@pytest.mark.parametrize("cell", list(TABLE))
def test_a_cell_reads_what_it_read_at_pr54_under_the_new_names(cell):
    """The fixture is the parent's ``per_layer`` (127 entries) with each
    entry's reader and arguments as its data file gave them.  Every row
    that lists the cell answers to one entry of the cell now, one to one,
    with the same reader and the same resolved arguments, and the same
    unit, direction, source, layer and ``moves``."""
    rows = [r for r in AT_PR54 if cell in r["cells"]]
    assert sorted(r["new"] for r in rows) == sorted(entries_of(cell))
    for r in rows:
        assert resolved(r["new"], cell) == (r["reader"], r["args"]), r["old"]
    assert len(rows) == TABLE[cell][2]


def test_the_fixture_is_the_whole_parent_and_52_names_went():
    assert len(AT_PR54) == 127
    assert len({r["old"] for r in AT_PR54}) == 127
    went = {r["old"] for r in AT_PR54} - set(BY_NAME)
    came = set(BY_NAME) - {r["old"] for r in AT_PR54}
    # (22 came and 97 stood at PR 55; PR 59, 63 and 66 brought 3 + 2 + 2)
    assert (len(went), len(came), len(ENTRIES)) == (52, 29, 104)
    for r in AT_PR54:
        assert set(r["cells"]) <= set(BY_NAME[r["new"]]["workloads"]), r


def test_a_later_cell_joins_four_families_with_two_files_of_its_own(
        tmp_path, monkeypatch):
    """A made-up twelfth cell: its name appended to four entries'
    ``workloads`` in a copy of the manifest, its arguments in a new
    configuration file and a new mix file; no file that exists is edited,
    and ``harness.Cell`` hands its readers those arguments."""
    import traffic

    bench = tmp_path / "benchmark"
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, kind), bench / kind)
    before = {p: p.read_bytes() for p in bench.rglob("*.json")}
    joins = ["decode_step_roofline.pool", "moe_pairs_held_pct.pool",
             "state_slots_pct.pool", "latent_fill_pct.pool"]
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "made-up-cell", "config": "made-up",
                              "traffic": "made-up-pool", "chips": 1,
                              "why": "a test's"})
    for m in spec["per_layer"]:
        if m["name"] in joins or m["name"] in SETUP:
            m["workloads"].append("made-up-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "made-up.json").write_text(json.dumps({
        "hidden_size": 8, harness.ARGS_KEY: {"decode_step_roofline.pool": {
            "fn": "ops_bytes_made_up.decode_step_bytes",
            "attrs": ["latent_positions"]}}}))
    (bench / "traffic" / "made-up-pool.json").write_text(json.dumps({
        "driver": "serve", "engine": {"num_slots": 8, "max_seq_len": 1000},
        harness.ARGS_KEY: {"state_slots_pct.pool": {"scale": 12.5},
                           "latent_fill_pct.pool": {"scale": 0.0125}}}))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "HERE", str(bench))
    monkeypatch.setattr(traffic, "HERE", str(bench))
    cell = harness.Cell("made-up-cell")
    assert [m["name"] for m in cell.metrics("per_layer")] == [
        n for n in BY_NAME if n in joins] + SETUP
    assert cell.reader_of("decode_step_roofline.pool") == ("roofline_span", {
        "peak": "hbm_bytes_per_s",
        "fn": "ops_bytes_made_up.decode_step_bytes",
        "attrs": ["latent_positions"]})
    assert cell.reader_of("moe_pairs_held_pct.pool") \
        == harness.Cell("solar-open2-agentturns").reader_of(
            "moe_pairs_held_pct.pool")
    assert cell.reader_of("state_slots_pct.pool")[1]["scale"] == 12.5
    assert cell.reader_of("latent_fill_pct.pool") == ("span_attr_mean", {
        "span": "generation/decode_step", "attr": "latent_positions",
        "scale": 0.0125})
    # an older cell's arguments are its own still
    assert harness.Cell("gigachat35-ragturns").reader_of(
        "latent_fill_pct.pool")[1]["scale"] == 100 / (32 * 2816)
    assert {p: p.read_bytes() for p in before} == before
    # and a cell that joins without its ``fn`` is caught before any run
    (bench / "configs" / "made-up.json").write_text(json.dumps({}))
    reader, args = harness.Cell("made-up-cell").reader_of(
        "decode_step_roofline.pool")
    assert not signature(reader)[0] <= set(args)


# -- what ``tests/test_benchmark_manifest.py`` still reads ------------------------
#
# That tier-1 file pins the four cells of PR 41-51 by the names their
# entries had at PR 54 and opens ``benchmark/metrics/<that name>.json``; a
# ``benchmark`` PR may not edit it, and a PR that may edit it may not edit
# this file.  Until it reads ``TABLE`` (``PERF.md`` section 7, "PR 55
# left", first), the manifest is handed to it under those names: each row
# of ``AT_PR54`` as the entry that reports its value NOW, with the row's
# old name and cells.  The 43 files it opens are the parent's, unread by
# the harness (``test_the_files_kept_for_tier_1_are_the_parents``).

KEPT_FOR_TIER1 = sorted(
    r["old"] for r in AT_PR54 if r["old"] != r["new"]
    and os.path.exists(os.path.join(BENCH, "metrics", r["old"] + ".json")))
PER_LAYER = [dict(BY_NAME[r["new"]], name=r["old"], workloads=r["cells"])
             for r in AT_PR54]
# patched there (``+= 1`` for PR 42's entry); no check here reads it
REPORTS = {"bert-base-seq512-dp4": 7}


def reported_by(cell):
    """``(own, shared)`` names of ``cell``'s entries AS AT PR 54."""
    own = [m["name"] for m in PER_LAYER if m.get("workloads") == [cell]]
    shared = [m["name"] for m in PER_LAYER
              if cell in m.get("workloads", ()) and len(m["workloads"]) > 1]
    return own, shared


def test_the_files_kept_for_tier_1_are_the_parents():
    assert len(KEPT_FOR_TIER1) == 43
    by_old = {r["old"]: r for r in AT_PR54}
    for old in KEPT_FOR_TIER1:
        spec = harness.load_json("metrics", old + ".json")
        row = by_old[old]
        assert (spec["reader"], spec["args"]) == (row["reader"], row["args"])
        for cell in row["cells"]:
            assert resolved(row["new"], cell) == (row["reader"], row["args"])


# -- what a serving program ran in (PR 67) ---------------------------------------
#
# ``as_run_checks.py`` holds the checks of the two admitted dtypes, the
# observation and the readers' item sizes; they are taken in here as this
# file's own so that tier-1, which collects this file's checks by name
# (``tests/test_benchmark_manifest.py``), collects them too.  JAX is
# imported inside those that build an engine, not by this module.

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import as_run_checks  # noqa: E402

globals().update({name: fn for name, fn in vars(as_run_checks).items()
                  if name.startswith("test_as_run_")})

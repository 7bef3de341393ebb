"""``BENCHMARK.json``'s ``per_layer`` against the files under
``benchmark/metrics``, and what each cell reports.  Pure JSON: no JAX is
imported and no engine started, so the file can also run among the
repository's tier-1 tests as it stands.

    python -m pytest benchmark/tests/test_manifest.py -q

Since PR 38 ``per_layer`` holds one entry a family, not one a cell: a
family that every closed-loop serving cell reads with the same reader
and arguments is one ``<family>.pool`` entry whose ``workloads`` lists
those cells.  A later closed-loop cell appends its name to the ``.pool``
entries it reports (``POOL``, and ``POOL_EXPERTS`` where it routes over
experts) and adds only the entries that are its own.
"""
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
PER_LAYER = SPEC["per_layer"]

# the per-layer values on each cell's newest ledger line before the merge
# (ledger, PR 37); the merge renamed values and took none away
REPORTS = {"bert-base-seq512": 7, "bert-base-seq512-dp4": 7,
           "mistral7b-chat": 32, "mistral7b-longprompt": 23,
           "smallthinker21b-mixedlen": 29, "sdar30b-blockgen": 30,
           "lfm2-24b-longanswer": 30}

# shared by every closed-loop serving cell: 22
POOL = ["ttft_closed_p50_ms"] + [f + ".pool" for f in (
    "decode_step_mean_ms", "prefill_mean_ms", "compiles_in_window",
    "device_idle_pct", "hbm_peak_gb", "iter_host_ms",
    "executor_run_host_ms", "decode_feeds_ms", "book_tokens_ms",
    "queue_wait_p50_ms", "decode_ahead_pct", "idle_decode_host_pct",
    "idle_prefill_host_pct", "idle_unattributed_pct",
    "device_starved_pct", "device_idle_known_pct", "device_idle_slack_pct",
    "iter_offcpu_ms", "iter_unnamed_ms", "stream_cpu_pct", "pass_max_ms")]
# and by those that route over experts: 3, and a 4th where the
# configuration counts its experts under ``num_experts``
POOL_EXPERTS = [f + ".pool" for f in (
    "moe_expert_load_max_over_mean", "expert_matmul_share_pct",
    "attention_kernel_share_pct")]
TOUCHED = "moe_experts_touched_pct.pool"

# a closed-loop cell's own entries (``workloads == [cell]``) and the
# shared ones that must list it
CLOSED_LOOP = {
    "mistral7b-longprompt": (["prefill_roofline.long"], POOL),
    "smallthinker21b-mixedlen": (
        ["moe_experts_touched_pct.mix", "kv_window_pages_saved_pct.mix",
         "decode_step_roofline.mix", "prefill_roofline.mix"],
        POOL + POOL_EXPERTS),
    "sdar30b-blockgen": (
        ["tokens_per_pass.blk", "commit_pass_share_pct.blk",
         "block_step_roofline.blk", "prefill_roofline.blk"],
        POOL + POOL_EXPERTS + [TOUCHED]),
    "lfm2-24b-longanswer": (
        ["decode_step_roofline.lfm", "prefill_roofline.lfm",
         "paged_kernel_roofline.lfm", "state_slots_pct.lfm"],
        POOL + POOL_EXPERTS + [TOUCHED]),
}


def reported_by(cell):
    """The names of ``cell``'s per-layer entries, split into its own and
    those it shares with another cell (``harness.Cell.metrics`` selects
    by the same membership)."""
    own = [m["name"] for m in PER_LAYER if m.get("workloads") == [cell]]
    shared = [m["name"] for m in PER_LAYER
              if cell in m.get("workloads", ()) and len(m["workloads"]) > 1]
    return own, shared


def check_closed_loop_cell(cell):
    """``cell`` reports its own entries by name and the shared ones that
    must list it, each moving ``served_tokens_per_s``; the two counts."""
    own, shared = reported_by(cell)
    want_own, want_shared = CLOSED_LOOP[cell]
    assert sorted(own) == sorted(want_own)
    assert sorted(shared) == sorted(want_shared)
    for m in PER_LAYER:
        if m["name"] in own + shared:
            assert m["moves"] == "served_tokens_per_s", m
    return len(own), len(shared)


def test_there_is_room_for_the_next_cells_entries():
    """79 of 128 when PR 38 had folded the copies; later cells add."""
    assert len(PER_LAYER) <= 128
    names = [m["name"] for m in PER_LAYER]
    assert len(set(names)) == len(names)
    assert (len(POOL), len(POOL + POOL_EXPERTS + [TOUCHED])) == (22, 26)


def test_every_entry_lists_the_cells_that_report_it():
    """An entry without ``workloads`` would be reported by any later cell
    that reports the metric it moves."""
    moved = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in PER_LAYER:
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) <= set(CELLS), m
        assert len(set(m["workloads"])) == len(m["workloads"]), m
        for cell in m["workloads"]:
            assert cell in moved[m["moves"]].get("workloads", CELLS), m


def test_data_files_are_exactly_the_entries():
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH, "metrics")) if f.endswith(".json")}
    assert files == {m["name"] for m in PER_LAYER}
    assert len(os.listdir(os.path.join(BENCH, "metrics"))) == len(files)


def test_every_data_file_agrees_with_its_entry_and_names_a_reader():
    for m in PER_LAYER:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), m["name"]


def test_no_per_cell_copy_of_a_shared_family_is_left():
    """A ``.pool`` family has no ``.long`` / ``.mix`` / ``.blk`` / ``.lfm``
    entry beside it, but for ``moe_experts_touched_pct.mix``, whose
    divisor is another configuration key."""
    families = {n[:-len(".pool")] for n in POOL + POOL_EXPERTS + [TOUCHED]
                if n.endswith(".pool")}
    beside = [m["name"] for m in PER_LAYER
              if m["name"].rsplit(".", 1)[0] in families
              and m["name"].rsplit(".", 1)[1] in ("long", "mix", "blk",
                                                   "lfm")]
    assert beside == ["moe_experts_touched_pct.mix"]


@pytest.mark.parametrize("cell", sorted(REPORTS))
def test_a_cell_reports_as_many_values_as_before_the_merge(cell):
    own, shared = reported_by(cell)
    assert len(own) + len(shared) == REPORTS[cell]


@pytest.mark.parametrize("cell", sorted(CLOSED_LOOP))
def test_a_closed_loop_cell_reports_its_own_and_the_shared_entries(cell):
    n_own, n_shared = check_closed_loop_cell(cell)
    assert n_own + n_shared == REPORTS[cell]

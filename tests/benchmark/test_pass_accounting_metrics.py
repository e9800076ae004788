"""The per-layer metrics that read the pass's own account (PR 24), each on
an ``obs`` written by hand.  Nothing here times the system under test."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402

# the cells the entries were written for; a later cell may list them too
CELLS = ["full.audit-sweep", "psp.audit-sweep"]
NEW = ["list.busy_s_per_pass", "audit_schedule.critical_occupancy",
       "audit_schedule.host_blocked_share", "pack_h2d.launch_s_per_pass",
       "fold_render.render_s_per_pass", "python_gc.full_span_s_per_pass",
       "audit_schedule.idle_unlabelled_share"]

# the idle gaps of the traced full.audit-sweep run, as PERF_LEDGER.jsonl has
# them for PR 22 (the ledger writes the '+' between two threads' spans '_')
LEDGER_PR22_FULL_GAPS = [
    ["audit.sweep", 0.849373278],
    ["audit.sweep_device.sweep_dispatch", 0.745448997],
    ["audit.sweep", 0.6991259],
    ["audit.sweep_ops.flatten.columnize", 0.626674807],
    ["audit.sweep_ops.flatten.columnize", 0.56146963],
    ["audit.sweep_pipeline.stage.fold_render", 0.482618556],
    ["audit.sweep_ops.flatten.columnize", 0.447944177],
    ["audit.sweep_ops.flatten.columnize", 0.445406579],
    ["audit.sweep_device.sweep_dispatch_pipeline.stage.fold_render",
     0.424904349],
    ["audit.sweep_pipeline.stage.flatten", 0.412013802],
]


def metric(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def read(name: str, obs: dict):
    out = readers.read_all([metric(name)], obs)
    return out[name]["value"] if name in out else None


def obs_of(manager=None, evaluator=None, **kw) -> dict:
    return dict({"perf": {"manager": manager or {},
                          "evaluator": evaluator or {}},
                 "passes": 2, "objects": 1000, "constraints": 3,
                 "spans": [], "trace": None}, **kw)


# two passes of a four-stage pipeline, two flatten workers
MANAGER = {
    "pipe_wall": 8.0, "list": 4.0, "list_cpu": 3.0,
    "pipe_source_stall": 1.0, "pipe_drain": 3.0,
    "pipe_flatten_busy": 6.0, "pipe_flatten_cpu": 1.0,
    "pipe_flatten_workers": 2.0,
    "pipe_dispatch_busy": 5.0, "pipe_dispatch_cpu": 4.0,
    "pipe_dispatch_workers": 1.0,
    "pipe_collect_busy": 0.5, "pipe_collect_cpu": 0.1,
    "pipe_collect_workers": 1.0,
    "pipe_fold_render_busy": 1.0, "pipe_fold_render_cpu": 1.0,
    "pipe_fold_render_workers": 1.0,
    "render": 0.5, "n_renders": 1400,
}


def test_the_manifest_lists_the_new_metrics_in_the_audit_cells():
    assert manifest.check() == []
    man = manifest.read_json(manifest.MANIFEST)
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        entry = by_name[name]
        assert entry["moves"] == "audit_pass_s"
        assert set(CELLS) <= set(entry["workloads"])
        assert entry["layer"] == metric(name)["layer"]
        assert entry["unit"] == metric(name)["unit"]
    # appended in this order behind what PR 22 had, with nothing put
    # between them; what later PRs append stands after
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    assert at > names.index("python_gc.full_s_per_pass")
    # and a cell loads each with its reader
    cell = manifest.Cell("psp.audit-sweep")
    assert set(NEW) <= {p["name"] for p in cell.per_layer}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_account_reads_nothing(name):
    # the parent of PR 24: no list, no cpu seconds, no gc span, and here
    # no trace either.  (evaluator.perf dispatch and the trace's gaps it
    # had; those two metrics read there too.)
    parent = obs_of(manager={"pipe_wall": 8.0, "pipe_device_wait": 0.5,
                             "pipe_stage_busy_sum": 12.0,
                             "fold_render": 1.0, "n_renders": 1400},
                    evaluator={"flatten": 2.0, "masks": 1.0},
                    spans=[{"name": "audit.sweep", "duration_s": 4.0}])
    if name == "python_gc.full_span_s_per_pass":
        # since PR 38 a tracer's spans with no full collection among them
        # are a window that held none (psp.audit-sweep since PR 35), so the
        # parent of PR 24 would read 0.0; only a run without spans is silent
        assert read(name, parent) == 0.0
        parent = dict(parent, spans=[])
    assert read(name, parent) is None


def test_list_launch_and_render_are_seconds_per_pass():
    obs = obs_of(manager=MANAGER, evaluator={"dispatch": 0.8})
    assert read("list.busy_s_per_pass", obs) == 2.0
    assert read("pack_h2d.launch_s_per_pass", obs) == 0.4
    assert read("fold_render.render_s_per_pass", obs) == 0.25


def test_critical_occupancy_is_the_busiest_slot_over_the_wall():
    # dispatch 5 / 8; the lister 4 / 8; flatten 6 / (8 x 2 workers)
    assert read("audit_schedule.critical_occupancy",
                obs_of(manager=MANAGER)) == pytest.approx(5.0 / 8.0)
    lister_bound = dict(MANAGER, list=7.0)
    assert read("audit_schedule.critical_occupancy",
                obs_of(manager=lister_bound)) == pytest.approx(7.0 / 8.0)
    # a stage whose worker count was not recorded counts as one slot
    unknown = {k: v for k, v in MANAGER.items()
               if k != "pipe_flatten_workers"}
    assert read("audit_schedule.critical_occupancy",
                obs_of(manager=unknown)) == pytest.approx(6.0 / 8.0)


def test_host_blocked_share_is_busy_less_cpu_of_the_host_slots():
    # (4 - 3) + (5 - 4) + (1 - 1) over 4 + 5 + 1; flatten and collect,
    # which release the GIL on purpose, are left out
    assert read("audit_schedule.host_blocked_share",
                obs_of(manager=MANAGER)) == pytest.approx(0.2)
    # thread_time may tick a hair past perf_counter: never below zero
    hot = dict(MANAGER, list_cpu=4.001, pipe_dispatch_cpu=5.0)
    assert read("audit_schedule.host_blocked_share",
                obs_of(manager=hot)) == 0.0


def test_full_span_seconds_are_the_gc_spans_over_the_passes():
    spans = [{"name": "audit.sweep", "duration_s": 4.0},
             {"name": "runtime.gc.full", "duration_s": 0.25},
             {"name": "pipeline.source", "duration_s": 0.5},
             {"name": "runtime.gc.full", "duration_s": 0.35}]
    assert read("python_gc.full_span_s_per_pass",
                obs_of(spans=spans)) == pytest.approx(0.3)
    assert read("python_gc.full_span_s_per_pass",
                obs_of(spans=spans, passes=0)) is None
    assert read("python_gc.full_span_s_per_pass",
                obs_of(spans=spans[::2])) == 0.0  # a window without one


def test_idle_unlabelled_share_on_the_ledgers_pr22_breakdown():
    obs = obs_of(trace={"idle_gaps": LEDGER_PR22_FULL_GAPS})
    assert read("audit_schedule.idle_unlabelled_share", obs) == \
        pytest.approx(0.27, abs=0.005)


def test_idle_unlabelled_share_counts_the_root_alone_and_no_span():
    gaps = [["pipeline.source", 1.0], ["-", 0.5], ["audit.sweep", 0.5],
            ["audit.sweep+pipeline.stage.flatten", 1.0],
            ["audit.report", 1.0]]
    assert read("audit_schedule.idle_unlabelled_share",
                obs_of(trace={"idle_gaps": gaps})) == pytest.approx(0.25)
    named = [["pipeline.source+pipeline.stage.dispatch", 2.0]]
    assert read("audit_schedule.idle_unlabelled_share",
                obs_of(trace={"idle_gaps": named})) == 0.0
    assert read("audit_schedule.idle_unlabelled_share",
                obs_of(trace={"idle_gaps": []})) is None

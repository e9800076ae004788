"""The eight per-layer metrics PR 36 adds, the readers of the GIL account:
five data files with the general ``perf`` reader and three python readers
that subtract (``held = cpu - released``).  On an ``obs`` written by hand;
nothing here times the system under test.  The dispatch and fold_render
stages' held seconds have no line of their own yet (an accepted test of
each layer takes whatever is appended to it for older than its metric):
``audit_schedule.gil_held_share`` reads them."""

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
CELLS = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
         "c500sel.audit-sweep", "cel.audit-sweep"]
# name -> (layer, unit, has a python reader); in the manifest's order
NEW = {
    "audit_schedule.gil_held_share": ("audit_schedule", "1", True),
    "audit_schedule.cores_busy": ("audit_schedule", "cores", False),
    "audit_schedule.drain_s_per_pass": ("audit_schedule", "s", False),
    "flatten.gil_held_s_per_pass": ("flatten", "s", True),
    "flatten.items_s_per_pass": ("flatten", "s", False),
    "flatten.glue_s_per_pass": ("flatten", "s", True),
    "flatten.stabilize_s_per_pass": ("flatten", "s", False),
    "masks.cpu_s_per_pass": ("masks", "s", False),
}
# the 31 the benchmark had: each stands before every new one
ACCEPTED_LAST = "fold_render.peeked_share"

STAGES = ("flatten", "dispatch", "collect", "fold_render")
# a window of two passes of c500.audit-sweep's shape (PERF.md section 5's
# stage table, doubled): the lister and four stages, cpu and released
MANAGER = {
    "pipe_wall": 2.0, "pipe_drain": 0.44, "pipe_process_cpu": 7.0,
    "list": 1.5, "list_cpu": 0.98, "list_released": 0.0,
    "pipe_flatten_cpu": 0.70, "pipe_flatten_released": 0.02,
    "pipe_dispatch_cpu": 0.43, "pipe_dispatch_released": 0.09,
    "pipe_collect_cpu": 0.04, "pipe_collect_released": 0.0,
    "pipe_fold_render_cpu": 0.10, "pipe_fold_render_released": 0.0,
}
EVALUATOR = {
    "fl_items_cpu": 0.06, "fl_columnize_cpu": 0.30,
    "fl_columnize_released": 0.02, "fl_stabilize_cpu": 0.012,
    "masks": 0.28, "masks_cpu": 0.20,
}
WANT = {
    "audit_schedule.gil_held_share":
        (0.98 + 0.68 + 0.34 + 0.04 + 0.10) / 2.0,
    "audit_schedule.cores_busy": 3.5,
    "audit_schedule.drain_s_per_pass": 0.22,
    "flatten.gil_held_s_per_pass": 0.34,
    "flatten.items_s_per_pass": 0.03,
    "flatten.glue_s_per_pass": 0.14,
    "flatten.stabilize_s_per_pass": 0.006,
    "masks.cpu_s_per_pass": 0.10,
}


def metric(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def read(name: str, manager: dict, evaluator: dict, passes: int = 2):
    obs = {"perf": {"manager": manager, "evaluator": evaluator},
           "passes": passes, "objects": 1000, "constraints": 3,
           "spans": [], "trace": None}
    out = readers.read_all([metric(name)], obs)
    return out[name]["value"] if name in out else None


def test_the_manifest_holds_together():
    assert manifest.check() == []


@pytest.mark.parametrize("name", list(NEW))
def test_the_entry_agrees_with_its_file_and_lists_the_audit_cells(name):
    layer, unit, python = NEW[name]
    m = manifest.read_json(manifest.MANIFEST)
    entries = m["per_layer"]
    entry = dict({p["name"]: p for p in entries}[name])
    spec = metric(name)
    listed = entry.pop("workloads")
    assert set(CELLS) <= set(listed)
    # in the manifest's own order
    cells = [w["name"] for w in m["workloads"]]
    assert [c for c in cells if c in listed] == listed
    assert [c for c in listed if c in CELLS] == CELLS
    assert entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": layer,
        "moves": "audit_pass_s"}
    assert (spec["name"], spec["layer"], spec["unit"]) == (name, layer, unit)
    # a layer the benchmark already names, letter for letter
    names = [p["name"] for p in entries]
    assert layer in {p["layer"] for p in entries
                     if names.index(p["name"]) <= names.index(ACCEPTED_LAST)}
    # appended: everything the benchmark had stands before it, and the
    # eight stand in the order they were written in
    assert names.index(name) > names.index(ACCEPTED_LAST)
    mine = [n for n in names if n in NEW]
    assert mine == list(NEW)
    py = manifest.metric_path(name)[:-5] + ".py"
    if python:
        assert spec["read"] == {"from": "python",
                                "file": os.path.basename(py)}
        assert os.path.exists(py)
    else:
        assert spec["read"]["from"] == "perf" and not os.path.exists(py)
    for cell in CELLS:
        assert name in {p["name"] for p in manifest.Cell(cell).per_layer}


def test_every_audit_cell_still_reports_what_it_reported():
    m = manifest.read_json(manifest.MANIFEST)
    names = [p["name"] for p in m["per_layer"]]
    accepted = names[:names.index(ACCEPTED_LAST) + 1]
    assert len(accepted) == 31 and not set(accepted) & set(NEW)
    for cell in CELLS:
        mine = [p["name"] for p in manifest.Cell(cell).per_layer]
        assert [n for n in mine if n in accepted] == accepted
        assert [n for n in mine if n in NEW] == list(NEW)


@pytest.mark.parametrize("name", list(NEW))
def test_the_reader_reads_the_fixture(name):
    assert read(name, MANAGER, EVALUATOR) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", list(NEW))
def test_the_reader_returns_none_without_its_keys(name):
    # a tree without the counters: the parent of PR 36 under this benchmark
    parent_m = {k: v for k, v in MANAGER.items()
                if not k.endswith("_released")
                and k not in ("pipe_process_cpu", "pipe_drain")}
    parent_e = {"masks": 0.28, "flatten": 1.0,
                "fl_c_columnize": 0.6, "fl_py_assemble": 0.1}
    assert read(name, parent_m, parent_e) is None
    assert read(name, {}, {}) is None
    obs = {"perf": {}, "passes": 2, "spans": [], "trace": None}
    assert readers.read_all([metric(name)], obs) == {}


def test_the_drain_is_read_off_a_key_the_parent_already_writes():
    # pipe_drain is PR 24's: the parent's line carries this one metric
    got = read("audit_schedule.drain_s_per_pass",
               {"pipe_drain": 0.68, "pipe_wall": 5.2}, {})
    assert got == pytest.approx(0.34)


@pytest.mark.parametrize("name", [n for n, v in NEW.items() if v[2]])
def test_a_subtracting_reader_wants_both_its_keys(name):
    # cpu without released, or released without cpu, is no account
    for drop in ("_cpu", "_released"):
        m = {k: v for k, v in MANAGER.items() if not k.endswith(drop)}
        e = {k: v for k, v in EVALUATOR.items() if not k.endswith(drop)}
        assert read(name, m, e) is None, drop


def test_the_share_is_reported_unclipped_above_one():
    # numpy's and XLA's own released CPU is booked as held: the threads'
    # held seconds can pass the wall, and the reader says so
    hot = dict(MANAGER, list_cpu=1.4, pipe_flatten_cpu=1.1)
    want = (1.4 + 1.08 + 0.34 + 0.04 + 0.10) / 2.0
    assert want > 1.0
    assert read("audit_schedule.gil_held_share", hot, {}) \
        == pytest.approx(want)


def test_held_is_not_clipped_below_zero():
    # two clocks read a few instructions apart: released may pass cpu by
    # microseconds, and the reader hides nothing
    m = dict(MANAGER, pipe_flatten_cpu=0.010, pipe_flatten_released=0.011)
    assert read("flatten.gil_held_s_per_pass", m, {}) \
        == pytest.approx(-0.0005)
    e = dict(EVALUATOR, fl_columnize_cpu=0.010, fl_columnize_released=0.011)
    assert read("flatten.glue_s_per_pass", {}, e) == pytest.approx(-0.0005)


def test_with_the_native_modules_unloaded_released_reads_zero():
    # every *_released key is written, a 0.0: held is the thread's cpu
    m = {k: (0.0 if k.endswith("_released") else v)
         for k, v in MANAGER.items()}
    e = dict(EVALUATOR, fl_columnize_released=0.0)
    assert read("flatten.gil_held_s_per_pass", m, e) == pytest.approx(0.35)
    assert read("flatten.glue_s_per_pass", m, e) == pytest.approx(0.15)
    assert read("audit_schedule.gil_held_share", m, e) == pytest.approx(
        (0.98 + 0.70 + 0.43 + 0.04 + 0.10) / 2.0)


def test_no_pass_in_the_window_is_nothing_to_read():
    for name in NEW:
        if name in ("audit_schedule.gil_held_share",
                    "audit_schedule.cores_busy"):
            continue  # shares of the wall, not per pass
        assert read(name, MANAGER, EVALUATOR, passes=0) is None, name
    assert read("audit_schedule.gil_held_share",
                dict(MANAGER, pipe_wall=0.0), {}) is None
    assert read("audit_schedule.cores_busy",
                dict(MANAGER, pipe_wall=0.0), {}) is None


def test_the_accepted_account_metrics_stay_on_the_line():
    # the keys PR 24 and 26 read keep their meaning beside the new ones
    assert read("list.cpu_s_per_pass", MANAGER, {}) == pytest.approx(0.49)
    assert read("list.busy_s_per_pass", MANAGER, {}) == pytest.approx(0.75)
    assert read("masks.busy_s_per_pass", {}, EVALUATOR) \
        == pytest.approx(0.14)
    busy = {f"pipe_{s}_busy": 1.0 for s in STAGES}
    workers = {f"pipe_{s}_workers": 1.0 for s in STAGES}
    m = dict(MANAGER, **busy, **workers)
    # the new pipe_<stage>_released and pipe_process_cpu are no slots
    assert read("audit_schedule.critical_occupancy", m, {}) \
        == pytest.approx(0.75)


@pytest.mark.parametrize("cpu,released", [
    ("list_cpu", "list_released")] + [
    (f"pipe_{s}_cpu", f"pipe_{s}_released") for s in STAGES])
def test_the_share_counts_every_thread_of_the_pass(cpu, released):
    # the lister and each stage, dispatch and fold_render too, which have
    # no line of their own: a second of CPU raises the share by a second
    # over the wall, a second of it released takes that away again
    base = read("audit_schedule.gil_held_share", MANAGER, {})
    more = dict(MANAGER, **{cpu: MANAGER[cpu] + 1.0})
    assert read("audit_schedule.gil_held_share", more, {}) \
        == pytest.approx(base + 0.5)
    more[released] = MANAGER[released] + 1.0
    assert read("audit_schedule.gil_held_share", more, {}) \
        == pytest.approx(base)


@pytest.mark.parametrize("layer", ["pack_h2d", "fold_render"])
def test_nothing_is_appended_to_a_layer_whose_test_pins_its_end(layer):
    """tests/benchmark/test_fused_share_metric.py and
    test_peeked_share_metric.py take every other entry of their metric's
    layer for older than it, so an entry appended to `pack_h2d` or
    `fold_render` fails them.  Until a `benchmark` PR names the older
    entries, the two stages' held seconds are read by the share alone."""
    assert layer not in {v[0] for v in NEW.values()}
    entries = manifest.read_json(manifest.MANIFEST)["per_layer"]
    names = [p["name"] for p in entries]
    last = {"pack_h2d": "pack_h2d.fused_share",
            "fold_render": "fold_render.peeked_share"}[layer]
    assert all(names.index(p["name"]) <= names.index(last)
               for p in entries if p["layer"] == layer)

"""A per-layer metric that is a data file with the general ``perf``
reader: ``sweep_device.counted_row_share`` (of the constraint rows the
window's dispatches carried, the share whose total the device gave with no
render).  On an ``obs`` written by hand; nothing here times the system under
test."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402

NAME = "sweep_device.counted_row_share"
# the cells the entry was written for, in the manifest's order; a later
# cell may list it too
CELLS = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
         "c500sel.audit-sweep", "cel.audit-sweep", "exact.audit-sweep"]


def metric() -> dict:
    with open(manifest.metric_path(NAME)) as f:
        return json.load(f)


def read(evaluator: dict, passes: int = 1):
    obs = {"perf": {"manager": {}, "evaluator": evaluator}, "passes": passes,
           "objects": 1000, "constraints": 46, "spans": [], "trace": None}
    out = readers.read_all([metric()], obs)
    return out[NAME]["value"] if NAME in out else None


def test_it_is_data_with_the_general_reader():
    spec = metric()
    assert spec["read"] == {
        "from": "perf", "of": "evaluator", "keys": ["sweep_rows_counted"],
        "over": {"of": "evaluator", "keys": ["sweep_rows"]}}
    assert spec["layer"] == "sweep_device" and spec["unit"] == "1"
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def test_the_entry_agrees_with_its_file_and_lists_the_audit_cells():
    assert manifest.check() == []
    entries = manifest.read_json(manifest.MANIFEST)["per_layer"]
    entry = dict({m["name"]: m for m in entries}[NAME])
    listed = entry.pop("workloads")
    assert set(CELLS) <= set(listed)
    cells = [w["name"] for w in
             manifest.read_json(manifest.MANIFEST)["workloads"]]
    assert [c for c in cells if c in listed] == listed
    assert [c for c in listed if c in CELLS] == CELLS
    spec = metric()
    assert entry == {
        "name": NAME, "unit": spec["unit"], "better": "higher",
        "source": "program_counter", "layer": spec["layer"],
        "moves": "audit_pass_s"}
    # appended behind every sweep_device entry the benchmark had
    names = [m["name"] for m in entries]
    assert names.index(NAME) > names.index("sweep_device.cel_row_share")
    assert names.index(NAME) > names.index("sweep_device.busy_s_per_pass")
    for cell in CELLS:
        assert NAME in {p["name"] for p in manifest.Cell(cell).per_layer}
    # the exact cell keeps exactly its control's list
    exact, control = manifest.Cell("exact.audit-sweep"), \
        manifest.Cell("full.audit-sweep")
    assert [p["name"] for p in exact.per_layer] == \
        [p["name"] for p in control.per_layer]


@pytest.mark.parametrize("counted,rows,want", [
    (5000.0, 5000.0, 1.0),    # the top-k lane: every total is the device's
    (3800.0, 4600.0, 0.826087),  # the exact lane: 38 of 46 kinds counted
    (0.0, 4600.0, 0.0),       # a library whose counts no lowering proves
])
def test_share_is_counted_rows_over_all_rows(counted, rows, want):
    got = read({"sweep_rows_counted": counted, "sweep_rows": rows})
    assert got == pytest.approx(want, rel=1e-6)


def test_a_tree_without_the_counter_reads_nothing():
    # the parent: sweep_rows and no sweep_rows_counted; left out, no raise
    assert read({"sweep_rows": 4600.0, "sweep_rows_cel": 200.0}) is None
    assert read({}) is None

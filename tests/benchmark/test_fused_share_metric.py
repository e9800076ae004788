"""The per-layer metric PR 33 adds, a data file with the general ``perf``
reader: ``pack_h2d.fused_share`` (of the columns the chunks' wire layouts
shipped, the share the one native call per chunk packed).  On an ``obs``
written by hand; nothing here times the system under test."""

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

NAME = "pack_h2d.fused_share"
# the cells the entry was written for; a later cell may list it too
CELLS = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
         "c500sel.audit-sweep"]


def metric(name: str = NAME) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def read(name: str, evaluator: dict, passes: int = 2):
    obs = {"perf": {"manager": {}, "evaluator": evaluator}, "passes": passes,
           "objects": 1000, "constraints": 3, "spans": [], "trace": None}
    out = readers.read_all([metric(name)], obs)
    return out[name]["value"] if name in out else None


def test_the_entry_agrees_with_its_file_and_lists_the_audit_cells():
    assert manifest.check() == []
    entries = manifest.read_json(manifest.MANIFEST)["per_layer"]
    entry = dict({m["name"]: m for m in entries}[NAME])
    spec = metric()
    assert set(CELLS) <= set(entry.pop("workloads"))
    assert entry == {
        "name": NAME, "unit": spec["unit"], "better": "higher",
        "source": "program_counter", "layer": spec["layer"],
        "moves": "audit_pass_s"}
    assert spec["layer"] == "pack_h2d" and spec["unit"] == "1"
    # appended: every pack_h2d entry the benchmark had stands before it
    names = [m["name"] for m in entries]
    older = [m["name"] for m in entries
             if m["layer"] == "pack_h2d" and m["name"] != NAME]
    assert set(older) >= {
        "pack_h2d.busy_s_per_pass", "pack_h2d.bytes_per_object",
        "pack_h2d.launch_s_per_pass", "pack_h2d.mask_bytes_per_object"}
    assert all(names.index(NAME) > names.index(n) for n in older)
    assert names.index(NAME) > names.index("masks.selector_s_per_pass")
    for cell in CELLS:
        assert NAME in {p["name"] for p in manifest.Cell(cell).per_layer}


def test_it_is_data_with_the_general_reader():
    spec = metric()
    assert spec["read"] == {
        "from": "perf", "of": "evaluator", "keys": ["wire_cols_fused"],
        "over": {"of": "evaluator",
                 "keys": ["wire_cols_fused", "wire_cols_numpy"]}}
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


@pytest.mark.parametrize("fused,numpy,want", [
    (8736, 0, 1.0),      # 14 passes of 8 chunks of 78 columns, all native
    (8658, 78, 0.99107),  # one chunk drifted and went whole to numpy
    (0, 8736, 0.0),      # the module did not build on the target
    (0, 0, None),        # no chunk was dispatched
])
def test_share_is_fused_over_fused_and_numpy(fused, numpy, want):
    got = read(NAME, {"wire_cols_fused": fused, "wire_cols_numpy": numpy,
                      "wire_pack": 1.0})
    assert got == (pytest.approx(want, abs=1e-5) if want is not None
                   else None)


def test_it_is_left_out_on_a_tree_without_the_counters():
    # the parent of PR 33 times the pack and counts no column
    assert read(NAME, {"wire_pack": 2.8, "masks": 0.1,
                       "wire_bytes": 1.6e8}) is None
    assert read(NAME, {"wire_cols_numpy": 78}) is None


def test_the_packs_older_metrics_stay_on_the_line():
    window = {"wire_cols_fused": 624, "wire_cols_numpy": 0, "masks": 0.2,
              "wire_pack": 0.6, "wire_bytes": 2000.0 * 620,
              "mask_wire_bytes": 2000.0 * 3.5, "dispatch": 0.1}
    assert read("pack_h2d.busy_s_per_pass", window) == pytest.approx(0.4)
    assert read("pack_h2d.bytes_per_object", window) == pytest.approx(620.0)
    assert read("pack_h2d.mask_bytes_per_object", window) \
        == pytest.approx(3.5)


def test_the_selector_cell_still_reports_what_its_control_reports():
    """What tests/benchmark/test_c500sel_library.py::
    test_the_cell_reports_what_the_control_reports_and_the_two_new holds,
    as containment and relative order (its `len(mine) == 28` and
    `mine[-2:] == NEW` broke when this PR appended an entry, and
    tests/conftest.py deselects it until a `benchmark` PR relaxes them)."""
    cell, control = "c500sel.audit-sweep", "c500.audit-sweep"
    new = ["masks.selector_row_share", "masks.selector_s_per_pass"]
    m = manifest.read_json(manifest.MANIFEST)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert set(CELLS) <= set(e2e["audit_pass_s"]["workloads"])
    theirs = [p["name"] for p in manifest.Cell(control).per_layer]
    mine = [p["name"] for p in manifest.Cell(cell).per_layer]
    assert mine == theirs and len(mine) >= 29
    assert mine.index(new[0]) < mine.index(new[1]) < mine.index(NAME)
    per_layer = {p["name"]: p for p in m["per_layer"]}
    # no list: every cell reports it, the planned admission cells too
    assert "workloads" not in per_layer["entry.compiles_in_window"]
    assert "entry.compiles_in_window" in mine
    for name, better, unit in zip(new, ["higher", "lower"], ["1", "s"]):
        p = per_layer[name]
        assert set(CELLS) <= set(p["workloads"])
        assert (p["layer"], p["moves"], p["source"]) == (
            "masks", "audit_pass_s", "program_counter")
        assert (p["better"], p["unit"]) == (better, unit)
        spec = manifest.read_json(manifest.metric_path(name))
        assert (p["layer"], p["unit"]) == (spec["layer"], spec["unit"])
        assert spec["read"]["from"] == "perf"  # data, the general reader
        assert not os.path.exists(manifest.metric_path(name)[:-5] + ".py")

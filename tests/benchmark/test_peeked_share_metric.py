"""The per-layer metric PR 35 adds, a data file with the general ``perf``
reader: ``fold_render.peeked_share`` (of the kept violations the passes
built, the share whose object's identity came off the bytes and never
through the dict).  On an ``obs`` written by hand; nothing here times the
system under test."""

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

NAME = "fold_render.peeked_share"
# the cells the entry was written for; a later cell may list it too
CELLS = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
         "c500sel.audit-sweep", "cel.audit-sweep"]


def metric(name: str = NAME) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def read(name: str, manager: dict, passes: int = 2):
    obs = {"perf": {"manager": manager, "evaluator": {}}, "passes": passes,
           "objects": 1000, "constraints": 3, "spans": [], "trace": None}
    out = readers.read_all([metric(name)], obs)
    return out[name]["value"] if name in out else None


def test_the_entry_agrees_with_its_file_and_lists_the_audit_cells():
    assert manifest.check() == []
    entries = manifest.read_json(manifest.MANIFEST)["per_layer"]
    entry = dict({m["name"]: m for m in entries}[NAME])
    spec = metric()
    listed = entry.pop("workloads")
    assert set(CELLS) <= set(listed)
    # in the manifest's own order
    cells = [w["name"] for w in
             manifest.read_json(manifest.MANIFEST)["workloads"]]
    assert [c for c in cells if c in listed] == listed
    assert [c for c in listed if c in CELLS] == CELLS
    assert entry == {
        "name": NAME, "unit": spec["unit"], "better": "higher",
        "source": "program_counter", "layer": spec["layer"],
        "moves": "audit_pass_s"}
    assert spec["layer"] == "fold_render" and spec["unit"] == "1"
    # appended: every fold_render entry the benchmark had stands before it
    names = [m["name"] for m in entries]
    older = [m["name"] for m in entries
             if m["layer"] == "fold_render" and m["name"] != NAME]
    assert set(older) >= {
        "fold_render.busy_s_per_pass", "fold_render.renders_per_pass",
        "fold_render.render_s_per_pass", "fold_render.memo_hit_share"}
    assert all(names.index(NAME) > names.index(n) for n in older)
    assert names.index(NAME) > names.index("pack_h2d.fused_share")
    assert names.index(NAME) > names.index("sweep_device.cel_row_share")
    for cell in CELLS:
        assert NAME in {p["name"] for p in manifest.Cell(cell).per_layer}


def test_it_is_data_with_the_general_reader():
    spec = metric()
    assert spec["read"] == {
        "from": "perf", "of": "manager", "keys": ["violation_peeked"],
        "over": {"of": "manager",
                 "keys": ["violation_peeked", "violation_loaded"]}}
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


@pytest.mark.parametrize("peeked,loaded,want", [
    (187000, 0, 1.0),       # 34 passes of 5,500 kept, none through the dict
    (0, 187000, 0.0),       # the module did not build on the target
    (181500, 5500, 0.97059),  # one pass in 34 rendered, and so loaded
    (0, 0, None),           # the passes kept nothing
])
def test_share_is_peeked_over_peeked_and_loaded(peeked, loaded, want):
    got = read(NAME, {"violation_peeked": peeked, "violation_loaded": loaded,
                      "fold_render": 1.0, "render_memo_hits": 187000})
    assert got == (pytest.approx(want, abs=1e-5) if want is not None
                   else None)


def test_it_is_left_out_on_a_tree_without_the_counters():
    # the parent of PR 35 builds its violations and counts neither way
    assert read(NAME, {"fold_render": 10.6, "render_memo_hits": 187000,
                       "n_renders": 0, "render": 0.0}) is None
    assert read(NAME, {"violation_loaded": 5500}) is None


def test_the_folds_older_metrics_stay_on_the_line():
    window = {"violation_peeked": 11000, "violation_loaded": 0,
              "fold_render": 0.4, "n_renders": 0, "render": 0.0,
              "render_memo_hits": 10400}
    assert read("fold_render.busy_s_per_pass", window) == pytest.approx(0.2)
    assert read("fold_render.renders_per_pass", window) == 0.0
    assert read("fold_render.render_s_per_pass", window) == 0.0
    assert read("fold_render.memo_hit_share", window) == 1.0


def test_every_audit_cell_reports_the_same_per_layer_list():
    m = manifest.read_json(manifest.MANIFEST)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert set(CELLS) <= set(e2e["audit_pass_s"]["workloads"])
    lists = [[p["name"] for p in manifest.Cell(c).per_layer] for c in CELLS]
    assert all(one == lists[0] for one in lists) and len(lists[0]) >= 31
    assert lists[0].index("fold_render.memo_hit_share") \
        < lists[0].index("pack_h2d.fused_share") < lists[0].index(NAME)

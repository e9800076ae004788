"""The per-layer metric PR 30 adds, a data file with the general ``perf``
reader: ``python_gc.untracked_share`` (of the objects a pass's lister
handed over, the share the native router took off the cyclic collector's
lists).  On an ``obs`` written by hand; nothing here times the system
under test."""

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

NAME = "python_gc.untracked_share"
# the cells the entries were written for; a later cell may list them too
CELLS = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep"]

# what the parent of PR 30 writes for a pass of 2,000 listed objects
PARENT = {"list": 3.0, "list_cpu": 2.0, "list_fast": 1990.0,
          "list_slow": 10.0}


def metric(name: str = NAME) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def read(name: str, manager: dict, passes: int = 2):
    obs = {"perf": {"manager": manager, "evaluator": {}}, "passes": passes,
           "objects": 1000, "constraints": 3, "spans": [], "trace": None}
    out = readers.read_all([metric(name)], obs)
    return out[name]["value"] if name in out else None


# fold_render.memo_hit_share rides along: its own test once pinned it as
# the manifest's last entry and was deselected for it (it runs again since
# PR 31)
@pytest.mark.parametrize("name,layer", [
    (NAME, "python_gc"), ("fold_render.memo_hit_share", "fold_render")])
def test_the_entry_agrees_with_its_file_and_lists_the_three_audit_cells(
        name, layer):
    assert manifest.check() == []
    entries = manifest.read_json(manifest.MANIFEST)["per_layer"]
    entry = dict({m["name"]: m for m in entries}[name])
    spec = metric(name)
    assert set(CELLS) <= set(entry.pop("workloads"))
    assert entry == {
        "name": name, "unit": spec["unit"], "better": "higher",
        "source": "program_counter", "layer": spec["layer"],
        "moves": "audit_pass_s"}
    assert spec["layer"] == layer and spec["unit"] == "1"
    for cell in CELLS:
        assert name in {p["name"] for p in manifest.Cell(cell).per_layer}


def test_it_is_appended_behind_what_the_benchmark_had():
    names = [m["name"] for m in
             manifest.read_json(manifest.MANIFEST)["per_layer"]]
    assert names.index(NAME) > names.index("fold_render.memo_hit_share")
    assert names.index(NAME) > names.index("python_gc.full_s_per_pass")


def test_it_is_data_with_the_general_reader():
    spec = metric()
    assert spec["read"] == {
        "from": "perf", "of": "manager", "keys": ["list_untracked"],
        "over": {"of": "manager", "keys": ["list_fast", "list_slow"]}}
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def test_it_is_left_out_on_a_tree_without_the_counter():
    assert read(NAME, PARENT) is None
    # and the metric the parent does report is still there
    assert read("list.fast_share", PARENT) == pytest.approx(0.995)


@pytest.mark.parametrize("untracked,fast,slow,want", [
    (2000, 2000, 0, 1.0),    # every cell: unloaded head-form RawJSON
    (2000, 1990, 10, 1.0),   # ten heads settled nothing: still acyclic
    (1500, 1500, 500, 0.75),  # a quarter arrived loaded, or as dicts
    (0, 0, 2000, 0.0),       # the per-object loop ran: nothing untracked
    (0, 0, 0, None),         # an empty listing
])
def test_share_is_untracked_over_all_listed(untracked, fast, slow, want):
    got = read(NAME, dict(PARENT, list_untracked=untracked, list_fast=fast,
                          list_slow=slow))
    assert got == (pytest.approx(want) if want is not None else None)

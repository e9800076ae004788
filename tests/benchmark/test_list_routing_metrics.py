"""The two per-layer metrics PR 26 adds, both data files with the general
``perf`` reader: ``list.fast_share`` (the share of the listing the native
routing call settled) and ``list.cpu_s_per_pass`` (the lister's own CPU
seconds).  Each on an ``obs`` written by hand; nothing here times the
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

# the cells the entries were written for; a later cell may list them too
CELLS = ["full.audit-sweep", "psp.audit-sweep"]
NEW = ["list.fast_share", "list.cpu_s_per_pass"]
# PR 24's, held here too since their own test once pinned them as the
# manifest's last and was deselected for it (it runs again since PR 31)
PR24 = ["list.busy_s_per_pass", "audit_schedule.critical_occupancy",
        "audit_schedule.host_blocked_share", "pack_h2d.launch_s_per_pass",
        "fold_render.render_s_per_pass", "python_gc.full_span_s_per_pass",
        "audit_schedule.idle_unlabelled_share"]


def metric(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def read(name: str, manager: dict, passes: int = 2):
    obs = {"perf": {"manager": manager, "evaluator": {}}, "passes": passes,
           "objects": 1000, "constraints": 3, "spans": [], "trace": None}
    out = readers.read_all([metric(name)], obs)
    return out[name]["value"] if name in out else None


# mgr.perf of the parent of PR 26 (it has the lister's seconds since PR 24)
# and of the change, two passes of 1,000 objects
PARENT = {"pipe_wall": 8.0, "list": 4.0, "list_cpu": 3.0}
CHANGE = dict(PARENT, list=3.0, list_cpu=2.0, list_fast=1990.0,
              list_slow=10.0)


def entries() -> list:
    return manifest.read_json(manifest.MANIFEST)["per_layer"]


def test_the_manifest_resolves_with_the_two_new_metrics():
    assert manifest.check() == []
    by_name = {m["name"]: m for m in entries()}
    for name in NEW:
        assert by_name[name]["layer"] == "list"
        assert by_name[name]["source"] == "program_counter"
    assert by_name["list.fast_share"]["better"] == "higher"
    assert by_name["list.cpu_s_per_pass"]["better"] == "lower"


@pytest.mark.parametrize("name", PR24 + NEW)
def test_an_entry_agrees_with_its_file_in_both_audit_cells(name):
    entry = {m["name"]: m for m in entries()}[name]
    assert entry["moves"] == "audit_pass_s"
    assert set(CELLS) <= set(entry["workloads"])
    assert entry["layer"] == metric(name)["layer"]
    assert entry["unit"] == metric(name)["unit"]
    # and a cell loads it with its reader
    for cell in CELLS:
        assert name in {p["name"] for p in manifest.Cell(cell).per_layer}


def test_entries_are_appended_never_put_in_the_middle():
    names = [m["name"] for m in entries()]
    at = names.index(PR24[0])
    assert names[at:at + len(PR24)] == PR24
    assert names[at + len(PR24):at + len(PR24) + len(NEW)] == NEW


@pytest.mark.parametrize("name", NEW)
def test_both_are_data_with_the_general_reader(name):
    spec = metric(name)
    assert spec["read"]["from"] == "perf" and spec["read"]["of"] == "manager"
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_fast_share_is_left_out_on_a_tree_without_the_counters():
    assert read("list.fast_share", PARENT) is None
    # one key alone is no reading either
    assert read("list.fast_share", dict(PARENT, list_fast=5.0)) is None


def test_cpu_seconds_read_on_the_parent_and_on_the_change():
    assert read("list.cpu_s_per_pass", PARENT) == 1.5
    assert read("list.cpu_s_per_pass", CHANGE) == 1.0
    assert read("list.cpu_s_per_pass", {"list": 4.0}) is None
    assert read("list.cpu_s_per_pass", PARENT, passes=0) is None


@pytest.mark.parametrize("fast,slow,want", [
    (1990.0, 10.0, 0.995), (2000.0, 0.0, 1.0),
    (0.0, 2000.0, 0.0),   # the module did not build: the per-object loop
    (0.0, 0.0, None),     # nothing was listed
])
def test_fast_share_is_fast_over_all_listed(fast, slow, want):
    got = read("list.fast_share",
               dict(PARENT, list_fast=fast, list_slow=slow))
    assert got == (pytest.approx(want) if want is not None else None)

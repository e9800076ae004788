"""The per-layer metric PR 28 adds, a data file with the general ``perf``
reader: ``fold_render.memo_hit_share`` (of the renders a pass's fold asked
for, the share the render memo answered).  On an ``obs`` written by hand;
nothing here times the system under test."""

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

NAME = "fold_render.memo_hit_share"
# the cells the entry was written for; a later cell may list it too
CELLS = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep"]


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
    assert set(CELLS) <= set(entry.pop("workloads"))
    assert entry == {
        "name": NAME, "unit": spec["unit"], "better": "higher",
        "source": "program_counter", "layer": spec["layer"],
        "moves": "audit_pass_s"}
    assert spec["layer"] == "fold_render" and spec["unit"] == "1"
    # appended: everything the benchmark had then stands before it
    names = [m["name"] for m in entries]
    assert names.index(NAME) > names.index("pack_h2d.mask_bytes_per_object")
    for cell in CELLS:
        assert NAME in {p["name"] for p in manifest.Cell(cell).per_layer}


def test_it_is_data_with_the_general_reader():
    spec = metric()
    assert spec["read"] == {
        "from": "perf", "of": "manager", "keys": ["render_memo_hits"],
        "over": {"of": "manager",
                 "keys": ["render_memo_hits", "n_renders"]}}
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


@pytest.mark.parametrize("hits,renders,want", [
    (9846, 0, 1.0),        # the window lists the set-up pass's bytes again
    (9747, 99, 0.98995),   # a percent of the kept violations changed
    (0, 9846, 0.0),        # the first pass after boot: every render misses
    (0, 0, None),          # nothing violates: no render was asked for
])
def test_hit_share_is_hits_over_hits_and_renders(hits, renders, want):
    got = read(NAME, {"render_memo_hits": hits, "n_renders": renders,
                      "render_memo_bypass": 0, "render": 0.0})
    assert got == (pytest.approx(want, abs=1e-5) if want is not None
                   else None)


def test_it_is_left_out_on_a_tree_without_the_counter():
    # the parent of PR 28 writes n_renders only
    assert read(NAME, {"n_renders": 9846, "render": 5.0}) is None


def test_a_window_of_hits_keeps_the_renderers_older_metrics_on_the_line():
    """``n_renders`` and ``render`` are written on every pass, a 0 too, so
    the two metrics that read them report 0 and are not left out."""
    window = {"render_memo_hits": 9846, "n_renders": 0, "render": 0.0,
              "render_memo_bypass": 0}
    assert read("fold_render.renders_per_pass", window) == 0
    assert read("fold_render.render_s_per_pass", window) == 0.0
    assert read("fold_render.renders_per_pass", {}) is None

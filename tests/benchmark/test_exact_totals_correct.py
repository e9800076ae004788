"""``library-full-exact`` and its cell ``exact.audit-sweep`` (PR 38): the audit
as ``python -m gatekeeper_tpu`` runs it, ``exact_totals`` true.  ``correct``
holds a configuration to the totals it states (results under exact totals,
violating objects otherwise), a window that holds few passes is still traced,
the configuration is ``library-full``'s in every key but four, the manifest
resolves with the sixth cell, and the cell runs end to end at toy size: as it
stands (correct), with the program counting objects where the configuration
states results (the control: not correct), and with the timed path broken
underneath (not correct).  Nothing here times the system under test."""

from __future__ import annotations

import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import audit, manifest, wiring  # noqa: E402

CELL = "exact.audit-sweep"
CONTROL = "full.audit-sweep"
SEED = "2147483999"


def config(name: str) -> dict:
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           name + ".json"))


# --- totals: results under exact_totals, violating objects otherwise -----------

A, B = ("Pod", "ns-0", "a"), ("Pod", "ns-1", "b")
K1, K2 = ("K8sPSPPrivilegedContainer", "psp"), ("K8sRequiredLabels", "owner")
IDENT = {0: A, 1: B}
# Pod a has two privileged containers (two results of K1) and one of K2;
# Pod b violates nothing; the sample is listed twice
RESULTS = {0: {K1: ["c1 is privileged", "c2 is privileged"],
               K2: ["no owner"]}}
ORDER = [0, 1, 0, 1]
KEPT = {K1: [(A, "c2 is privileged"), (A, "c1 is privileged"),
             (A, "c1 is privileged")],
        K2: [(A, "no owner"), (A, "no owner")]}
RESULT_TOTALS = {K1: 4, K2: 2}   # what an exact_totals audit reports
OBJECT_TOTALS = {K1: 2, K2: 2}   # what the device's counts say


def audited(totals: dict, kept: dict = KEPT, n: int = len(ORDER)):
    V = collections.namedtuple("V", "kind namespace name message")
    Run = collections.namedtuple(
        "Run", "total_violations kept total_objects incomplete")
    return Run(totals, {k: [V(*obj, msg) for obj, msg in vs]
                        for k, vs in kept.items()}, n, False)


@pytest.mark.parametrize("exact,totals,differ", [
    (True, RESULT_TOTALS, 0), (True, OBJECT_TOTALS, 1),
    (False, OBJECT_TOTALS, 0), (False, RESULT_TOTALS, 1)])
def test_each_lane_passes_with_its_own_totals_and_fails_with_the_others(
        exact, totals, differ):
    counts: dict = {}
    problems = audit.sample_audit_problems(audited(totals), ORDER, RESULTS,
                                           IDENT, 3, exact, counts)
    assert len(problems) == differ
    assert counts == {"sample_audit_short": 0, "sample_kept_differ": 0,
                      "sample_totals_differ": differ,
                      "sample_totals_missing": 0}
    if differ:
        assert "K8sPSPPrivilegedContainer" in problems[0]


def test_the_lane_defaults_to_violating_objects_as_every_older_cell_states():
    assert audit.sample_audit_problems(audited(OBJECT_TOTALS), ORDER, RESULTS,
                                       IDENT, 3) == []
    for name in ("library-full", "psp-pods", "library-c500",
                 "library-c500sel", "library-cel"):
        assert config(name)["audit"]["exact_totals"] is False


def test_kept_violations_are_results_in_both_lanes():
    short = {**KEPT, K1: KEPT[K1][:2]}  # the limit is 3: one is owed
    for exact, totals in ((True, RESULT_TOTALS), (False, OBJECT_TOTALS)):
        counts: dict = {}
        assert len(audit.sample_audit_problems(
            audited(totals, short), ORDER, RESULTS, IDENT, 3, exact,
            counts)) == 1
        assert counts["sample_kept_differ"] == 1
        assert counts["sample_totals_differ"] == 0


# --- which passes of the window a traced run wraps -----------------------------

@pytest.mark.parametrize("setup_pass_s,window_s,plan", [
    (2.5, 51.0, (2, 2)),     # full.audit-sweep: twenty passes a window
    (10.54, 51.0, (2, 2)),   # the slowest set-up pass on record (c500sel, cold)
    (12.75, 51.0, (2, 2)),   # four fit exactly
    (12.76, 51.0, (0, 1)),   # three fit: the first pass alone
    (35.0, 51.0, (0, 1)),    # exact.audit-sweep: one pass a window
    (60.0, 51.0, (0, 1)),    # none fits whole: the first is still traced
    (0.15, 51.0, (2, 2)),
    (2.0, 7.0, (0, 1)),      # a short window by hand (--seconds 7)
])
def test_a_window_of_few_passes_traces_its_first_alone(setup_pass_s, window_s,
                                                       plan):
    assert audit.trace_plan(setup_pass_s, window_s, 2, 2) == plan


def test_the_plan_is_the_mixes_own_where_the_window_holds_it():
    mix = manifest.read_json(manifest.traffic_path("audit-sweep"))
    assert audit.trace_plan(1.6, 51.0, mix["trace_from_pass"],
                            mix["trace_passes"]) == (2, 2)
    toy = mix["rehearse"]
    assert audit.trace_plan(0.8, 51.0, toy["trace_from_pass"],
                            toy["trace_passes"]) == (1, 1)
    assert audit.trace_plan(30.0, 51.0, toy["trace_from_pass"],
                            toy["trace_passes"]) == (0, 1)


# --- a set-up pass that grew a hit buffer is followed by one more ---------------

class FakeEvaluator:
    """``warm_state()`` as ``ShardedEvaluator`` gives it, the hit-buffer
    state moving on one step with every pass the fake manager runs."""

    def __init__(self, states: list):
        self.states, self.at = states, 0

    def warm_state(self) -> dict:
        return {"hit_state": self.states[min(self.at, len(self.states) - 1)]}


class FakeManager:
    def __init__(self, ev):
        self.ev = ev

    def audit(self):
        self.ev.at += 1
        return f"pass {self.ev.at}"


POD, CRB = (("Pod",), 32768), (("ClusterRoleBinding",), 4096)


def hit_state(pod_cap=65536, crb_cap=256, pinned=False, low=0, **more):
    return dict({POD: {"cap": pod_cap, "low": low, "pinned": False,
                       "blast": None},
                 CRB: {"cap": crb_cap, "low": 0, "pinned": pinned,
                       "blast": None}}, **more)


@pytest.mark.parametrize("states,passes", [
    # nothing that is part of a program's key moves: one set-up pass (the
    # `low` counter and the top-k lane's `blast` are not part of it)
    ([hit_state(), hit_state(low=3)], 1),
    # a chunk overflowed and its buffer grew: the pass after asks for the
    # program of the new size, so it is set-up's too
    ([hit_state(), hit_state(crb_cap=1024), hit_state(crb_cap=1024)], 2),
    # ... unless the overflow pinned the shape to the bit grid, whose
    # program the overflowing chunk itself ran (most seeds of
    # exact.audit-sweep: one set-up pass)
    ([hit_state(), hit_state(pinned=True)], 1),
    ([hit_state(), hit_state(crb_cap=1024, pinned=True)], 1),
    # a shape first swept in the set-up pass asked for its program there
    ([hit_state(), hit_state(**{"new": {"cap": 256, "low": 0,
                                        "pinned": False, "blast": 7}})], 1),
    # never more than three, whatever the program does
    ([hit_state(crb_cap=c) for c in (256, 512, 1024, 2048, 4096)], 3),
])
def test_the_set_up_pass_is_run_again_while_a_hit_buffer_grows(states,
                                                               passes):
    ev = FakeEvaluator(states)
    first, seconds, n = audit.settled_pass(FakeManager(ev), ev)
    assert (first, n) == (f"pass {passes}", passes) and seconds >= 0.0
    assert audit.hit_buffers(ev)[POD] == 65536


# --- the configuration and the manifest ------------------------------------------

def test_the_configuration_is_library_fulls_in_every_key_but_four():
    full, exact = config("library-full"), config("library-full-exact")
    assert exact["audit"].pop("exact_totals") is True
    assert full["audit"].pop("exact_totals") is False
    assert exact.pop("objects") == 131072 and full.pop("objects") == 262144
    assert "results" in exact["guarantees"].pop("totals")
    assert "exact_totals=false" in full["guarantees"].pop("totals")
    assert "audit.exact_totals" not in exact["assumed"]
    del full["assumed"]["audit.exact_totals"]
    # the words that say which deployment this is, and why it was cut
    for key in ("name", "source", "source_detail", "reduced"):
        assert exact.pop(key) != full.pop(key)
    assert exact == full
    for key in ("cluster", "library", "referential_kinds",
                "reference_sample", "rehearse"):
        assert key in exact


def test_the_manifest_resolves_with_the_sixth_configuration_and_cell():
    assert manifest.check() == []
    m = manifest.read_json(manifest.MANIFEST)
    assert len(m["configs"]) >= 6 and len(m["workloads"]) >= 6
    entry = next(c for c in m["configs"] if c["name"] == "library-full-exact")
    doc = config("library-full-exact")
    assert entry["file"] == "benchmark/configs/library-full-exact.json"
    assert entry["source"] == doc["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["objects"] == list(doc["reduced"])
    work = next(w for w in m["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "library-full-exact",
                    "traffic": "audit-sweep", "chips": 1, "why": work["why"]}
    assert f"{doc['objects']} objects x 46 constraints" in work["why"]
    assert "exact totals" in work["why"] and CONTROL in work["why"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0


def test_the_cell_reports_what_its_control_reports_under_the_same_bounds():
    cell, control = manifest.Cell(CELL), manifest.Cell(CONTROL)
    assert cell.end_to_end == control.end_to_end
    assert {e["name"] for e in cell.end_to_end} == {"audit_pass_s",
                                                    "setup_s"}
    mine = [p["name"] for p in cell.per_layer]
    assert mine == [p["name"] for p in control.per_layer] and len(mine) >= 39
    m = manifest.read_json(manifest.MANIFEST)
    for metric in m["end_to_end"] + m["per_layer"]:
        listed = metric.get("workloads")
        if listed and CONTROL in listed:
            assert listed.index(CONTROL) < listed.index(CELL)
    assert cell.config["audit"] == {"chunk_size": 32768,
                                    "violations_limit": 20,
                                    "exact_totals": True}
    assert cell.traffic["name"] == "audit-sweep"


# --- the cell, end to end at toy size ----------------------------------------------

def rehearse(capsys, *args: str) -> tuple:
    """(the result line, the notes) of one rehearsal of the cell."""
    from benchmark import run as run_py

    assert run_py.main(["--workload", CELL, "--rehearse", "--seed", SEED,
                        "--seconds", "4", *args]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert "rehearsal" in line and list(line)[-1] == "compared"
    last = captured.err.strip().splitlines()[-1]
    assert last.startswith(f"benchmark: correct={line['correct']}, compared: ")
    assert json.loads(last.partition("compared: ")[2]) == line["compared"]
    # `correct` is what the numbers compared say, each against its limit
    assert line["correct"] is all(c["value"] <= c["limit"]
                                  for c in line["compared"].values())
    with open(os.path.join(ROOT, "benchmark", ".cache", CELL,
                           "notes.json")) as f:
        return line, json.load(f)


def test_rehearse_the_cell(capsys):
    """The whole audit path at toy sizes on whatever JAX finds, in the lane
    ``python -m gatekeeper_tpu`` runs: the totals are result counts (more
    than the violating objects, and the notes show both), every hit of the
    ``return_bits`` sweep is a render or a memo lookup, and the traced run
    reports every per-layer metric the cell lists.  Half a minute."""
    line, notes = rehearse(capsys, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert all(c == {"value": 0, "limit": 0}
               for c in line["compared"].values())
    assert len(line["compared"]) == 10
    assert notes["problems"] == []
    assert notes["sample_audit_violations"] > \
        notes["sample_audit_violating_objects"] > 0
    assert notes["violations"] > notes["kept"] > 0
    assert (notes["traced_from"], notes["traced_passes"]) == (1, 1)
    assert notes["setup_passes"] == 1
    metrics = line["metrics"]
    # (all but the roofline share, which has no peak off the chip)
    listed = {p["name"] for p in manifest.Cell(CELL).per_layer}
    assert listed - set(metrics) <= {"sweep_device_roofline"}
    asked = metrics["fold_render.renders_per_pass"]["value"] / (
        1.0 - metrics["fold_render.memo_hit_share"]["value"])
    # the fold asks for every hit, not only for those it keeps
    assert notes["kept"] < asked <= notes["violations"]
    assert metrics["entry.compiles_in_window"]["value"] == 0.0
    assert "python_gc.full_span_s_per_pass" in metrics


def program_counts_objects(monkeypatch) -> None:
    """The control: the program's own cheaper lane (totals are the device's
    counts of violating objects, only the kept top-k render) under a
    configuration that states result counts."""
    real = wiring.Program.build_audit

    def build_audit(self, lister):
        stated = self.config
        self.config = dict(stated, audit=dict(stated["audit"],
                                              exact_totals=False))
        try:
            return real(self, lister)
        finally:
            self.config = stated

    monkeypatch.setattr(wiring.Program, "build_audit", build_audit)


def half_of_the_hits_left_out(monkeypatch) -> None:
    """Half of each constraint's hits of a chunk never reach the fold."""
    from gatekeeper_tpu.audit import manager

    real = manager.violation_rows

    def violation_rows(bits_or_hits, ci, n):
        rows = real(bits_or_hits, ci, n)
        return rows[:(len(rows) + 1) // 2]

    monkeypatch.setattr(manager, "violation_rows", violation_rows)


def a_total_altered_in_the_window(monkeypatch) -> None:
    """Set-up is sound (the sample's audit, the set-up pass); every pass
    after them reports one result more for its first constraint."""
    from gatekeeper_tpu.audit.manager import AuditManager

    real = AuditManager.audit
    calls = [0]

    def audit_(self, *a, **kw):
        out = real(self, *a, **kw)
        calls[0] += 1
        if calls[0] > 2:
            key = next(iter(out.total_violations))
            out.total_violations[key] += 1
        return out

    monkeypatch.setattr(AuditManager, "audit", audit_)


@pytest.mark.parametrize("fault,fails", [
    (program_counts_objects, "sample_totals_differ"),
    (half_of_the_hits_left_out, "sample_totals_differ"),
    (a_total_altered_in_the_window, "passes_differ")])
def test_the_control_and_the_faults_come_out_not_correct(
        capsys, monkeypatch, fault, fails):
    """The harness's look for a chip skipped (``--rehearse``), the rest of a
    run driven with the timed path broken underneath: ``correct`` is false,
    and the number that says so is the one expected."""
    fault(monkeypatch)
    line, notes = rehearse(capsys)
    assert line["correct"] is False
    assert line["compared"][fails]["value"] > 0
    assert notes["problems"]
    if fault is program_counts_objects:
        # nothing else is amiss: the verdicts, the kept violations and the
        # passes are the sound program's
        assert {n for n, c in line["compared"].items() if c["value"]} == {
            fails}
        assert notes["sample_audit_violations"] == \
            notes["sample_audit_violating_objects"]
    if fault is a_total_altered_in_the_window:
        assert line["compared"][fails]["value"] == notes["passes"]
        assert line["compared"]["sample_totals_differ"]["value"] == 0

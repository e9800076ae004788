"""The reader of ``mutlane.scoped_copy_share``: of the working copies the
batched mutation lane built in a window, the share copied along the
mutators' paths, from the attributes ``copies_scoped`` and ``copies_full``
of the spans ``mutlane.apply``.  On an ``obs`` written by hand; nothing
here times the system under test."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers  # noqa: E402

FILE = "mutlane.scoped_copy_share.py"


def span(name, **attributes) -> dict:
    return {"name": name, "duration_s": 0.5, "attributes": attributes}


def read(spans, passes: int = 2):
    return readers.read_python({"spans": spans, "passes": passes},
                               {"from": "python", "file": FILE})


@pytest.mark.parametrize("counts,want", [
    # every probe and solo scoped, the host's objects whole
    ([(8720, 72), (8750, 70)], 17470 / 17612),
    ([(12, 0)], 1.0),
    ([(0, 5)], 0.0),
    # a pass whose lane copied nothing still counts in the sum
    ([(0, 0), (3, 1)], 0.75),
])
def test_share_is_scoped_over_all_copies(counts, want):
    spans = [span("mutlane.apply", n=100, copies_scoped=s, copies_full=f)
             for s, f in counts]
    spans.append(span("expansion.stage", phase="audit", resultants=100))
    assert read(spans) == pytest.approx(want, rel=1e-9)


def test_a_window_without_the_lane_reads_nothing():
    assert read([]) is None
    assert read([span("expansion.stage", phase="audit", resultants=9)]) \
        is None
    # lane spans of a tree that does not count its copies
    assert read([span("mutlane.apply", n=9, lane_solo=9)]) is None
    # lane spans that copied nothing: no share to read
    assert read([span("mutlane.apply", n=9, lane_noop=9, copies_scoped=0,
                      copies_full=0)]) is None


def test_the_lane_sets_the_attributes_the_reader_reads():
    from gatekeeper_tpu.mutation.system import MutationSystem
    from gatekeeper_tpu.mutlane import MutationLane
    from gatekeeper_tpu.observability import tracing

    system = MutationSystem()
    system.upsert_unstructured({
        "apiVersion": "mutations.gatekeeper.sh/v1", "kind": "Assign",
        "metadata": {"name": "pull"},
        "spec": {"applyTo": [{"groups": [""], "versions": ["v1"],
                              "kinds": ["Pod"]}],
                 "location": "spec.containers[name: *].imagePullPolicy",
                 "parameters": {"assign": {"value": "Always"}}}})
    pods = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}"},
             "spec": {"containers": [{"name": "a"}]}} for i in range(3)]
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        MutationLane(system).mutate_objects(pods, want_objects=True)
    spans = [s for t in tracer.traces() for s in t["spans"]]
    assert [s["attributes"]["copies_scoped"] for s in spans
            if s["name"] == "mutlane.apply"] == [3]
    assert read(spans, passes=1) == 1.0

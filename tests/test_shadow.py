"""Shadow canary lane: candidate policy against live traffic.

The safety pins the module docstring of ``replay/shadow.py`` promises:

1. THE serving-identity pin: with a shadow lane active (worker running,
   candidate evaluating), every served admission response is
   field-for-field identical to the lane-off response — the lane can
   never alter, delay, or answer an admission.
2. Divergence detection both ways: a candidate missing a deny-firing
   constraint reports ``would_allow``; the inverse deployment reports
   ``would_deny``; a candidate that errors reports ``would_error`` and
   a lane-internal crash is swallowed into ``lane_errors``.
3. Backpressure: a full queue drops the OLDEST item, counted, never
   blocking the submitter; served shed/error/deadline responses are
   skipped (nothing to shadow).
4. Promote/abort: ``promote()`` applies the candidate docs to the
   SERVING client (the generation-swap ride) so a previously-allowed
   admission turns deny; both end states refuse further submits.
5. The ``shadow-divergence-rate`` SLO objective sums the divergence
   counter ACROSS its {kind} labelsets (the labels-omitted ratio path).
6. ``/debug/shadow``: GET snapshot, POST promote/abort.

Wall budget: one module-scoped 3-template library + shared compile
cache; every runtime after the first loads with zero fresh lowerings.
"""

from __future__ import annotations

import copy
import json
import urllib.error
import urllib.request

import pytest

from gatekeeper_tpu.gator import reader
from gatekeeper_tpu.metrics import registry as M
from gatekeeper_tpu.metrics.registry import MetricsRegistry
from gatekeeper_tpu.observability import flightrec
from gatekeeper_tpu.observability.slo import SLOObjective
from gatekeeper_tpu.replay import core, shadow
from gatekeeper_tpu.replay.shadow import SHADOW_OBJECTIVE, ShadowLane
from gatekeeper_tpu.utils.unstructured import name_of
from gatekeeper_tpu.webhook.policy import ValidationResponse
from gatekeeper_tpu.webhook.server import WebhookServer
from tests import traffic_helpers


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    """Serving (full 3-template library) and candidate (same library
    minus one deny-firing constraint) runtimes over one shared compile
    cache, plus the traffic split by the serving verdict."""
    cache_dir = str(tmp_path_factory.mktemp("shadow-cc"))
    full = traffic_helpers.library_docs(3)
    bodies = traffic_helpers.admission_bodies(40, seed=5)
    serving = core.load_candidate(full, compile_cache_dir=cache_dir)
    served = [serving.handler.handle(copy.deepcopy(b)) for b in bodies]
    denied = [b for b, r in zip(bodies, served) if not r.allowed]
    allowed = [b for b, r in zip(bodies, served) if r.allowed]
    assert denied and allowed, "traffic mix regressed; reseed the bodies"
    drop = sorted(core.recorded_constraints(
        next(r for r in served if not r.allowed).message))[0]
    minus = [d for d in full
             if not (reader.is_constraint(d) and name_of(d) == drop)]
    candidate = core.load_candidate(minus, compile_cache_dir=cache_dir)
    # only-dropped-constraint denials: the clean would_allow population
    solely = [b for b, r in zip(bodies, served)
              if not r.allowed and core.recorded_constraints(r.message)
              == {drop}]
    assert solely, f"no admission denied solely by {drop}"
    return {"cache_dir": cache_dir, "full": full, "minus": minus,
            "drop": drop, "serving": serving, "candidate": candidate,
            "bodies": bodies, "denied": denied, "allowed": allowed,
            "solely": solely}


def _fields(resp):
    return (resp.allowed, resp.message, resp.code,
            tuple(resp.warnings), resp.uid, resp.retry_after_s)


# --- 1. THE serving-identity pin -------------------------------------------

def test_shadow_lane_never_alters_served_response(ctx):
    handler = ctx["serving"].handler
    baseline = [_fields(handler.handle(copy.deepcopy(b)))
                for b in ctx["bodies"]]
    lane = ShadowLane(ctx["candidate"], max_queue=256).start()
    try:
        with shadow.activate(lane):
            shadowed = [_fields(handler.handle(copy.deepcopy(b)))
                        for b in ctx["bodies"]]
        lane.drain()
    finally:
        lane.stop()
    assert shadowed == baseline
    assert lane.submitted == len(ctx["bodies"])
    assert lane.evaluated == lane.submitted and lane.lane_errors == 0


# --- 2. divergence detection -----------------------------------------------

def test_shadow_reports_would_allow(ctx):
    metrics = MetricsRegistry()
    rec = flightrec.FlightRecorder(capacity=64)
    lane = ShadowLane(ctx["candidate"], recorder=rec,
                      metrics=metrics).start()
    try:
        with shadow.activate(lane):
            for b in ctx["solely"]:
                ctx["serving"].handler.handle(copy.deepcopy(b))
        lane.drain()
    finally:
        lane.stop()
    # every solely-dropped-constraint deny flips to allow in the shadow
    assert lane.divergences["would_allow"] == len(ctx["solely"])
    snap = lane.snapshot()
    assert snap["divergence_rate"] > 0
    assert snap["recent_divergences"]
    for d in snap["recent_divergences"]:
        assert d["served"] == "deny" and d["shadow"] == "allow"
    assert metrics.get_counter(M.SHADOW_DIVERGENCE,
                               {"kind": "would_allow"}) == \
        len(ctx["solely"])
    # shadow verdicts land on the recorder's shadow stream, never the
    # serving one
    entries = rec.snapshot()["decisions"]
    assert entries and all(e["endpoint"] == "shadow" for e in entries)
    assert any(e.get("divergence") == "would_allow" and
               e.get("served") == "deny" for e in entries)


def test_shadow_reports_would_deny(ctx):
    # inverse deployment: serving = minus, candidate = full library
    lane = ShadowLane(ctx["serving"]).start()
    try:
        with shadow.activate(lane):
            for b in ctx["solely"]:
                resp = ctx["candidate"].handler.handle(copy.deepcopy(b))
                assert resp.allowed  # the minus library allows these
        lane.drain()
    finally:
        lane.stop()
    assert lane.divergences["would_deny"] == len(ctx["solely"])


def test_shadow_reports_would_error_and_swallows_lane_crash(ctx,
                                                            monkeypatch):
    # candidate whose review path errors per item -> would_error
    lane = ShadowLane(ctx["candidate"]).start()
    try:
        monkeypatch.setattr(
            ctx["candidate"].client, "review_batch",
            lambda reviews, **kw: [RuntimeError("boom")] * len(reviews))
        with shadow.activate(lane):
            for b in ctx["allowed"][:3]:
                ctx["serving"].handler.handle(copy.deepcopy(b))
        lane.drain()
        assert lane.divergences["would_error"] == 3
        assert lane.decisions["error"] == 3
    finally:
        lane.stop()
    # candidate whose review path RAISES: the whole batch is swallowed
    # into lane_errors — a candidate bug stays invisible to serving
    lane2 = ShadowLane(ctx["candidate"]).start()
    try:
        def _raise(reviews, **kw):
            raise RuntimeError("candidate down")

        monkeypatch.setattr(ctx["candidate"].client, "review_batch",
                            _raise)
        with shadow.activate(lane2):
            resp = ctx["serving"].handler.handle(
                copy.deepcopy(ctx["allowed"][0]))
            assert resp.allowed  # serving unaffected
        lane2.drain()
        assert lane2.lane_errors == 1 and lane2.evaluated == 0
    finally:
        lane2.stop()


# --- 3. backpressure --------------------------------------------------------

def test_shadow_full_queue_drops_oldest_never_blocks(ctx):
    metrics = MetricsRegistry()
    lane = ShadowLane(ctx["candidate"], max_queue=4,
                      metrics=metrics)  # no worker: the queue fills
    body = {"request": {"uid": "q", "userInfo": {"username": "u"}}}
    for i in range(10):
        assert lane.submit(dict(body), ValidationResponse(allowed=True))
    assert lane.submitted == 10
    assert lane.dropped == 6
    assert lane._queue.qsize() == 4
    assert metrics.counter_total(M.SHADOW_DROPPED) == 6
    assert metrics.get_gauge(M.SHADOW_QUEUE_DEPTH) == 4


def test_shadow_skips_unserved_decisions(ctx):
    lane = ShadowLane(ctx["candidate"])
    body = {"request": {"uid": "e"}}
    for code in (500, 504):
        assert not lane.submit(dict(body), ValidationResponse(
            allowed=False, code=code))
    assert lane.skipped == 2 and lane.submitted == 0
    assert lane._queue.qsize() == 0


# --- 4. promote / abort -----------------------------------------------------

def test_promote_applies_candidate_to_serving(ctx):
    # a fresh "serving" stack running the MINUS library (warm cache)
    serving = core.load_candidate(ctx["minus"],
                                  compile_cache_dir=ctx["cache_dir"])
    body = ctx["solely"][0]
    assert serving.handler.handle(copy.deepcopy(body)).allowed
    lane = ShadowLane(ctx["candidate"], serving_client=serving.client,
                      candidate_docs=ctx["full"])
    out = lane.promote()
    assert out["state"] == "promoted" and lane.state == "promoted"
    assert out["applied"]["templates"] == 3
    assert out["applied"]["constraints"] > 0
    assert "errors" not in out
    # the candidate library now SERVES: the admission flips to deny
    resp = serving.handler.handle(copy.deepcopy(body))
    assert not resp.allowed and ctx["drop"] in resp.message
    # an ended lane refuses traffic
    assert not lane.submit({"request": {}},
                           ValidationResponse(allowed=True))


def test_abort_stops_shadowing(ctx):
    lane = ShadowLane(ctx["candidate"]).start()
    out = lane.abort(reason="divergence SLO breached")
    assert out == {"state": "aborted",
                   "reason": "divergence SLO breached"}
    assert not lane.submit({"request": {}},
                           ValidationResponse(allowed=True))


# --- 5. the SLO objective ---------------------------------------------------

def test_shadow_slo_objective_sums_divergence_kinds(ctx):
    metrics = MetricsRegistry()
    lane = ShadowLane(ctx["candidate"], metrics=metrics).start()
    try:
        with shadow.activate(lane):
            for b in ctx["solely"][:2] + ctx["allowed"][:3]:
                ctx["serving"].handler.handle(copy.deepcopy(b))
        lane.drain()
    finally:
        lane.stop()
    assert lane.evaluated == 5
    obj = SLOObjective(SHADOW_OBJECTIVE)
    bad, total = obj.sample(metrics, 0.0)
    # bad sums ACROSS {kind} labelsets; total counts every shadowed
    # decision regardless of {decision} label
    assert bad == sum(lane.divergences.values()) == 2
    assert total == 5
    assert obj.target == SHADOW_OBJECTIVE["target"]


# --- 6. /debug/shadow -------------------------------------------------------

def _http(url, body=None):
    req = urllib.request.Request(
        url, data=(json.dumps(body).encode() if body is not None
                   else None),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_debug_shadow_endpoint(ctx):
    serving = core.load_candidate(ctx["minus"],
                                  compile_cache_dir=ctx["cache_dir"])
    lane = ShadowLane(ctx["candidate"], serving_client=serving.client,
                      candidate_docs=ctx["full"])
    srv = WebhookServer(port=0).start()
    base = f"http://127.0.0.1:{srv.port}/debug/shadow"
    try:
        with shadow.activate(lane):
            doc = _http(base)
            assert doc["state"] == "shadowing"
            assert set(doc) >= {"submitted", "evaluated", "divergences",
                                "divergence_rate", "recent_divergences"}
            try:
                _http(base, {"action": "bogus"})
                assert False, "expected 400"
            except urllib.error.HTTPError as e:
                assert e.code == 400
            out = _http(base, {"action": "promote"})
            assert out["state"] == "promoted"
            assert out["applied"]["templates"] == 3
        # lane uninstalled: the endpoint 404s like the other debug seams
        try:
            _http(base)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()


def test_debug_shadow_abort_endpoint(ctx):
    lane = ShadowLane(ctx["candidate"])
    srv = WebhookServer(port=0).start()
    base = f"http://127.0.0.1:{srv.port}/debug/shadow"
    try:
        with shadow.activate(lane):
            out = _http(base, {"action": "abort", "reason": "slo"})
            assert out == {"state": "aborted", "reason": "slo"}
    finally:
        srv.stop()


def test_slo_breach_auto_aborts_shadow_lane():
    """bind_slo: a RISING-EDGE breach of the shadow divergence
    objective aborts a shadowing lane; a promoted lane is immune, and
    a continued breach never re-fires (edge, not level)."""
    from gatekeeper_tpu.observability.slo import SLOEngine

    fake = {"t": 0.0}
    m = MetricsRegistry()
    eng = SLOEngine(
        m, objectives=[SHADOW_OBJECTIVE],
        tiers=[{"name": "page", "short_s": 60.0, "long_s": 300.0,
                "burn": 2.0}],
        clock=lambda: fake["t"], wall=lambda: 1_000_000.0 + fake["t"])
    lane = ShadowLane(runtime=None)  # never started: abort() is a
    lane.bind_slo(eng)               # state flip + no-op stop()
    eng.tick()  # t=0 baseline
    m.inc_counter("shadow_decisions_count", value=100.0)
    fake["t"] = 60.0
    out = eng.tick()
    assert not out["objectives"][0]["breach"]
    assert lane.state == "shadowing"
    # a divergent minute: 50/50 bad >> the 1% budget at burn 2.0
    m.inc_counter("shadow_divergence_count", {"kind": "verdict"},
                  value=50.0)
    m.inc_counter("shadow_decisions_count", value=50.0)
    fake["t"] = 120.0
    out = eng.tick()
    assert out["objectives"][0]["breach"]
    assert lane.state == "aborted"
    assert "slo auto-abort" in lane.abort_reason
    assert SHADOW_OBJECTIVE["name"] in lane.abort_reason
    # edge semantics: still breached on the next tick, but the hook
    # does not fire again (a lane resurrected by hand stays put)
    lane.state = "shadowing"
    m.inc_counter("shadow_divergence_count", {"kind": "verdict"},
                  value=50.0)
    m.inc_counter("shadow_decisions_count", value=50.0)
    fake["t"] = 121.0
    out = eng.tick()
    assert out["objectives"][0]["breach"]
    assert lane.state == "shadowing"


def test_slo_auto_abort_spares_promoted_lane():
    """The hook must never touch a lane that already promoted — the
    canary decision is done; only a shadowing lane may auto-abort."""
    from gatekeeper_tpu.observability.slo import SLOEngine

    fake = {"t": 0.0}
    m = MetricsRegistry()
    eng = SLOEngine(
        m, objectives=[SHADOW_OBJECTIVE],
        tiers=[{"name": "page", "short_s": 60.0, "long_s": 300.0,
                "burn": 2.0}],
        clock=lambda: fake["t"], wall=lambda: 1_000_000.0 + fake["t"])
    lane = ShadowLane(runtime=None)
    lane.bind_slo(eng)
    lane.state = "promoted"
    eng.tick()
    m.inc_counter("shadow_divergence_count", value=50.0)
    m.inc_counter("shadow_decisions_count", value=50.0)
    fake["t"] = 60.0
    out = eng.tick()
    assert out["objectives"][0]["breach"]
    assert lane.state == "promoted"

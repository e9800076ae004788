"""Staged host-pipeline executor: ordering, backpressure, degradation,
and the pipelined-vs-serial differential over the shipped library corpus
(the tier-1 guarantee that the overlap schedule changes NOTHING about
audit output)."""

import threading
import time

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.pipeline import (PipelineError, Stage, StagedPipeline,
                                     resolve_schedule)
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import load_library, make_cluster_objects


# --- executor unit behavior ------------------------------------------------

def test_executor_preserves_order_across_worker_pool():
    """Multi-worker stages must emit in INPUT order (the fold stage's
    bit-identity depends on it), regardless of completion order."""
    import random

    out = []

    def jitter(x):
        time.sleep(random.random() * 0.003)
        return None if x % 7 == 3 else x * 2  # None = dropped item

    run = StagedPipeline([
        Stage("jitter", jitter, workers=4, queue_cap=2),
        Stage("sink", lambda x: (out.append(x), None)[1], queue_cap=2),
    ]).run(range(150))
    assert out == [x * 2 for x in range(150) if x % 7 != 3]
    assert run.source_items == 150
    assert run.stage("jitter").items == 150
    assert run.stage("sink").items == len(out)


def test_executor_backpressure_bounds_queues_and_completes():
    """Tiny queue bounds: the pipeline must neither deadlock nor queue
    unboundedly — a fast producer stalls (bounded buffering = bounded
    RSS) instead of piling chunks up in front of a slow stage."""
    out = []
    run = StagedPipeline([
        Stage("slow", lambda x: (time.sleep(0.002), x)[1], queue_cap=1),
        Stage("sink", lambda x: (out.append(x), None)[1], queue_cap=1),
    ], source_cap=1).run(range(60))
    assert out == list(range(60))
    for s in run.stages:
        assert s.queue_highwater <= 1, (s.name, s.queue_highwater)
    # the source measurably stalled on the bounded queue (backpressure
    # reached all the way upstream)
    assert run.source_stall_s > 0


def test_executor_stage_error_propagates_without_hanging():
    def boom(x):
        if x == 5:
            raise ValueError("stage blew up")
        return x

    t0 = time.perf_counter()
    with pytest.raises(PipelineError) as ei:
        StagedPipeline([
            Stage("boom", boom, queue_cap=1),
            Stage("sink", lambda x: None, queue_cap=1),
        ]).run(range(1000))
    assert time.perf_counter() - t0 < 30  # unwound, not deadlocked
    assert ei.value.stage == "boom"
    assert isinstance(ei.value.__cause__, ValueError)


def test_executor_source_error_propagates():
    def src():
        yield 1
        raise RuntimeError("lister died")

    with pytest.raises(PipelineError) as ei:
        StagedPipeline([Stage("s", lambda x: None)]).run(src())
    assert ei.value.stage == "<source>"


def test_executor_overlap_is_measurable():
    """Two stages doing real (releasing-the-GIL) waits must overlap:
    stage busy sum > pipeline wall."""
    run = StagedPipeline([
        Stage("a", lambda x: (time.sleep(0.01), x)[1], queue_cap=2),
        Stage("b", lambda x: (time.sleep(0.01), None)[1], queue_cap=2),
    ]).run(range(20))
    assert run.stage_busy_sum() > run.wall_s * 1.3, (
        run.stage_busy_sum(), run.wall_s)


# --- schedule resolution ---------------------------------------------------

def test_schedule_resolution_one_core_degrades_to_serial(monkeypatch):
    import gatekeeper_tpu.pipeline as P

    monkeypatch.setattr(P, "effective_cpu_count", lambda: 1)
    assert P.resolve_schedule("auto", True) == "serial"
    monkeypatch.setattr(P, "effective_cpu_count", lambda: 8)
    assert P.resolve_schedule("auto", True) == "pipelined"
    # forced modes ignore core count; off and non-capable always serial
    monkeypatch.setattr(P, "effective_cpu_count", lambda: 1)
    assert P.resolve_schedule("on", True) == "pipelined"
    assert P.resolve_schedule("off", True) == "serial"
    assert P.resolve_schedule("on", False) == "serial"
    with pytest.raises(ValueError):
        P.resolve_schedule("sideways", True)


# --- audit-manager integration --------------------------------------------

def _library_client():
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[AUDIT_EP])
    load_library(client)
    return client, tpu


def _mgr(client, tpu, objects, **cfg_kw):
    cfg_kw.setdefault("exact_totals", False)
    cfg = AuditConfig(chunk_size=96, **cfg_kw)
    return AuditManager(
        client, lister=lambda: iter(objects), config=cfg,
        evaluator=ShardedEvaluator(tpu, make_mesh(), violations_limit=20),
    )


def _kept_signature(run):
    return {
        k: [(v.message, v.kind, v.name, v.namespace, v.enforcement_action)
            for v in vs]
        for k, vs in run.kept.items()
    }


def test_pipelined_vs_serial_differential_on_library_corpus():
    """Acceptance: bit-identical verdicts AND rendered messages between
    the serial eager-poll schedule and the staged pipeline, over the full
    shipped library against a mixed synthetic cluster."""
    client, tpu = _library_client()
    objects = make_cluster_objects(260, seed=11)
    for o in objects:
        if o.get("kind") == "Ingress":
            client.add_data(o)  # referential join inventory

    run_serial = _mgr(client, tpu, objects, pipeline="off").audit()
    # 2 flatten workers: covers the executor's order-restoring reorder
    # buffer on the real sweep path, not just the unit test
    mgr_pipe = _mgr(client, tpu, objects, pipeline="on",
                    pipeline_flatten_workers=2)
    run_pipe = mgr_pipe.audit()

    assert mgr_pipe.perf["pipelined"] == 1.0
    assert mgr_pipe.pipe_stats is not None
    assert run_serial.total_objects == run_pipe.total_objects == 260
    assert run_serial.total_violations == run_pipe.total_violations
    assert _kept_signature(run_serial) == _kept_signature(run_pipe)
    assert sum(run_serial.total_violations.values()) > 0  # non-vacuous

    # the built-in differential mode asserts the same equivalence inline
    mgr_diff = _mgr(client, tpu, objects, pipeline="differential")
    run_diff = mgr_diff.audit()
    assert mgr_diff.perf.get("pipeline_differential_ok") == 1.0
    assert run_diff.total_violations == run_serial.total_violations


@pytest.mark.slow  # tier-1 wall budget (PR 16): 27s; the non-exact
# pipelined-vs-serial differential above stays in tier 1.
def test_pipelined_exact_totals_matches_serial():
    """Exact-totals mode ships verdict bitmaps; the pipelined fold must
    count and render them identically."""
    client, tpu = _library_client()
    objects = make_cluster_objects(150, seed=29)
    for o in objects:
        if o.get("kind") == "Ingress":
            client.add_data(o)
    r_s = _mgr(client, tpu, objects, pipeline="off",
               exact_totals=True).audit()
    r_p = _mgr(client, tpu, objects, pipeline="on",
               exact_totals=True).audit()
    assert r_s.total_violations == r_p.total_violations
    assert _kept_signature(r_s) == _kept_signature(r_p)


def test_audit_one_core_detection_takes_serial_path(monkeypatch):
    """Acceptance: on a one-core host (or --pipeline=off) the audit runs
    the existing eager-poll serial schedule — no stage threads."""
    import gatekeeper_tpu.pipeline as P

    client, tpu = _library_client()
    objects = make_cluster_objects(80, seed=5)

    monkeypatch.setattr(P, "effective_cpu_count", lambda: 1)
    mgr = _mgr(client, tpu, objects, pipeline="auto")
    run = mgr.audit()
    assert mgr.perf["pipelined"] == 0.0
    assert mgr.pipe_stats is None
    assert run.total_objects == 80

    # multi-core auto flips to the pipeline, same output
    monkeypatch.setattr(P, "effective_cpu_count", lambda: 8)
    mgr2 = _mgr(client, tpu, objects, pipeline="auto")
    run2 = mgr2.audit()
    assert mgr2.perf["pipelined"] == 1.0
    assert run2.total_violations == run.total_violations

    mgr3 = _mgr(client, tpu, objects, pipeline="off")
    run3 = mgr3.audit()
    assert mgr3.perf["pipelined"] == 0.0
    assert run3.total_violations == run.total_violations


@pytest.mark.slow  # tier-1 wall budget (PR 15): the pipelined-vs-
# serial differential above keeps the schedule's bit-identity in
# tier-1; this backpressure stress (tiny queue bounds, 1-core) rides
# the slow lane
def test_audit_pipeline_backpressure_tiny_bounds():
    """Acceptance: queue bound of 1 + submit window of 1 over many small
    chunks — no deadlock, bounded in-flight depth, identical output."""
    client, tpu = _library_client()
    objects = make_cluster_objects(200, seed=3)
    mgr = _mgr(client, tpu, objects, pipeline="on",
               pipeline_queue_cap=1, submit_window=1)
    mgr.config.chunk_size = 16  # many chunks through the tiny windows
    done = []
    t = threading.Thread(target=lambda: done.append(mgr.audit()))
    t.start()
    t.join(timeout=300)
    assert not t.is_alive(), "pipelined audit deadlocked under tiny bounds"
    run = done[0]
    for name, s in mgr.pipe_stats["stages"].items():
        cap = 1 if name != "collect" else max(1, mgr.config.submit_window)
        assert s["queue_highwater"] <= cap, (name, s)
    serial = _mgr(client, tpu, objects, pipeline="off")
    serial.config.chunk_size = 16
    run_s = serial.audit()
    assert run.total_violations == run_s.total_violations
    assert _kept_signature(run) == _kept_signature(run_s)


def test_pipeline_stats_flow_into_metrics_registry():
    from gatekeeper_tpu.metrics import registry as M

    client, tpu = _library_client()
    objects = make_cluster_objects(60, seed=7)
    metrics = M.MetricsRegistry()
    cfg = AuditConfig(chunk_size=32, exact_totals=False, pipeline="on")
    mgr = AuditManager(
        client, lister=lambda: iter(objects), config=cfg,
        evaluator=ShardedEvaluator(tpu, make_mesh(), violations_limit=20),
        metrics=metrics,
    )
    mgr.audit()
    rendered = metrics.render()
    for stage in ("flatten", "dispatch", "collect", "fold_render"):
        assert metrics.get_gauge(M.PIPELINE_STAGE_SECONDS,
                                 {"stage": stage}) is not None, stage
    assert metrics.get_gauge(M.PIPELINE_DEVICE_WAIT) is not None
    assert M.PREFIX + M.PIPELINE_STAGE_OCCUPANCY in rendered
    assert metrics.get_counter(
        M.AUDIT_DURATION, None) == 0.0  # histogram, not counter
    assert M.PREFIX + M.AUDIT_DURATION in rendered


def test_lowering_fallback_counter_increments():
    """Satellite: a template the lowering cannot compile increments the
    fallback counter (visible in metrics + gator bench output)."""
    from gatekeeper_tpu.metrics import registry as M

    metrics = M.MetricsRegistry()
    tpu = TpuDriver(metrics=metrics)
    client = Client(target=K8sValidationTarget(), drivers=[tpu],
                    enforcement_points=[AUDIT_EP])
    # http.send is not lowerable: guaranteed interpreter fallback
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8sfallbackprobe"},
        "spec": {"crd": {"spec": {"names": {"kind": "K8sFallbackProbe"}}},
                 "targets": [{"target": "admission.k8s.gatekeeper.sh",
                              "rego": """
package k8sfallbackprobe
violation[{"msg": msg}] {
  resp := http.send({"method": "get", "url": "http://example.invalid"})
  resp.status_code != 200
  msg := "probe failed"
}
"""}]},
    })
    assert metrics.counter_total(M.LOWERING_FALLBACK) == 1
    stats = tpu.lowering_stats()
    assert stats["fallback"] == 1 and stats["lowered"] == 0
    assert stats["fallback_fraction"] == 1.0
    assert "K8sFallbackProbe" in stats["fallback_kinds"]

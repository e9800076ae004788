"""Observability overhead bench: instrumented vs bare serving + sweep.

Measures the cost of the FULL observability stack — metrics registry
(bucketed histograms + exemplars), per-template cost attribution, the
admission flight recorder, and the keep-all span tracer — against the
bare path, on both enforcement surfaces:

- **webhook**: ``ValidationHandler.handle`` over an admission burst
  (the per-request seams: duration histogram, decision record, request
  spans, query_batch attribution);
- **sweep**: one library-corpus audit pass (the per-chunk seams:
  dispatch/flatten attribution, chunk spans, pipeline gauges);
- **degradation engine**: the bare webhook path with the targeted
  degradation maps ARMED but healthy (``--slo-degradation on``, a
  DegradationRegistry installed, an SLOEngine holding the default
  maps, nothing active) — the per-request cost of the
  ``degradation_active()`` checks the hot paths grew.

Passes interleave bare/instrumented (ABAB...) so clock drift and cache
warmth cancel, and the comparison uses medians.  Appends a history
entry to BENCH_TPU.json (``kind: obs_overhead``); the tier-1 smoke
(tests/test_obs_overhead.py) runs ``--smoke`` and asserts the serial
1-core overhead bound.

Usage: python tools/bench_obs_overhead.py [--objects N] [--passes K]
       [--smoke] [--no-append]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_setup(n_objects: int):
    from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.drivers.cel_driver import CELDriver
    from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
    from gatekeeper_tpu.target.target import K8sValidationTarget
    from gatekeeper_tpu.utils.synthetic import (load_library,
                                                make_cluster_objects)

    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    load_library(client)
    objects = make_cluster_objects(n_objects, seed=41)
    return client, tpu, objects


def _bodies(objects):
    from gatekeeper_tpu.utils.unstructured import gvk_of

    out = []
    for i, obj in enumerate(objects):
        g, v, k = gvk_of(obj)
        out.append({
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "request": {"uid": f"b{i}", "operation": "CREATE",
                        "kind": {"group": g, "version": v, "kind": k},
                        "name": (obj.get("metadata") or {}).get(
                            "name", ""),
                        "namespace": (obj.get("metadata") or {}).get(
                            "namespace", ""),
                        "userInfo": {"username": "bench"},
                        "object": obj},
        })
    return out


def _instrumented():
    """(contextmanager, registry): the full production observability
    stack, freshly installed."""
    import contextlib

    from gatekeeper_tpu.metrics.registry import MetricsRegistry
    from gatekeeper_tpu.observability import costattr, flightrec, tracing

    @contextlib.contextmanager
    def ctx():
        m = MetricsRegistry()
        attr = costattr.CostAttribution(metrics=m)
        rec = flightrec.FlightRecorder(metrics=m)
        tracer = tracing.Tracer(seed=0, ring_capacity=256)
        with tracing.activate(tracer), costattr.activate(attr), \
                flightrec.activate(rec):
            yield m
    return ctx


def run(n_objects: int = 200, passes: int = 5,
        append: bool = True) -> dict:
    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.metrics.registry import MetricsRegistry
    from gatekeeper_tpu.parallel.sharded import (ShardedEvaluator,
                                                 make_mesh)
    from gatekeeper_tpu.webhook.policy import ValidationHandler

    client, tpu, objects = build_setup(n_objects)
    bodies = _bodies(objects[: max(20, n_objects // 4)])
    mgr = AuditManager(
        client, lister=lambda: iter(objects),
        config=AuditConfig(chunk_size=64, exact_totals=False,
                           pipeline="off"),
        evaluator=ShardedEvaluator(tpu, make_mesh(),
                                   violations_limit=20))
    bare_handler = ValidationHandler(client)
    ctx = _instrumented()

    # warmup: vocab + jit compile outside every timed pass
    mgr.audit()
    for b in bodies[:4]:
        bare_handler.handle(b)

    from gatekeeper_tpu.observability import slo as slo_mod
    from gatekeeper_tpu.resilience import overload as ovl

    bare_web, inst_web, bare_sweep, inst_sweep = [], [], [], []
    deg_web = []
    # round 0 is a discarded warmup (lazy imports, first-touch caches on
    # BOTH variants) — medians are robust but the noise-spread guard the
    # smoke keys on must not see the one-time costs
    for rnd in range(passes + 1):
        t0 = time.perf_counter()
        for b in bodies:
            bare_handler.handle(b)
        bare_web.append(time.perf_counter() - t0)

        with ctx() as m:
            inst_handler = ValidationHandler(client, metrics=m)
            t0 = time.perf_counter()
            for b in bodies:
                inst_handler.handle(b)
            inst_web.append(time.perf_counter() - t0)

        # degradation-engine lane: registry installed + engine holding
        # the default maps, all objectives healthy — measures only the
        # armed checks (is_active reads) on the bare serving path
        reg = ovl.DegradationRegistry()
        eng = slo_mod.SLOEngine(MetricsRegistry(), degradations=reg)
        eng.tick()
        with ovl.activate_degradations(reg):
            t0 = time.perf_counter()
            for b in bodies:
                bare_handler.handle(b)
            deg_web.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        mgr.audit()
        bare_sweep.append(time.perf_counter() - t0)

        with ctx() as m:
            mgr.metrics = m
            t0 = time.perf_counter()
            mgr.audit()
            inst_sweep.append(time.perf_counter() - t0)
            mgr.metrics = None
        if rnd == 0:
            bare_web.clear()
            inst_web.clear()
            bare_sweep.clear()
            inst_sweep.clear()
            deg_web.clear()

    def med(xs):
        return statistics.median(xs)

    def spread(xs):
        # median absolute deviation relative to the median: how reliable
        # the median comparison is.  A single outlier pass (GC, page
        # cache, noisy neighbor) moves a max-min range wildly but barely
        # moves the MAD — and the comparison itself uses medians.
        m = med(xs)
        if not m:
            return 0.0
        return statistics.median(abs(x - m) for x in xs) / m

    import jax

    entry = {
        "kind": "obs_overhead",
        "note": "instrumented (metrics+attribution+flightrec+tracer) "
                "vs bare, serial schedule",
        "date": time.strftime("%Y-%m-%d"),
        "platform": jax.devices()[0].platform,
        "host_cpus": os.cpu_count(),
        "objects": n_objects,
        "admissions": len(bodies),
        "passes": passes,
        "webhook_bare_s": round(med(bare_web), 4),
        "webhook_instrumented_s": round(med(inst_web), 4),
        "webhook_overhead_pct": round(
            100.0 * (med(inst_web) / med(bare_web) - 1.0), 2),
        "sweep_bare_s": round(med(bare_sweep), 4),
        "sweep_instrumented_s": round(med(inst_sweep), 4),
        "sweep_overhead_pct": round(
            100.0 * (med(inst_sweep) / med(bare_sweep) - 1.0), 2),
        # min-of-passes: scheduler noise strictly ADDS time, so the
        # fastest pass of each variant is the cleanest-machine estimate
        # — the tier-1 smoke asserts on these (median ratios jitter
        # several % on a busy 1-core host; minima are stable)
        "webhook_overhead_min_pct": round(
            100.0 * (min(inst_web) / min(bare_web) - 1.0), 2),
        "sweep_overhead_min_pct": round(
            100.0 * (min(inst_sweep) / min(bare_sweep) - 1.0), 2),
        # armed-but-healthy degradation maps vs bare: the marginal cost
        # of the degradation_active() reads on the serving path
        "webhook_degradation_armed_s": round(med(deg_web), 4),
        "degradation_overhead_pct": round(
            100.0 * (med(deg_web) / med(bare_web) - 1.0), 2),
        "degradation_overhead_min_pct": round(
            100.0 * (min(deg_web) / min(bare_web) - 1.0), 2),
        "noise_spread_pct": round(100.0 * max(
            spread(bare_web), spread(bare_sweep)), 2),
    }
    if append:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from bench import bench_history_append

        bench_history_append(entry)
    return entry


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--objects", type=int, default=200)
    p.add_argument("--passes", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="small corpus, no history append (the tier-1 "
                        "smoke shape)")
    p.add_argument("--no-append", action="store_true")
    args = p.parse_args()
    if args.smoke:
        entry = run(n_objects=120, passes=3, append=False)
    else:
        entry = run(n_objects=args.objects, passes=args.passes,
                    append=not args.no_append)
    print(json.dumps(entry, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

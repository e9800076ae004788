"""Webhook serving-layer load test.

Drives the real WebhookServer (TLS off) with concurrent AdmissionReview
POSTs over persistent connections, through the full stack: HTTP parse →
ValidationHandler → Batcher microbatch lane → device verdict grids →
deny/warn partition.  Reports throughput + a latency histogram and writes
WEBHOOK_LOAD.json at the repo root.  Server and load generator share ONE
process (a chip belongs to one process), on whatever platform JAX
selects; the record carries it.

    python tools/loadtest_webhook.py [n_requests] [conc]

The reference's concurrency model is goroutine-per-request capped by
--max-serving-threads (pkg/webhook/policy.go:116-120); here the cap is the
batch window — see the batch-size distribution in the output.
"""

from __future__ import annotations

import http.client
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_server():
    from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.drivers.cel_driver import CELDriver
    from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
    from gatekeeper_tpu.target.target import K8sValidationTarget
    from gatekeeper_tpu.utils.synthetic import load_library
    from gatekeeper_tpu.webhook.policy import Batcher, ValidationHandler
    from gatekeeper_tpu.webhook.server import WebhookServer

    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    nt, nc = load_library(client)
    batcher = Batcher(client, window_s=0.002, max_batch=64).start()
    handler = ValidationHandler(client, batcher=batcher)
    # warm EVERY grid-lane pad bucket (9->16, 17->32, 33->64): shapes
    # otherwise compile lazily inside the first saturated lane
    # (seconds-long P99 spikes that say nothing about steady state)
    from gatekeeper_tpu.target.review import AugmentedUnstructured
    from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
    warm = [AugmentedUnstructured(
        object=json.loads(make_body(i))["request"]["object"],
        source=SOURCE_ORIGINAL) for i in range(batcher.max_batch)]
    n = max(1, batcher.small_batch + 1)
    while n <= batcher.max_batch:
        client.review_batch(warm[:n])
        n *= 2
    client.review_batch(warm)
    srv = WebhookServer(validation_handler=handler, port=0,
                        readiness_check=lambda: True).start()
    return srv, batcher, nt, nc


def make_body(i: int) -> bytes:
    from gatekeeper_tpu.utils.synthetic import make_cluster_objects

    obj = make_cluster_objects(1, seed=i)[0]
    from gatekeeper_tpu.utils.unstructured import gvk_of

    g, v, k = gvk_of(obj)
    return json.dumps({
        "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
        "request": {
            "uid": f"u{i}", "operation": "CREATE",
            "kind": {"group": g, "version": v, "kind": k},
            "name": obj["metadata"].get("name", ""),
            "namespace": obj["metadata"].get("namespace", ""),
            "userInfo": {"username": "load"},
            "object": obj,
        },
    }).encode()


def run_load(port: int, bodies: list, n: int, conc: int) -> dict:
    """Drive ``n`` requests over ``conc`` persistent connections; return
    a stats dict (latency percentiles + throughput + histogram)."""
    latencies: list = []
    denied = [0]
    lock = threading.Lock()
    per_worker = n // conc
    errors: list = []

    def worker(wid: int):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        local = []
        local_denied = 0
        try:
            for i in range(per_worker):
                body = bodies[(wid * per_worker + i) % len(bodies)]
                t0 = time.perf_counter()
                c.request("POST", "/v1/admit", body=body,
                          headers={"Content-Type": "application/json"})
                resp = json.loads(c.getresponse().read())
                local.append(time.perf_counter() - t0)
                if not resp["response"]["allowed"]:
                    local_denied += 1
        except Exception as e:
            with lock:
                errors.append(f"worker {wid}: {type(e).__name__}: {e}")
        finally:
            c.close()
        with lock:
            latencies.extend(local)
            denied[0] += local_denied

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(conc)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0

    lat_ms = sorted(x * 1000 for x in latencies)
    if not lat_ms:
        return {"errors": errors, "requests": 0, "concurrency": conc,
                "elapsed_s": round(elapsed, 3)}

    def pct(p):
        return lat_ms[min(len(lat_ms) - 1, int(p / 100 * len(lat_ms)))]

    hist_edges = [1, 2, 5, 10, 20, 50, 100, 200, 500, 1000]
    hist = {f"le_{e}ms": sum(1 for x in lat_ms if x <= e)
            for e in hist_edges}
    return {
        "errors": errors,
        "requests": len(lat_ms),
        "concurrency": conc,
        "elapsed_s": round(elapsed, 3),
        "requests_per_s": round(len(lat_ms) / elapsed, 1),
        "denied": denied[0],
        "p50_ms": round(pct(50), 2),
        "p90_ms": round(pct(90), 2),
        "p99_ms": round(pct(99), 2),
        "max_ms": round(lat_ms[-1], 2) if lat_ms else 0,
        "mean_ms": round(statistics.mean(lat_ms), 2) if lat_ms else 0,
        "histogram": hist,
    }


def warmup(port: int, bodies: list, k: int = 8) -> None:
    conn = http.client.HTTPConnection("127.0.0.1", port)
    for i in range(k):
        conn.request("POST", "/v1/admit", body=bodies[i % len(bodies)],
                     headers={"Content-Type": "application/json"})
        conn.getresponse().read()
    conn.close()


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
    conc = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    srv, batcher, nt, nc = build_server()
    print(f"server on :{srv.port}; library {nt} templates / {nc} "
          f"constraints", file=sys.stderr)
    bodies = [make_body(i) for i in range(256)]
    warmup(srv.port, bodies)

    # lane 1: true per-request latency — one connection, no batch window
    # (the batcher still runs but a lone request never waits: the window
    # opens when the first request of a batch arrives)
    print("lane n1 (sequential, N=1)...", file=sys.stderr)
    lane_n1 = run_load(srv.port, bodies, min(n, 400), 1)
    # lane 2: moderate concurrency (a small cluster's admission load)
    print("lane conc8...", file=sys.stderr)
    lane_c8 = run_load(srv.port, bodies, n, 8)
    # lane 3: saturation (r2-comparable: 64 connections)
    print(f"lane conc{conc}...", file=sys.stderr)
    lane_sat = run_load(srv.port, bodies, n, conc)
    batcher.stop()
    srv.stop()
    import jax

    out = {
        "metric": "webhook serving load",
        "platform": jax.devices()[0].platform,
        "host_cpus": os.cpu_count(),
        "batch_window_ms": 2.0,
        "n1": lane_n1,
        "conc8": lane_c8,
        f"conc{conc}": lane_sat,
        "server": "stdlib ThreadingHTTPServer (thread-per-connection; the "
                  "Batcher coalesces concurrent reviews so handler threads "
                  "block on the shared device pass, not on per-request "
                  "evaluation)",
    }
    print(json.dumps(out, indent=1))
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    with open(os.path.join(root, "WEBHOOK_LOAD.json"), "w") as f:
        f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()

"""Traffic the replay, shadow and QoS tests drive through the program.

``library_docs`` / ``admission_bodies`` / ``serve_and_record`` give
``tests/test_replay.py`` and ``tests/test_shadow.py`` their recorded
corpus: a real ``ValidationHandler`` with a capture-mode flight recorder
answers synthetic admissions over a slice of the shipped library, and
the sink is what `gator replay` reads.  ``drive_tenant_mix`` is the
multi-tenant closed-loop client of ``tests/test_qos.py``.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

from gatekeeper_tpu.fuzz import corpus as fuzz_corpus
from gatekeeper_tpu.fuzz.soak import _library_docs
from gatekeeper_tpu.observability import flightrec
from gatekeeper_tpu.replay import core
from gatekeeper_tpu.utils.synthetic import make_cluster_objects


def library_docs(keep: int = 5) -> list:
    """The first ``keep`` shipped library templates + their sample
    constraints, as unstructured docs (the `--candidate` input shape);
    5 bounds the compile wall."""
    return _library_docs(keep)


def admission_bodies(n: int, seed: int = 7) -> list:
    """AdmissionReview bodies over the synthetic cluster mix (CREATE of
    the object, a non-gatekeeper user)."""
    return fuzz_corpus.admission_bodies(make_cluster_objects(n, seed=seed),
                                        seed=seed, prefix="replay")


def serve_and_record(docs: list, bodies: list, sink_path: str,
                     cache_dir: str) -> dict:
    """The serving pass: a real ValidationHandler + capture-mode flight
    recorder answers every body; the sink becomes the replay corpus."""
    runtime = core.load_candidate(docs, compile_cache_dir=cache_dir)
    rec = flightrec.FlightRecorder(capacity=64, sink_path=sink_path,
                                   capture=True)
    with flightrec.activate(rec):
        denies = sum(not runtime.handler.handle(body).allowed
                     for body in bodies)
    rec.close()
    gc = getattr(runtime.driver, "gen_coord", None)
    if gc is not None:
        gc.stop()
    return {"served": len(bodies), "denies": denies}


def drive_tenant_mix(port: int, plan: list, bodies: dict,
                     timeout_s: float = 60.0) -> dict:
    """Offer a multi-tenant load mix against a running webhook and
    report per-tenant latency/shed stats.

    ``plan``: [{"name": tenant, "conc": N, "n": total requests}, ...] —
    every tenant's workers run concurrently (the contention IS the
    measurement); ``bodies``: {tenant: [request bytes, ...]}.  Returns
    {tenant: {requests, accepted, shed, p50_ms, p99_ms, errors}} —
    accepted-request latency only, sheds (a 429, or an allow that
    carries the overload warning) counted separately."""
    stats = {t["name"]: {"lat": [], "shed": 0, "errors": []}
             for t in plan}
    lock = threading.Lock()

    def worker(tenant: str, wid: int, conc: int, n: int):
        tb = bodies[tenant]
        st = stats[tenant]
        c = http.client.HTTPConnection("127.0.0.1", port,
                                       timeout=timeout_s)
        try:
            for i in range(max(1, n // conc)):
                body = tb[(wid + i * conc) % len(tb)]
                t0 = time.perf_counter()
                c.request("POST", "/v1/admit", body=body,
                          headers={"Content-Type": "application/json"})
                r = json.loads(c.getresponse().read())["response"]
                dt = (time.perf_counter() - t0) * 1000
                shed = (r.get("status", {}).get("code") == 429
                        or any("overload" in w
                               for w in r.get("warnings", [])))
                with lock:
                    if shed:
                        st["shed"] += 1
                    else:
                        st["lat"].append(dt)
        except Exception as e:
            with lock:
                st["errors"].append(f"{wid}: {type(e).__name__}: {e}")
        finally:
            c.close()

    threads = [threading.Thread(target=worker,
                                args=(t["name"], w, t["conc"], t["n"]))
               for t in plan for w in range(t["conc"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out = {}
    for name, st in stats.items():
        sv = sorted(st["lat"])

        def pct(p):
            return round(sv[min(len(sv) - 1,
                                int(p / 100 * len(sv)))], 2) if sv else 0.0

        out[name] = {"requests": len(sv) + st["shed"],
                     "accepted": len(sv), "shed": st["shed"],
                     "p50_ms": pct(50), "p99_ms": pct(99),
                     "errors": st["errors"]}
    return out

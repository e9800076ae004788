"""Chip smoke: the audit sweep and the served webhook, once, on the TPU.

    python chip_smoke.py                 # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny    # pre-flight, CPU

Drives the system's main path through the objects ``python -m
gatekeeper_tpu`` wires, in ONE process (a chip belongs to one process:
nothing is spawned once JAX is touched, and no probe child before), at
deployment width — the ENTIRE shipped library (46 templates / 46
constraints, none on the interpreter fallback), chunk 32,768, k = 20 —
with depth cut to 8 chunks (``--objects`` raises it):

1. kernels    both Pallas epilogue kernels compiled by Mosaic at
              [46, 32768] and [46, 131072], k = 20, bit-equal to the
              XLA twin (``topk_violations`` + sums);
2. audit      262,144 synthetic cluster objects streamed as RawJSON from
              a JSONL spill through ``ShardedEvaluator(tpu, make_mesh(1))``
              -> ``AuditManager.audit()`` (the benchmark's audit shape:
              violating-object totals, kept violations rendered).  Pass 1
              (warm pass + compile) is set-up; pass 2 must trace nothing,
              drop or retry no chunk, and flatten on the raw C lane;
3. verdicts   for the first 1,024 objects the (constraint, object)
              violation set of the device sweep equals the interpreter's
              (``client.review``);
4. admission  the same process serves /v1/admit over real HTTP: 512
              AdmissionReviews over 32 persistent connections, then the
              first 64 bodies sequentially; at least one ``lane=grid``
              flush with batch > 8, and every grid-lane answer equals
              the interpreter-lane answer for the same body;
5. residency  100,000 snapshot rows under ``DeviceResidency(mode="auto")``:
              full pass, clean tick, 1%-churn tick; ``auto`` must have
              promoted, and the tick must equal a fresh relist
              (``audit_resync`` differential);
6. mesh       with more than one device: the audit phase again on
              ``make_mesh()`` — totals and kept sets equal the 1-device
              run, every device holds a shard of the packed columns.

Any failed check fails the run.  Without a TPU the script exits non-zero
before building anything; ``--tiny`` is the only CPU mode (a pre-flight
of the control flow at toy sizes, with the Pallas kernels interpreted
because it asks) and is never a chip pass.  Times printed here are smoke
timings, not benchmark results.  Records go to stdout and to ``--out``
(default ``chiprun_out/chip_smoke/``); the last stdout line of a chip
pass is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import http.client
import json
import os
import sys
import tempfile
import threading
import time

FULL = dict(objects=262_144, chunk=32_768, sample=1_024, requests=512,
            conns=32, sequential=64, rows=100_000,
            kernel_shapes=((46, 32_768), (46, 131_072)))
TINY = dict(objects=2_048, chunk=1_024, sample=256, requests=64,
            conns=16, sequential=16, rows=1_000,
            kernel_shapes=((46, 4_096),))
K = 20  # --constraint-violations-limit (the reference default)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Recorder:
    """Every record carries the device; all of them land in one file.
    Each phase also reports the executables it asked XLA for, and how
    many of those the persistent cache answered (the rest compiled)."""

    def __init__(self, out_dir: str, device: dict):
        import jax.monitoring

        os.makedirs(out_dir, exist_ok=True)
        self.path = os.path.join(
            out_dir, f"chip_smoke_{int(time.time())}_{os.getpid()}.jsonl")
        self.device = device
        self.phases: dict = {}
        self.xla = {"programs": 0, "cache_hits": 0, "compile_s": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.xla["programs"] += 1
            self.xla["compile_s"] += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.xla["cache_hits"] += 1

    def emit(self, record: dict) -> None:
        line = json.dumps({**self.device, **record})
        print(line, flush=True)
        with open(self.path, "a") as f:
            f.write(line + "\n")

    @contextlib.contextmanager
    def phase(self, name: str):
        """Times one phase and emits its record: the body fills the
        yielded dict; an exception emits a failed record and goes on up."""
        t0 = time.perf_counter()
        before = dict(self.xla)
        record: dict = {}
        try:
            yield record
        except Exception as e:
            self.phases[name] = "fail"
            self.emit({"phase": name, "pass": False,
                       "error": f"{type(e).__name__}: {e}"[:2000],
                       "smoke_wall_s": round(time.perf_counter() - t0, 2)})
            raise
        self.phases[name] = "pass"
        self.emit({"phase": name, "pass": True, **record,
                   "xla_programs": self.xla["programs"] - before["programs"],
                   "xla_cache_hits": (self.xla["cache_hits"]
                                      - before["cache_hits"]),
                   "xla_compile_s": round(self.xla["compile_s"]
                                          - before["compile_s"], 1),
                   "smoke_wall_s": round(time.perf_counter() - t0, 2)})


# --- phase 1: kernels ------------------------------------------------------

def build_client():
    """TpuDriver + CELDriver -> Client with the entire shipped library."""
    from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.drivers.cel_driver import CELDriver
    from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
    from gatekeeper_tpu.target.target import K8sValidationTarget
    from gatekeeper_tpu.utils.synthetic import load_library

    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(),
                    drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    nt, nc = load_library(client)
    fb = tpu.fallback_kinds()
    check(not fb, f"library templates fell back to interpreter: {fb}")
    return client, tpu, nt, nc


def spill_corpus(client, n: int, spill_fd: int, seed: int = 0) -> int:
    """Stream ``n`` synthetic cluster objects to the JSONL spill (the
    reference's disk list-cache) and sync the Ingresses into the
    inventory for the referential join.  Returns the Ingress count."""
    from gatekeeper_tpu.utils.synthetic import iter_cluster_objects

    n_ing = 0
    with os.fdopen(spill_fd, "wb") as f:
        for o in iter_cluster_objects(n, seed):
            if o.get("kind") == "Ingress":
                client.add_data(o)  # referential inventory sync
                n_ing += 1
            f.write(json.dumps(o, separators=(",", ":")).encode())
            f.write(b"\n")
    return n_ing


def spill_lister(path: str, limit: int = 0):
    """A lister streaming the spill as RawJSON (``limit`` > 0: only its
    first ``limit`` objects)."""
    from gatekeeper_tpu.utils.rawjson import RawJSON

    def lister():
        with open(path, "rb") as f:
            for i, line in enumerate(f):
                if limit and i >= limit:
                    return
                yield RawJSON(line.rstrip(b"\n"))

    return lister


def phase_kernels(shapes, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gatekeeper_tpu.ops.pallas_topk import (
        fused_fold_pallas, topk_violations_counts_pallas)
    from gatekeeper_tpu.parallel.sharded import topk_violations

    @jax.jit
    def xla_fold(g, m):
        masked = g & m
        idx, valid = topk_violations(masked, K)
        return (jnp.where(valid, idx, 0), valid,
                jnp.sum(masked, axis=1, dtype=jnp.int32),
                jnp.sum(m, axis=1, dtype=jnp.int32))

    out = []
    for c, n in shapes:
        rng = np.random.default_rng(n)
        grid = rng.random((c, n)) < 0.02
        mask = rng.random((c, n)) < 0.7
        grid[0] = True          # full row
        grid[1] = False         # clean row
        mask[2] = False         # out-of-scope row
        grid[3] = False
        grid[3, -1] = True      # lone hit in the last lane
        g, m = jnp.asarray(grid), jnp.asarray(mask)
        ones = jnp.ones_like(m)
        ref_g = [np.asarray(a) for a in xla_fold(g, ones)]
        ref_m = [np.asarray(a) for a in xla_fold(g, m)]
        got_g = [np.asarray(a) for a in jax.jit(
            lambda a: topk_violations_counts_pallas(
                a, K, interpret=interpret))(g)]
        got_m = [np.asarray(a) for a in jax.jit(
            lambda a, b: fused_fold_pallas(
                a, b, K, interpret=interpret))(g, m)]
        for name, ref, got in (("epilogue", ref_g[:3], got_g),
                               ("fused_fold", ref_m, got_m)):
            for r, x in zip(ref, got):
                check(np.array_equal(r, x),
                      f"pallas {name} != XLA twin at [{c}, {n}]")
        out.append([c, n])
    return {"shapes": out, "k": K,
            "compiled_by": "interpreter" if interpret else "mosaic",
            "bit_equal_to_xla": True}


# --- shared set-up -----------------------------------------------------------

def kept_canonical(run) -> dict:
    return {key: sorted((v.message, v.kind, v.namespace, v.name)
                        for v in vs) for key, vs in run.kept.items()}


# --- phase 2 / 6: audit ------------------------------------------------------

def phase_audit(client, evaluator, metrics, lister, n: int, chunk: int):
    """Set-up pass + checked pass on one evaluator.  Returns (record,
    the checked pass's AuditRun)."""
    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.metrics import registry as M

    mgr = AuditManager(
        client, lister=lister, evaluator=evaluator,
        config=AuditConfig(violations_limit=K, chunk_size=chunk,
                           exact_totals=False))
    t0 = time.perf_counter()
    evaluator.warm_pass(client.constraints(), lister(), chunk)
    warm_s = time.perf_counter() - t0
    run1 = mgr.audit()
    setup_s = time.perf_counter() - t0
    check(not run1.incomplete, "set-up pass incomplete "
          f"({run1.failed_chunks} dropped, {run1.retried_chunks} retried)")

    traces0 = evaluator.trace_count
    lanes0 = metrics.counter_total(M.FLATTEN_LANE)
    raw0 = metrics.counter_total(M.FLATTEN_LANE, {"lane": "raw"})
    evaluator.perf_reset()
    mgr.perf = {}
    t0 = time.perf_counter()
    run = mgr.audit()
    pass_s = time.perf_counter() - t0
    new_traces = evaluator.trace_count - traces0
    flattens = metrics.counter_total(M.FLATTEN_LANE) - lanes0
    raw = metrics.counter_total(M.FLATTEN_LANE, {"lane": "raw"}) - raw0
    check(not run.incomplete and run.failed_chunks == 0
          and run.retried_chunks == 0,
          f"checked pass: incomplete={run.incomplete} "
          f"failed={run.failed_chunks} retried={run.retried_chunks}")
    check(new_traces == 0, f"{new_traces} compiles inside the checked pass")
    check(run.total_objects == n, f"swept {run.total_objects} of {n}")
    check(flattens > 0 and raw == flattens,
          f"flatten lane: {raw:.0f} of {flattens:.0f} chunks on 'raw'")
    check(run.total_violations == run1.total_violations
          and kept_canonical(run) == kept_canonical(run1),
          "two passes over one corpus disagree")
    violations = sum(run.total_violations.values())
    check(violations > 0, "the sweep found no violation at all")
    rec = {
        "objects": n, "chunk": chunk, "constraints": len(run.kept),
        "n_devices": run.n_devices,
        "setup_s": round(setup_s, 2), "warm_pass_s": round(warm_s, 2),
        "checked_pass_smoke_s": round(pass_s, 2),
        "new_traces": new_traces, "incomplete": run.incomplete,
        "failed_chunks": run.failed_chunks,
        "retried_chunks": run.retried_chunks,
        "collect_fallbacks": int(evaluator.perf.get(
            "collect_fallbacks", 0)),
        "schedule": "pipelined" if mgr.perf.get("pipelined") else "serial",
        "flatten_lane": "raw", "violations": violations,
        "kept": sum(len(v) for v in run.kept.values()),
        "h2d_mb": round(evaluator.perf.get("wire_bytes", 0) / 1e6, 1),
        "d2h_kb": round(evaluator.perf.get("d2h_bytes", 0) / 1e3, 1),
    }
    return rec, run


# --- phase 3: verdicts vs the interpreter ------------------------------------

def phase_verdicts(client, evaluator, sample: list) -> dict:
    from gatekeeper_tpu.apis.constraints import AUDIT_EP
    from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
    from gatekeeper_tpu.parallel.sharded import (make_kind_router,
                                                 violation_rows)
    from gatekeeper_tpu.target.review import AugmentedUnstructured
    from gatekeeper_tpu.utils.rawjson import peek_kind

    constraints = [c for c in client.constraints()
                   if c.actions_for(AUDIT_EP)]
    router = make_kind_router(constraints)
    groups: dict = {}
    for i, obj in enumerate(sample):
        g = router(peek_kind(obj))
        if g:
            groups.setdefault(g, []).append(i)
    device: set = set()
    for g, idxs in groups.items():
        cons_g = [c for c in constraints if c.kind in g]
        chunk = [sample[i] for i in idxs]
        swept = evaluator.sweep(cons_g, chunk, return_bits=True)
        check(set(swept) == {c.kind for c in cons_g},
              f"not evaluated on the device: "
              f"{sorted({c.kind for c in cons_g} - set(swept))}")
        for kcons, _idx, _valid, _counts, hits in swept.values():
            for ci, con in enumerate(kcons):
                for oi in violation_rows(hits, ci, len(chunk)):
                    device.add((con.key(), idxs[int(oi)]))
    interp: set = set()
    for i, obj in enumerate(sample):
        resp = client.review(
            AugmentedUnstructured(object=json.loads(obj.raw),
                                  source=SOURCE_ORIGINAL),
            enforcement_point=AUDIT_EP)
        for r in resp.results():
            con = r.constraint or {}
            interp.add(((con.get("kind"),
                         (con.get("metadata") or {}).get("name")), i))
    check(device == interp,
          f"device sweep != interpreter on {len(sample)} objects: "
          f"{len(device - interp)} device-only, "
          f"{len(interp - device)} interpreter-only, e.g. "
          f"{sorted(device ^ interp)[:3]}")
    check(len(interp) > 0, "the sample holds no violation")
    return {"objects": len(sample),
            "violating_pairs": len(interp), "equal": True}


# --- phase 4: admission over HTTP --------------------------------------------

def _answer(resp: dict) -> tuple:
    r = resp["response"]
    st = r.get("status") or {}
    return (bool(r["allowed"]), st.get("code"),
            frozenset((st.get("message") or "").split("\n")),
            frozenset(r.get("warnings") or ()))


def _admission_body(i: int) -> bytes:
    """The AdmissionReview of a CREATE of synthetic cluster object ``i``."""
    from gatekeeper_tpu.utils.synthetic import make_cluster_objects
    from gatekeeper_tpu.utils.unstructured import gvk_of

    obj = make_cluster_objects(1, seed=i)[0]
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


def phase_admission(client, metrics, seed: int, requests: int, conns: int,
                    sequential: int) -> dict:
    """Batcher -> ValidationHandler -> WebhookServer over real HTTP.

    Which lane answers is the Batcher's own rule (a flush of <= 8 goes
    to the interpreter, a larger one to the device grid), but how many
    requests a flush coalesces depends on thread timing — a lone request
    is flushed at once, and under the GIL a burst mostly trickles in.
    So the smoke pumps the batcher itself: it starts the flush loop only
    once a whole burst is queued (``start()`` then the draining
    ``stop()``), which makes every burst of ``conns`` ONE grid flush and
    every sequential request ONE interpreter flush, deterministically."""
    from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
    from gatekeeper_tpu.observability import tracing
    from gatekeeper_tpu.target.review import AugmentedUnstructured
    from gatekeeper_tpu.webhook.policy import Batcher, ValidationHandler
    from gatekeeper_tpu.webhook.server import WebhookServer
    bodies = [_admission_body(seed * 100_003 + i) for i in range(sequential)]
    batcher = Batcher(client, metrics=metrics)  # pumped below, not started
    check(batcher.small_batch < conns <= batcher.max_batch,
          f"a burst of {conns} would not be one grid flush")
    handler = ValidationHandler(client, batcher=batcher, metrics=metrics)
    # warm the grid-lane pad bucket the bursts will hit, as __main__
    # warms its buckets before serving; part of this phase's set-up
    t0 = time.perf_counter()
    client.review_batch([AugmentedUnstructured(
        object=json.loads(bodies[i % sequential])["request"]["object"],
        source=SOURCE_ORIGINAL) for i in range(conns)])
    warm_s = time.perf_counter() - t0

    tracer = tracing.Tracer(seed=seed, ring_capacity=2 * requests + 1024)
    tracing.install(tracer)
    srv = WebhookServer(validation_handler=handler, port=0, metrics=metrics,
                        batcher=batcher,
                        readiness_check=lambda: True).start()
    answers: dict = {}   # request id -> (body index, answer)
    errors: list = []
    lock = threading.Lock()

    def worker(wid: int, rids: list, barrier) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=300)
        try:
            for rid in rids:
                barrier.wait(timeout=300)
                bi = (rid - 1) % sequential
                conn.request("POST", "/v1/admit", body=bodies[bi], headers={
                    "Content-Type": "application/json",
                    "traceparent": f"00-{rid:032x}-{1:016x}-01"})
                resp = json.loads(conn.getresponse().read())
                with lock:
                    answers[rid] = (bi, _answer(resp))
        except Exception as e:
            barrier.abort()
            with lock:
                errors.append(f"connection {wid}: {type(e).__name__}: {e}")
        finally:
            conn.close()

    def drive(rids: list, n_conns: int) -> None:
        """``rids`` over ``n_conns`` persistent connections, in bursts
        of ``n_conns``; each burst is queued whole, then flushed."""
        barrier = threading.Barrier(n_conns + 1)
        threads = [threading.Thread(target=worker,
                                    args=(w, rids[w::n_conns], barrier))
                   for w in range(n_conns)]
        base = len(answers)
        for t in threads:
            t.start()
        try:
            for burst in range(1, len(rids) // n_conns + 1):
                barrier.wait(timeout=300)
                deadline = time.monotonic() + 300
                # a request the handler answers without a review never
                # reaches the queue; it counts as arrived once answered
                while batcher.queue_depth() + len(answers) \
                        < base + burst * n_conns:
                    check(time.monotonic() < deadline and not errors,
                          f"burst never arrived: {errors[:3]}")
                    time.sleep(0.0005)
                batcher.start()
                check(batcher.stop(timeout=600), "batcher did not drain")
        except threading.BrokenBarrierError:
            pass  # a connection failed; its error is reported below
        for t in threads:
            t.join(timeout=600)
        check(not errors, f"admission errors: {errors[:3]}")
        check(not any(t.is_alive() for t in threads),
              "admission connections hung")

    try:
        t0 = time.perf_counter()
        drive(list(range(1, requests + 1)), conns)
        conc_s = time.perf_counter() - t0
        seq_first = requests + 1
        drive(list(range(seq_first, seq_first + sequential)), 1)
    finally:
        srv.stop(drain_timeout=10.0)
        tracing.uninstall()

    lanes: dict = {}     # request id -> lane that answered it
    grid_flushes: list = []
    for tr in tracer.traces():
        for sp in tr["spans"]:
            if sp["name"] == "webhook.batcher.enqueue":
                lanes[int(tr["trace_id"], 16)] = \
                    sp["attributes"].get("lane", "")
            elif sp["name"] == "webhook.batcher.flush" \
                    and sp["attributes"].get("lane") == "grid":
                grid_flushes.append(sp["attributes"].get("batch_size", 0))
    check(any(b > 8 for b in grid_flushes),
          "no lane=grid flush with batch > 8 ran")
    check(len(answers) == requests + sequential,
          f"{len(answers)} answers for {requests + sequential} requests")
    reference = {}
    for bi in range(sequential):
        rid = seq_first + bi
        check(lanes.get(rid) == "interp",
              f"sequential request {bi} was answered by lane "
              f"{lanes.get(rid)!r}, not the interpreter")
        reference[bi] = answers[rid][1]
    on_grid = set()
    for rid in range(1, seq_first):
        bi, ans = answers[rid]
        check(ans == reference[bi],
              f"body {bi}: lane {lanes.get(rid)!r} answered {ans}, "
              f"interpreter lane answered {reference[bi]}")
        if lanes.get(rid) == "grid":
            on_grid.add(bi)
    check(len(on_grid) == sequential,
          f"bodies never answered by the grid lane: "
          f"{sorted(set(range(sequential)) - on_grid)}")
    return {"requests": requests, "connections": conns,
            "sequential": sequential, "warm_s": round(warm_s, 2),
            "concurrent_smoke_s": round(conc_s, 2),
            "grid_flushes": len(grid_flushes),
            "max_grid_batch": max(grid_flushes),
            "requests_on_grid": sum(
                1 for r, ln in lanes.items() if ln == "grid"),
            "denied": sum(1 for a in reference.values() if not a[0]),
            "grid_equals_interp_bodies": len(on_grid)}


# --- phase 5: device-resident snapshot ---------------------------------------

def phase_residency(client, evaluator, rows: int, chunk: int, seed: int,
                    mode: str) -> dict:
    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.snapshot import (ClusterSnapshot, DeviceResidency,
                                         SnapshotConfig, WatchIngester,
                                         gvks_of)
    from gatekeeper_tpu.sync.source import FakeCluster
    from gatekeeper_tpu.utils.synthetic import iter_cluster_objects

    cluster = FakeCluster()
    churn = []
    for o in iter_cluster_objects(rows, seed + 1):
        if len(churn) < max(1, rows // 100):
            churn.append(copy.deepcopy(o))
        cluster.apply(o)
    residency = DeviceResidency(evaluator, mode=mode)
    snap = ClusterSnapshot(evaluator, SnapshotConfig())
    mgr = AuditManager(
        client, lister=lambda: iter(cluster.list()),
        config=AuditConfig(violations_limit=K, chunk_size=chunk,
                           exact_totals=False, audit_source="snapshot"),
        evaluator=evaluator, snapshot=snap, residency=residency)
    ing = WatchIngester(snap, cluster, gvks_of(cluster.list())).start()
    try:
        t0 = time.perf_counter()
        full = mgr.audit()
        full_s = time.perf_counter() - t0
        check(not full.incomplete, "resident full pass incomplete")
        check(residency.resident_bytes() > 0 and residency.upload_count,
              f"DeviceResidency(mode={mode!r}) did not promote: "
              f"{residency.stats()}")
        mgr.audit_tick()  # primes the gather-index + table caches
        traces0 = evaluator.trace_count
        t0 = time.perf_counter()
        clean = mgr.audit_tick()
        clean_s = time.perf_counter() - t0
        h2d_clean = int(mgr.perf.get("tick_h2d_bytes", 0))
        check(clean.total_violations == full.total_violations
              and kept_canonical(clean) == kept_canonical(full),
              "clean tick != full pass")
        check(evaluator.trace_count == traces0,
              "a clean tick compiled")
        for o in churn:
            o.setdefault("metadata", {}).setdefault(
                "labels", {})["smoke-churn"] = "r1"
            cluster.apply(o)
        ing.pump()
        dirty = sum(len(v) for v in snap.dirty_rows().values())
        patches0 = residency.patch_count
        t0 = time.perf_counter()
        tick = mgr.audit_tick()
        dirty_s = time.perf_counter() - t0
        h2d_dirty = int(mgr.perf.get("tick_h2d_bytes", 0))
        dirty_traces = evaluator.trace_count - traces0
        check(not tick.incomplete, "churn tick incomplete")
        check(residency.patch_count > patches0,
              "the churn tick did not scatter-patch the device mirror")
        # the repo's own differential: re-list + re-flatten fresh,
        # columns and verdicts asserted equal to the resident snapshot
        resync = mgr.audit_resync()
        check(mgr.last_resync_diff is None and not resync.incomplete,
              f"tick != fresh relist: {mgr.last_resync_diff}")
        diff = AuditManager._verdicts_differ_canonical(
            tick.kept, tick.total_violations,
            resync.kept, resync.total_violations, K)
        check(diff is None, f"churn tick != resync tick: {diff}")
    finally:
        ing.stop()
    return {"rows": rows, "mode": mode, "promoted": True,
            "resident_mb": round(residency.resident_bytes() / 1e6, 2),
            "uploads": residency.upload_count,
            "patches": residency.patch_count, "dirty_rows": dirty,
            "h2d_bytes_clean_tick": h2d_clean,
            "h2d_bytes_dirty_tick": h2d_dirty,
            "sweep_traces_in_dirty_tick": dirty_traces,
            "full_pass_smoke_s": round(full_s, 2),
            "clean_tick_smoke_s": round(clean_s, 3),
            "dirty_tick_smoke_s": round(dirty_s, 3),
            "tick_equals_relist": True}


# --- phase 6: every device ---------------------------------------------------

def mesh_evaluator(tpu, metrics):
    """ShardedEvaluator over make_mesh() (every device) that also notes,
    for each dispatch, which devices hold a non-empty shard of the
    packed column buffers handed to the fused program."""
    from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh

    class ShardNoting(ShardedEvaluator):
        shard_devices: set = set()
        shard_rows: set = set()

        def _sweep_fn_reduced(self, *a, **kw):
            fn = super()._sweep_fn_reduced(*a, **kw)

            def noting(tables, cols, *rest):
                for buf in cols.values():
                    for s in buf.addressable_shards:
                        if s.data.size:
                            self.shard_devices.add(s.device.id)
                            self.shard_rows.add(
                                (buf.shape[0], s.data.shape[0]))
                return fn(tables, cols, *rest)

            return noting

    return ShardNoting(tpu, make_mesh(), violations_limit=K,
                       metrics=metrics)


# --- main --------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true",
                   help="pre-flight at toy sizes (the only CPU mode); "
                        "never a chip pass")
    p.add_argument("--objects", type=int, default=0,
                   help="audit corpus size (default 262,144 = 8 chunks; "
                        "1000000 is the BASELINE sweep)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "chiprun_out", "chip_smoke"))
    args = p.parse_args(argv)
    size = dict(TINY if args.tiny else FULL)
    if args.objects:
        size["objects"] = args.objects
    t_start = time.perf_counter()

    # the C columnizers build (a compiler child) BEFORE JAX is touched
    from gatekeeper_tpu.ops import native

    if native.load() is None or native.load_json() is None:
        print("chip_smoke: the native columnizers failed to build",
              file=sys.stderr)
        return 1
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform,
              "device_kind": devs[0].device_kind,
              "device_count": len(devs)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['device_kind']!r} "
          f"device_count={device['device_count']}"
          + (" [--tiny: not a chip pass]" if args.tiny else ""),
          flush=True)
    if device["platform"] != "tpu" and not args.tiny:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); refusing to run. The only CPU "
              "mode is the pre-flight: JAX_PLATFORMS=cpu python "
              "chip_smoke.py --tiny", file=sys.stderr)
        return 1
    from gatekeeper_tpu.utils.xla_cache import configure_xla_cache

    cache_dir = configure_xla_cache()
    rec = Recorder(args.out, device)
    on_tpu = device["platform"] == "tpu"
    spill_fd, spill = tempfile.mkstemp(prefix="chip_smoke_", suffix=".jsonl")
    ok = False
    try:
        with rec.phase("kernels") as r:
            r.update(phase_kernels(size["kernel_shapes"],
                                   interpret=not on_tpu))

        from gatekeeper_tpu.metrics.registry import MetricsRegistry
        from gatekeeper_tpu.parallel.sharded import (ShardedEvaluator,
                                                     make_mesh)

        metrics = MetricsRegistry()
        with rec.phase("library_and_corpus") as r:
            # TpuDriver + CELDriver -> Client with the entire shipped
            # library, none of it on the interpreter fallback
            client, tpu, nt, nc = build_client()
            check(nt == nc == len(tpu.lowered_kinds()),
                  f"library: {nt} templates, {nc} constraints, "
                  f"{len(tpu.lowered_kinds())} lowered")
            n_ing = spill_corpus(client, size["objects"], spill_fd,
                                 args.seed)
            r.update(templates=nt, constraints=nc, fallback_kinds=0,
                     objects=size["objects"], ingresses_synced=n_ing,
                     spill_mb=round(os.path.getsize(spill) / 1e6, 1))
        lister = spill_lister(spill)
        ev1 = ShardedEvaluator(tpu, make_mesh(1), violations_limit=K,
                               metrics=metrics)
        with rec.phase("audit") as r:
            audit_rec, run1 = phase_audit(client, ev1, metrics, lister,
                                          size["objects"], size["chunk"])
            r.update(audit_rec)
        with rec.phase("verdicts_vs_interpreter") as r:
            r.update(phase_verdicts(
                client, ev1, list(spill_lister(spill, size["sample"])())))
        with rec.phase("admission") as r:
            adm_rec = phase_admission(
                client, metrics, args.seed, size["requests"],
                size["conns"], size["sequential"])
            r.update(adm_rec)
        # 'auto' declines on a CPU mesh by design: the pre-flight forces
        # the lane on to walk its control flow, the chip run must see
        # 'auto' promote by itself
        with rec.phase("residency") as r:
            r.update(phase_residency(
                client, ev1, size["rows"], size["chunk"], args.seed,
                mode="auto" if on_tpu else "on"))
        if len(devs) > 1:
            with rec.phase("audit_mesh") as r:
                ev = mesh_evaluator(tpu, metrics)
                mesh_rec, run = phase_audit(client, ev, metrics, lister,
                                            size["objects"], size["chunk"])
                check(run.total_violations == run1.total_violations,
                      "mesh totals != 1-device totals")
                check(kept_canonical(run) == kept_canonical(run1),
                      "mesh kept sets != 1-device kept sets")
                check(ev.shard_devices == {d.id for d in devs},
                      f"devices holding a column shard: "
                      f"{sorted(ev.shard_devices)} of {len(devs)}")
                r.update(mesh_rec,
                         devices_holding_a_shard=len(ev.shard_devices),
                         shard_rows=sorted(ev.shard_rows),
                         equals_one_device_run=True)
        ok = True
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: "
              f"{str(e)[:1000]}", file=sys.stderr)
    finally:
        with contextlib.suppress(OSError):
            os.unlink(spill)
    chip_pass = ok and on_tpu and not args.tiny
    summary = {"phase": "summary", "phases": rec.phases,
               "tiny": args.tiny, "chip_pass": chip_pass,
               "xla_cache_dir": cache_dir,
               "total_smoke_s": round(time.perf_counter() - t_start, 1)}
    if ok:
        summary["setup_s"] = {"audit": audit_rec["setup_s"],
                              "admission_warm": adm_rec["warm_s"]}
    summary["xla"] = {"programs": rec.xla["programs"],
                      "cache_hits": rec.xla["cache_hits"],
                      "compile_s": round(rec.xla["compile_s"], 1)}
    summary["claim"] = None
    rec.emit(summary)
    if not ok:
        return 1
    if chip_pass:
        print(json.dumps({"ok": True, "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["device_count"]}}), flush=True)
    else:
        print(f"chip_smoke: pre-flight passed on platform="
              f"{device['platform']} — not a chip pass", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

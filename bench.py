"""Benchmark: full shipped-library audit sweep rate on one chip.

Prints ONE JSON line:
  {"metric": "library audit reviews/sec/chip", "value": N,
   "unit": "reviews/s", "vs_baseline": R}

A "review" is one object evaluated against the full constraint set (the
reference's Client.Review unit, pkg/webhook/policy.go:664).  The workload is
BASELINE config #2: the ENTIRE shipped policy library (library/general — 21
Rego templates lowered to device verdict programs, incl. the referential
uniqueingresshost with device inventory-join tables, + 1 CEL template on the
interpreter lane) against a realistic mixed cluster
(gatekeeper_tpu/utils/synthetic.py: Pods/Services/Ingresses/Deployments/
Namespaces/RBAC bindings shaped per template).

The timed region is a full AuditManager.audit() run: host flattening, match
masks, pipelined chunked device sweeps, top-k extraction AND message
rendering of kept violations through the exact interpreter — the same path
a production audit pod executes (audit/manager.go:258-973 analog).

``vs_baseline`` is value / 100_000 — the BASELINE.json north-star target
(>=100k reviews/sec/chip); the reference publishes no absolute numbers
(BASELINE.md) so the target is the comparison point.

Component timings go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

# --pipeline=auto|on|off|differential (default auto: staged host pipeline
# when the host has >1 effective core, serial eager-poll otherwise)
PIPELINE_MODE = "auto"
# --flatten-lane=auto|dict|raw|py|differential (sweep columnizer lane;
# auto = raw bytes through the threaded C columnizer when available)
FLATTEN_LANE = "auto"
# --collect=reduced|masks|differential (sweep collect lane; reduced
# folds totals/top-k/occupancy on device and ships O(kept) bytes, masks
# is the host-fold reference, differential runs both and asserts
# bit-identical)
COLLECT_LANE = "reduced"
# --flatten-workers=N (sweep ingest: fan each chunk's raw byte spans
# across N flatten worker processes; 0 = in-process).  Requested counts
# >1 on a 1-core host SKIP with a recorded reason (FLATTEN_BENCH
# convention: the numbers would measure process contention, not
# parallelism) and run workers=0 instead.
FLATTEN_WORKERS = 0
# --shard-chunks=K (audit scheduler: pack K consecutive same-group
# chunks into one mesh-wide dispatch, object axis sharded over 'data')
SHARD_CHUNKS = 0
# --trace out.json: span-trace the timed sweeps and export a Chrome
# trace-event file at exit (Perfetto-loadable device timeline)
TRACE_PATH = ""
# --resident[=N]: after the streaming sweep, run the device-resident
# snapshot tick lane over N rows (default min(n, 100k) — the snapshot
# holds full columns in host memory, unlike the O(chunk) stream) and
# record upload/clean-tick/dirty-sliver phases + h2d_bytes into the
# same SWEEP1M.json history entry
RESIDENT_LANE = 0


def _parse_pipeline_flag(argv: list) -> list:
    """Strip --pipeline[=mode], --flatten-lane[=lane], --chaos[=spec.json]
    and --trace[=path]
    from argv (the remaining args stay positional: N [chunk] |
    sweep [N [chunk]]).  --chaos installs the fault-injection plan
    process-wide so a bench run doubles as a deterministic chaos run (the
    resilience metrics and the run's incomplete/retried counters land in
    the JSON artifact); --trace installs the span tracer (seeded, full
    sampling) and writes the Chrome trace-event artifact — with --chaos
    the injected faults show up as instant events on the spans they hit."""
    global PIPELINE_MODE, TRACE_PATH, FLATTEN_LANE, COLLECT_LANE, \
        FLATTEN_WORKERS, SHARD_CHUNKS, RESIDENT_LANE
    out = []
    chaos = ""
    it = iter(argv)
    for a in it:
        if a == "--pipeline":
            PIPELINE_MODE = next(it, "auto")
        elif a.startswith("--pipeline="):
            PIPELINE_MODE = a.split("=", 1)[1]
        elif a == "--flatten-workers":
            FLATTEN_WORKERS = int(next(it, "0") or 0)
        elif a.startswith("--flatten-workers="):
            FLATTEN_WORKERS = int(a.split("=", 1)[1] or 0)
        elif a == "--shard-chunks":
            SHARD_CHUNKS = int(next(it, "0") or 0)
        elif a.startswith("--shard-chunks="):
            SHARD_CHUNKS = int(a.split("=", 1)[1] or 0)
        elif a == "--flatten-lane":
            FLATTEN_LANE = next(it, "auto")
        elif a.startswith("--flatten-lane="):
            FLATTEN_LANE = a.split("=", 1)[1]
        elif a == "--collect":
            COLLECT_LANE = next(it, "reduced")
        elif a.startswith("--collect="):
            COLLECT_LANE = a.split("=", 1)[1]
        elif a == "--resident":
            RESIDENT_LANE = -1
        elif a.startswith("--resident="):
            RESIDENT_LANE = int(a.split("=", 1)[1] or -1)
        elif a == "--chaos":
            chaos = next(it, "")
        elif a.startswith("--chaos="):
            chaos = a.split("=", 1)[1]
        elif a == "--trace":
            TRACE_PATH = next(it, "")
        elif a.startswith("--trace="):
            TRACE_PATH = a.split("=", 1)[1]
        else:
            out.append(a)
    if TRACE_PATH:
        from gatekeeper_tpu.observability import tracing

        tracing.install(tracing.Tracer(seed=0))
        log(f"span tracer active (export: {TRACE_PATH})")
    if chaos:
        from gatekeeper_tpu.resilience import faults

        faults.install(faults.load_chaos_spec(chaos))
        log(f"chaos harness active: {chaos}")
    return out


def export_trace() -> None:
    """Write the Chrome trace-event artifact (--trace), if tracing ran."""
    if not TRACE_PATH:
        return
    from gatekeeper_tpu.observability import (format_span_summary, tracing,
                                              write_chrome_trace)

    tracer = tracing.active_tracer()
    if tracer is None:
        return
    n = write_chrome_trace(TRACE_PATH, tracer)
    log(f"trace: {n} events ({tracer.kept} traces kept) -> {TRACE_PATH} "
        "(load in ui.perfetto.dev or chrome://tracing)")
    log(format_span_summary(tracer.traces()))


def bench_history_append(entry: dict, path: str = None) -> None:
    """Append this run to BENCH_TPU.json's history (created when
    missing).  The top-level headline only moves for TPU runs — the file
    is the per-chip record; explicitly CPU-pinned runs append to history
    with their platform marked but never overwrite the headline."""
    path = path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_TPU.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"metric": "library audit reviews/sec/chip",
               "unit": "reviews/s", "history": []}
    doc.setdefault("history", []).append(entry)
    if entry.get("platform") == "tpu":
        doc["value"] = entry["value"]
        doc["vs_baseline"] = round(entry["value"] / 100_000, 4)
        doc["platform"] = "tpu"
        if "legacy" in entry:
            doc["legacy_3template_reviews_per_s"] = entry["legacy"]
    try:
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    except OSError as e:
        log(f"BENCH_TPU.json append failed: {e}")


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def effective_flatten_workers() -> tuple:
    """(workers, skip_reason): multi-worker flatten lanes SKIP with a
    recorded reason on 1-core hosts (the FLATTEN_BENCH convention —
    r05 showed 1T==8T at host_cpus=1, so the measurement would be
    process contention, not parallelism) and run workers=0 instead;
    the requested count still lands in the artifact so a multi-core
    re-run knows what was asked for."""
    n = os.cpu_count() or 1
    if FLATTEN_WORKERS > 1 and n < 2:
        return 0, (f"host_cpus={n}: {FLATTEN_WORKERS} flatten workers "
                   "would measure process contention, not parallelism "
                   "(FLATTEN_BENCH skip convention); ran workers=0")
    return FLATTEN_WORKERS, None


def build_client():
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
    assert not fb, f"library templates fell back to interpreter: {fb}"
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


def setup_platform_and_client():
    """Shared preamble for every bench lane: the device check + client/
    library build, in THIS process (a chip belongs to one process; a
    probe child would hold it).  The bench measures the accelerator: it
    runs on the CPU only under an explicit ``JAX_PLATFORMS=cpu`` (tests,
    count checks) and otherwise fails when JAX finds no chip — it never
    picks the CPU on its own.  Returns (jax, client, tpu, nt, nc)."""
    from gatekeeper_tpu.ops import native

    # build the C columnizers before JAX initializes the device (the
    # build runs the compiler as a child) — and refuse to go on without
    # them: the Python flattener is the library's fallback, not
    # something to measure
    if native.load() is None or native.load_json() is None:
        sys.exit("bench: the native columnizers failed to build; "
                 "refusing to measure the Python flattener")
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("bench: JAX found no accelerator; set JAX_PLATFORMS=cpu "
                 "explicitly for a CPU run (never a device result)")
    from gatekeeper_tpu.utils.xla_cache import configure_xla_cache

    configure_xla_cache()
    log(f"devices: {jax.devices()}")
    client, tpu, nt, nc = build_client()
    log(f"library loaded: {nt} templates ({len(tpu.lowered_kinds())} on the "
        f"device verdict path), {nc} constraints")
    return jax, client, tpu, nt, nc


def require_complete(run) -> None:
    """A pass that dropped chunks has no verdicts for them; its rate is
    not a result."""
    if run.incomplete:
        sys.exit(f"bench: audit run incomplete ({run.failed_chunks} chunks "
                 f"dropped, {run.retried_chunks} retried) — no rate "
                 "reported")


def setup(n: int):
    """setup_platform_and_client + synthetic workload generation +
    referential inventory sync.  Returns (jax, client, tpu, nt, nc,
    objects, gen_s, inv_s)."""
    jax, client, tpu, nt, nc = setup_platform_and_client()
    from gatekeeper_tpu.utils.synthetic import make_cluster_objects
    t0 = time.perf_counter()
    log(f"generating {n} synthetic cluster objects...")
    objects = make_cluster_objects(n)
    gen_s = time.perf_counter() - t0
    # referential inventory: uniqueingresshost joins over synced Ingresses
    t0 = time.perf_counter()
    n_ing = 0
    for o in objects:
        if o.get("kind") == "Ingress":
            client.add_data(o)
            n_ing += 1
    inv_s = time.perf_counter() - t0
    # serialize the corpus once (still the generation phase, untimed by the
    # sweep): the audit flattens raw JSON through the threaded native lane
    # (native/flattenjsonmod.c) without materializing Python dicts
    from gatekeeper_tpu.utils.rawjson import as_raw

    t0 = time.perf_counter()
    objects = [as_raw(o) for o in objects]
    wrap_s = time.perf_counter() - t0
    gen_s += wrap_s
    log(f"generation {gen_s:.1f}s (incl. {wrap_s:.1f}s JSON serialize); "
        f"inventory: {n_ing} Ingresses synced for the referential join "
        f"({inv_s:.1f}s)")
    return jax, client, tpu, nt, nc, objects, gen_s, inv_s


def sweep_main(n: int = 1_000_000, chunk: int = 32_768,
               submit_window: int = 4):
    """BASELINE config #6: the N-object audit sweep, measured (not
    extrapolated), at O(chunk) host memory.  Writes SWEEP1M.json with
    elapsed + phase breakdown + peak RSS.

    The corpus spills to a JSONL file at generation time (the reference's
    disk list-cache, pkg/audit/manager.go:502-561: list pages spill to
    disk and review streams file-by-file); the warm pass and the timed
    sweep both STREAM it — no pass ever holds more than
    ``submit_window + 1`` chunks of objects, so peak RSS is bounded by
    vocab/table state + in-flight chunks instead of the whole corpus.

    Per-constraint violating-object counts come from the device count
    reduction (exact per (constraint, object) pair); kept top-20
    violations render through the exact engine — the production audit
    shape (pkg/audit/manager.go:258).
    """
    import json as _json
    import os
    import resource
    import tempfile

    jax, client, tpu, nt, nc = setup_platform_and_client()

    # unique, safely-created spill (mkstemp): a fixed predictable path in
    # the shared tmp dir clobbers under concurrent runs and is a
    # pre-creation/symlink hazard on multi-user hosts
    spill_fd, spill = tempfile.mkstemp(
        prefix=f"sweep_corpus_{n}_", suffix=".jsonl")
    try:
        return _sweep_timed(jax, client, tpu, nt, nc, spill_fd, spill, n,
                            chunk, submit_window)
    finally:
        # unlink unconditionally: an interrupted run must not leak a
        # multi-GB uniquely-named spill per retry
        try:
            os.unlink(spill)
        except OSError:
            pass


def _sweep_timed(jax, client, tpu, nt, nc, spill_fd, spill, n, chunk,
                 submit_window):
    import json as _json
    import os
    import resource
    import time

    t0 = time.perf_counter()
    log(f"generating {n} objects to disk spill {spill} (streaming)...")
    n_ing = spill_corpus(client, n, spill_fd)
    gen_s = time.perf_counter() - t0
    log(f"generation+spill: {gen_s:.1f}s ({n_ing} Ingresses synced; "
        f"{os.path.getsize(spill) / 1e9:.2f}GB on disk)")
    lister = spill_lister(spill)

    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh

    workers, workers_skip = effective_flatten_workers()
    if workers_skip:
        log(f"flatten-workers lane skipped: {workers_skip}")
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=20,
                                 flatten_lane=FLATTEN_LANE,
                                 collect=COLLECT_LANE,
                                 flatten_workers=workers)
    cfg = AuditConfig(violations_limit=20, chunk_size=chunk,
                      exact_totals=False, submit_window=submit_window,
                      pipeline=PIPELINE_MODE, shard_chunks=SHARD_CHUNKS)
    mgr = AuditManager(client, lister=lister, config=cfg,
                       evaluator=evaluator)
    # fetch-free warmup: interns every name (vocab reaches its final
    # bucket) and compiles all chunk shapes
    log("warmup (streaming vocab pass + per-group jit compile)...")
    t_w = time.perf_counter()
    # warm at the PACKED chunk size: shard_chunks coalesces K chunks
    # into one dispatch, so the timed sweep's pad buckets are K x chunk
    # wide — warming at the unpacked size would retrace mid-sweep
    evaluator.warm_pass(client.constraints(), lister(),
                        chunk * max(1, SHARD_CHUNKS),
                        return_bits=cfg.exact_totals)
    log(f"warmup: {time.perf_counter() - t_w:.1f}s")

    log(f"timed {n}-object sweep (chunk={chunk}, "
        f"window={submit_window})...")
    evaluator.perf_reset()
    mgr.perf = {}
    t0 = time.perf_counter()
    run = mgr.audit()
    elapsed = time.perf_counter() - t0
    require_complete(run)
    phases = {k: round(v, 2) for k, v in evaluator.perf.items()}
    phases.update({k: round(v, 2) for k, v in mgr.perf.items()})
    phases["wire_mb"] = round(phases.pop("wire_bytes", 0.0) / 1e6, 1)
    # host-vs-device bytes per direction: wire_mb is H2D (packed columns
    # + tables + masks), d2h_kb is what collect fetched back — the
    # reduced lane's O(kept) contract shows up here
    phases["d2h_kb"] = round(phases.pop("d2h_bytes", 0.0) / 1e3, 2)
    # sum over constraints of violating-object counts: an object violating
    # k constraints contributes k (a violation count, not distinct objects)
    violations = sum(run.total_violations.values())
    kept = sum(len(v) for v in run.kept.values())
    rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    log(f"sweep: {elapsed:.2f}s for {n} objects x {nc} constraints "
        f"({violations} constraint violations, {kept} kept) "
        f"-> {n / elapsed:,.0f} reviews/s; peak RSS {rss_gb:.1f}GB")
    log(f"phases: {phases}")
    out = {
        "metric": "1M-object library audit sweep",
        "platform": jax.devices()[0].platform,
        "n_objects": n,
        "n_constraints": nc,
        "elapsed_s": round(elapsed, 2),
        "reviews_per_s": round(n / elapsed, 1),
        "violations": violations,
        "kept_rendered": kept,
        "generation_s": round(gen_s, 2),
        "peak_rss_gb": round(rss_gb, 2),
        "chunk_size": chunk,
        "submit_window": submit_window,
        "streaming": "disk JSONL spill; O(chunk) host memory",
        "phase_s": phases,
        "target": "<10s on v5e-4 (x4 chips: data-parallel chunks shard "
                  "across ICI; single-chip time / 4 is the honest "
                  "extrapolation only for the device phase — host flatten "
                  "stays serial unless hosts scale too)",
    }
    out["pipeline"] = {"mode": PIPELINE_MODE,
                       "schedule": ("pipelined"
                                    if mgr.perf.get("pipelined")
                                    else "serial")}
    out["flatten_lane"] = FLATTEN_LANE
    out["collect"] = COLLECT_LANE
    # self-describing ingest/dispatch geometry (run.flatten_workers etc.
    # come from the AuditRun annotation — the effective values, not the
    # requested flags)
    out["flatten_workers"] = run.flatten_workers
    out["shard_chunks"] = run.shard_chunks
    out["n_devices"] = run.n_devices
    if workers_skip:
        out["flatten_workers_requested"] = FLATTEN_WORKERS
        out["skipped_workers_reason"] = workers_skip
    worker_busy = phases.get("fl_worker_busy", 0.0)
    if worker_busy:
        # aggregate objects per worker-second across the timed sweep
        out["per_worker_objs_per_s"] = round(n / worker_busy, 1)
    if mgr.pipe_stats:
        out["pipeline"].update(mgr.pipe_stats)
    if RESIDENT_LANE:
        rows = RESIDENT_LANE if RESIDENT_LANE > 0 else min(n, 100_000)
        out["device_resident"] = _resident_lane(client, tpu, rows, chunk)
    sweep_history_append(out)
    export_trace()
    print(_json.dumps(out))


def _resident_lane(client, tpu, rows: int, chunk: int) -> dict:
    """The --resident sweep lane: HBM-resident snapshot columns ticked
    against watch churn.  Three timed phases — (1) full rebuild + first
    upload, (2) warm clean-rows tick (the zero-H2D pin: gather indices
    cached, no bytes cross the link), (3) dirty-sliver tick (~1% rows
    churned; only the sliver's scatter-patch ships)."""
    import copy as _copy
    import time as _time

    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
    from gatekeeper_tpu.snapshot import (ClusterSnapshot, DeviceResidency,
                                         SnapshotConfig, WatchIngester,
                                         gvks_of)
    from gatekeeper_tpu.sync.source import FakeCluster
    from gatekeeper_tpu.utils.synthetic import iter_cluster_objects

    log(f"device-resident lane: {rows} snapshot rows...")
    # single-device mesh: the resident lane is single-chip by design
    ev = ShardedEvaluator(tpu, make_mesh(1), violations_limit=20)
    cluster = FakeCluster()
    churn_pool = []
    for o in iter_cluster_objects(rows):
        if len(churn_pool) < max(1, rows // 100):
            churn_pool.append(_copy.deepcopy(o))
        cluster.apply(o)
    residency = DeviceResidency(ev, mode="on")
    snap = ClusterSnapshot(ev, SnapshotConfig())
    mgr = AuditManager(
        client, lister=lambda: iter(cluster.list()),
        config=AuditConfig(violations_limit=20, chunk_size=chunk,
                           exact_totals=False, pipeline="off",
                           audit_source="snapshot"),
        evaluator=ev, snapshot=snap, residency=residency)
    ing = WatchIngester(snap, cluster, gvks_of(cluster.list())).start()
    try:
        phases = {}
        t0 = _time.perf_counter()
        mgr.audit()
        phases["rebuild_upload_s"] = round(_time.perf_counter() - t0, 3)
        mgr.audit_tick()  # prime the gather-index + param-table caches
        t0 = _time.perf_counter()
        mgr.audit_tick()
        phases["clean_tick_s"] = round(_time.perf_counter() - t0, 3)
        h2d_clean = int(mgr.perf.get("tick_h2d_bytes", 0))
        for o in churn_pool:
            o.setdefault("metadata", {}).setdefault(
                "labels", {})["bench-churn"] = "r1"
            cluster.apply(o)
        ing.pump()
        dirty = sum(len(v) for v in snap.dirty_rows().values())
        t0 = _time.perf_counter()
        mgr.audit_tick()
        phases["dirty_sliver_tick_s"] = round(_time.perf_counter() - t0, 3)
        h2d_dirty = int(mgr.perf.get("tick_h2d_bytes", 0))
    finally:
        ing.stop()
    lane = {
        "rows": rows,
        "resident_mb": round(residency.resident_bytes() / 1e6, 2),
        "uploads": residency.upload_count,
        "patches": residency.patch_count,
        "dirty_rows": dirty,
        "h2d_bytes_clean_tick": h2d_clean,
        "h2d_bytes_dirty_tick": h2d_dirty,
        "h2d_clean_ok": h2d_clean == 0,  # the acceptance pin
        "phase_s": phases,
    }
    log(f"device-resident lane: {lane}")
    if h2d_clean != 0:
        log(f"WARNING: warm clean-rows tick shipped {h2d_clean} bytes "
            "(expected 0)")
    return lane


def sweep_history_append(entry: dict) -> None:
    """SWEEP1M.json keeps a run history like BENCH_TPU.json: every run
    appends (with its collect/flatten lanes and both transfer-direction
    byte counts), the top-level headline only moves for TPU runs —
    explicitly CPU-pinned runs must not overwrite the per-chip record."""
    import json as _json
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SWEEP1M.json")
    try:
        with open(path) as f:
            doc = _json.load(f)
    except (OSError, ValueError):
        doc = {}
    history = doc.pop("history", [])
    if doc and "metric" in doc:
        headline = doc
    else:
        headline = {}
    entry = dict(entry)
    entry["date"] = time.strftime("%Y-%m-%d")
    history.append(entry)
    if entry.get("platform") == "tpu":
        headline = {k: v for k, v in entry.items() if k != "date"}
    out_doc = dict(headline)
    out_doc["history"] = history
    try:
        with open(path, "w") as f:
            _json.dump(out_doc, f, indent=1)
            f.write("\n")
    except OSError as e:
        log(f"SWEEP1M.json append failed: {e}")


def legacy_lane(n: int = 100_000):
    """The round-1 comparison lane: 3 templates x 40 constraints raw
    device sweep over synthetic pods (no audit manager, no rendering).
    Kept so round-over-round perf is comparable after the primary lane
    hardened to the full library (VERDICT r2 weak #7)."""
    import __graft_entry__ as g
    from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh

    tpu = g._build_driver(
        [g._PRIV_TEMPLATE, g._REQ_LABELS_TEMPLATE, g._HOST_NS_TEMPLATE]
    )
    cons = g._constraints(n_labels=38)  # 40 constraints, as in round 1
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=20)
    pods = g._make_pods(n)
    evaluator.sweep(cons, pods[:1024])  # compile small bucket
    evaluator.sweep(cons, pods)  # compile full bucket + warm vocab
    elapsed = None
    for _ in range(2):  # best of 2
        t0 = time.perf_counter()
        evaluator.sweep(cons, pods)
        dt = time.perf_counter() - t0
        elapsed = dt if elapsed is None else min(elapsed, dt)
    rate = n / elapsed
    log(f"legacy 3-template lane: {elapsed:.3f}s for {n} pods x "
        f"{len(cons)} constraints -> {rate:,.0f} reviews/s")
    return rate


def make_tenant_body(i: int, namespace: str) -> bytes:
    """A loadtest admission body re-homed into ``namespace`` (both the
    request and the object), so the QoS tenant key and the policy
    matchers see one coherent tenant."""
    from tools.loadtest_webhook import make_body

    doc = json.loads(make_body(i))
    doc["request"]["namespace"] = namespace
    obj = doc["request"].get("object") or {}
    obj.setdefault("metadata", {})["namespace"] = namespace
    return json.dumps(doc).encode()


def drive_tenant_mix(port: int, plan: list, bodies: dict,
                     timeout_s: float = 60.0) -> dict:
    """Offer a multi-tenant load mix against a running webhook and
    report per-tenant latency/shed stats.

    ``plan``: [{"name": tenant, "conc": N, "n": total requests}, ...] —
    every tenant's workers run concurrently (the contention IS the
    measurement); ``bodies``: {tenant: [request bytes, ...]}.  Returns
    {tenant: {requests, accepted, shed, shed_rate, p50_ms, p99_ms,
    mean_ms, errors}} — accepted-request latency only, sheds counted
    separately (the PR 5 burst-lane convention)."""
    import http.client
    import statistics
    import threading

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
                resp = json.loads(c.getresponse().read())
                dt = (time.perf_counter() - t0) * 1000
                r = resp["response"]
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
    for t in plan:
        st = stats[t["name"]]
        sv = sorted(st["lat"])

        def pct(p):
            return round(sv[min(len(sv) - 1,
                                int(p / 100 * len(sv)))], 2) if sv else 0.0

        total = len(sv) + st["shed"]
        out[t["name"]] = {
            "concurrency": t["conc"], "requests": total,
            "accepted": len(sv), "shed": st["shed"],
            "shed_rate": round(st["shed"] / total, 4) if total else 0.0,
            "p50_ms": pct(50), "p99_ms": pct(99),
            "mean_ms": (round(statistics.mean(sv), 2) if sv else 0.0),
            "errors": st["errors"],
        }
    return out


def burst_main(n_base: int = 240, conc_base: int = 2,
               burst_mult: int = 8):
    """``--burst``: offered-load step pattern against the real webhook
    stack with the overload limiter engaged — the overload-trajectory
    record (P50/P99/shed-rate per step), appended to WEBHOOK_LOAD.json's
    ``burst_history`` like FLATTEN_BENCH tracks the columnizer.

    Step 1 serves ``conc_base`` connections (the unloaded anchor); step 2
    offers ``burst_mult``x that.  The limiter is sized SMALL (the point is
    to exercise the shed path, not to absorb the burst), so the burst
    step reports how accepted-request latency holds while excess load is
    shed per failurePolicy."""
    import http.client
    import statistics
    import threading

    from gatekeeper_tpu.metrics.registry import MetricsRegistry
    from gatekeeper_tpu.resilience import overload as _overload
    from gatekeeper_tpu.webhook.policy import Batcher, ValidationHandler
    from gatekeeper_tpu.webhook.server import WebhookServer
    from tools.loadtest_webhook import make_body

    jax, client, tpu, nt, nc = setup_platform_and_client()
    metrics = MetricsRegistry()
    # deliberately tight: in-flight capped at 4 with a 4-deep/50ms queue
    # so a burst_mult x step actually overflows into the shed path (a
    # production-sized limiter would absorb this workload's ~5ms reviews
    # without a single shed, recording nothing about the trajectory)
    ctl = _overload.OverloadController(_overload.OverloadConfig(
        min_inflight=1, max_inflight=4, initial_inflight=4,
        queue_depth=4, queue_timeout_s=0.05), metrics=metrics)
    _overload.install(ctl)
    batcher = Batcher(client, window_s=0.002, max_batch=64,
                      metrics=metrics).start()
    handler = ValidationHandler(client, batcher=batcher, metrics=metrics,
                                failure_policy="fail", overload=ctl)
    srv = WebhookServer(validation_handler=handler, port=0,
                        metrics=metrics, batcher=batcher).start()
    bodies = [make_body(i) for i in range(128)]

    def drive(n: int, conc: int) -> dict:
        lat_ms: list = []
        sheds = [0]
        errors: list = []
        lock = threading.Lock()

        def worker(wid: int):
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=60)
            try:
                for i in range(n // conc):
                    body = bodies[(wid + i * conc) % len(bodies)]
                    t0 = time.perf_counter()
                    c.request("POST", "/v1/admit", body=body,
                              headers={"Content-Type": "application/json"})
                    resp = json.loads(c.getresponse().read())
                    dt = (time.perf_counter() - t0) * 1000
                    r = resp["response"]
                    shed = (r.get("status", {}).get("code") == 429
                            or any("overload" in w
                                   for w in r.get("warnings", [])))
                    with lock:
                        if shed:
                            sheds[0] += 1
                        else:
                            lat_ms.append(dt)
            except Exception as e:
                with lock:
                    errors.append(f"{wid}: {type(e).__name__}: {e}")
            finally:
                c.close()

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        sv = sorted(lat_ms)

        def pct(p):
            return round(sv[min(len(sv) - 1, int(p / 100 * len(sv)))], 2) \
                if sv else 0.0

        total = len(lat_ms) + sheds[0]
        return {"concurrency": conc, "requests": total,
                "accepted": len(lat_ms), "shed": sheds[0],
                "shed_rate": round(sheds[0] / total, 4) if total else 0.0,
                "p50_ms": pct(50), "p99_ms": pct(99),
                "mean_ms": (round(statistics.mean(sv), 2) if sv else 0.0),
                "requests_per_s": round(total / elapsed, 1),
                "errors": errors}

    log("warmup...")
    drive(32, 1)
    log(f"step 1: unloaded anchor (conc={conc_base}, n={n_base})...")
    unloaded = drive(n_base, conc_base)
    log(f"  p50 {unloaded['p50_ms']}ms p99 {unloaded['p99_ms']}ms "
        f"shed {unloaded['shed']}")
    conc_burst = conc_base * burst_mult
    log(f"step 2: {burst_mult}x offered-load burst (conc={conc_burst})...")
    burst = drive(n_base * burst_mult, conc_burst)
    log(f"  p50 {burst['p50_ms']}ms p99 {burst['p99_ms']}ms "
        f"shed {burst['shed']} ({burst['shed_rate']:.1%})")

    # step 3: multi-tenant offered-load mix under QoS — tenant A bursts
    # at burst_mult x tenant B's load plus a system-lane trickle, the
    # isolation_ratio is B's accepted P99 under attack over B unloaded
    # (1.0 = perfect isolation; the tier-1 chaos test pins <= 2.0 with
    # a tight limiter)
    from gatekeeper_tpu.resilience.qos import QoSConfig

    # tight like steps 1-2: cap 1 slot per tenant and a short queue so
    # the attacker SHEDS instead of convoying the (1-core) host — the
    # isolation number then measures the scheduler, not CPU contention
    qos_ctl = _overload.OverloadController(_overload.OverloadConfig(
        min_inflight=1, max_inflight=4, initial_inflight=4,
        queue_depth=16, queue_timeout_s=0.25,
        qos=QoSConfig(tenant_inflight_cap=1, quantum=16384.0)),
        metrics=metrics)
    handler.overload = qos_ctl
    _overload.install(qos_ctl)
    tenant_bodies = {
        "tenant-a": [make_tenant_body(i, "tenant-a") for i in range(32)],
        "tenant-b": [make_tenant_body(i, "tenant-b") for i in range(32)],
        "kube-system": [make_tenant_body(i, "kube-system")
                        for i in range(8)],
    }
    log(f"step 3: multi-tenant mix (QoS on: tenant-a {burst_mult}x "
        f"tenant-b + system trickle)...")
    anchor = drive_tenant_mix(srv.port, [
        {"name": "tenant-b", "conc": conc_base, "n": n_base}],
        tenant_bodies)
    mix = drive_tenant_mix(srv.port, [
        {"name": "tenant-a", "conc": conc_base * burst_mult,
         "n": n_base * burst_mult},
        {"name": "tenant-b", "conc": conc_base, "n": n_base},
        {"name": "kube-system", "conc": 1, "n": max(8, n_base // 8)},
    ], tenant_bodies)
    b_unloaded_p99 = anchor["tenant-b"]["p99_ms"]
    isolation_ratio = (round(mix["tenant-b"]["p99_ms"] / b_unloaded_p99, 2)
                       if b_unloaded_p99 else None)
    for tn, st in sorted(mix.items()):
        log(f"  {tn}: p50 {st['p50_ms']}ms p99 {st['p99_ms']}ms "
            f"shed {st['shed']} ({st['shed_rate']:.1%})")
    log(f"  isolation_ratio (tenant-b p99 attacked/unloaded): "
        f"{isolation_ratio}")
    tenant_mix = {
        "qos": {"lanes": "system|break-glass|user",
                "tenant_inflight_cap": 1, "quantum": 16384,
                "queue_depth": 16, "queue_timeout_s": 0.25},
        "note": "1-core host: reviews are CPU-bound, so B's attacked "
                "P99 includes core contention the scheduler cannot "
                "remove; the pinned <=2x isolation bound is proven "
                "with controlled service times in tests/test_qos.py",
        "unloaded_b": anchor["tenant-b"],
        "mix": mix,
        "isolation_ratio": isolation_ratio,
        "sheds_by_tenant": {
            tn: st["shed"] for tn, st in sorted(mix.items())},
    }
    srv.stop(drain_timeout=5.0)
    _overload.uninstall()

    entry = {
        "date": time.strftime("%Y-%m-%d"),
        "host_cpus": os.cpu_count(),
        "limiter": {"max_inflight": 4, "initial": 4, "queue_depth": 4,
                    "queue_timeout_s": 0.05,
                    "final_limit": ctl.limiter.limit},
        "unloaded": unloaded,
        "burst": burst,
        "tenant_mix": tenant_mix,
        "p99_ratio": (round(burst["p99_ms"] / unloaded["p99_ms"], 2)
                      if unloaded["p99_ms"] else None),
        "note": f"offered-load step {conc_base}->{conc_burst} conns; "
                "accepted-request latency only (sheds excluded, counted "
                "in shed_rate); failurePolicy=fail (429 + Retry-After)",
    }
    root = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(root, "WEBHOOK_LOAD.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"metric": "webhook serving load"}
    doc.setdefault("burst_history", []).append(entry)
    with open(path, "w") as f:
        f.write(json.dumps(doc) + "\n")
    print(json.dumps({"metric": "webhook overload burst", **entry}))


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    chunk = int(sys.argv[2]) if len(sys.argv) > 2 else 16_384
    jax, client, tpu, nt, nc, objects, _gen_s, _inv_s = setup(n)
    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh

    workers, workers_skip = effective_flatten_workers()
    if workers_skip:
        log(f"flatten-workers lane skipped: {workers_skip}")
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=20,
                                 flatten_lane=FLATTEN_LANE,
                                 collect=COLLECT_LANE,
                                 flatten_workers=workers)
    cfg = AuditConfig(violations_limit=20, chunk_size=chunk,
                      exact_totals=False, pipeline=PIPELINE_MODE,
                      shard_chunks=SHARD_CHUNKS)
    mgr = AuditManager(client, lister=lambda: iter(objects), config=cfg,
                       evaluator=evaluator)

    # fetch-free warmup (see sweep_main): vocab + jit compile
    log("warmup (vocab pass + per-bucket jit compile, fetch-free)...")
    t0 = time.perf_counter()
    evaluator.warm_pass(client.constraints(), objects,
                        chunk * max(1, SHARD_CHUNKS),
                        return_bits=cfg.exact_totals)
    log(f"warmup: {time.perf_counter() - t0:.1f}s")

    # methodology: FIVE timed passes, MEDIAN reported as the headline (a
    # best-of-2 is not a defensible steady-state number).  All pass
    # times + the IQR go into the JSON artifact; phases come from the
    # median pass.
    n_passes = 5
    log(f"timed audit sweep (median of {n_passes} passes)...")
    pass_times = []
    pass_phases = []
    pass_pipes = []
    runs = []
    for p in range(n_passes):
        evaluator.perf_reset()
        mgr.perf = {}
        t0 = time.perf_counter()
        run = mgr.audit()
        dt = time.perf_counter() - t0
        require_complete(run)
        log(f"  pass {p + 1}: {dt:.3f}s")
        pass_times.append(round(dt, 3))
        ph = {k: round(v, 3) for k, v in evaluator.perf.items()}
        ph.update({k: round(v, 3) for k, v in mgr.perf.items()})
        ph["wire_mb"] = round(ph.pop("wire_bytes", 0.0) / 1e6, 1)
        ph["d2h_kb"] = round(ph.pop("d2h_bytes", 0.0) / 1e3, 2)
        pass_phases.append(ph)
        pass_pipes.append(mgr.pipe_stats)
        runs.append(run)
    order = sorted(range(n_passes), key=lambda i: pass_times[i])
    med_i = order[n_passes // 2]
    elapsed = pass_times[med_i]
    phases = pass_phases[med_i]
    pipe_stats = pass_pipes[med_i]
    run = runs[med_i]
    iqr = round(pass_times[order[-(n_passes // 4 + 1)]]
                - pass_times[order[n_passes // 4]], 3)
    log(f"  median {elapsed:.3f}s, IQR {iqr:.3f}s")
    log(f"  phase breakdown (median pass): {phases}")
    violations = sum(run.total_violations.values())
    total_kept = sum(len(v) for v in run.kept.values())
    reviews_per_s = n / elapsed

    log(f"end-to-end: {elapsed:.3f}s for {n} objects x {nc} constraints "
        f"({violations} constraint violations, {total_kept} rendered "
        f"kept violations) -> {reviews_per_s:,.0f} reviews/s")
    log(f"constraint-evals/sec: {n * nc / elapsed:,.0f}")

    log("legacy 3-template lane (round-over-round comparison)...")
    legacy_rate = legacy_lane(n)

    out = {
        "metric": "library audit reviews/sec/chip",
        "value": round(reviews_per_s, 1),
        "unit": "reviews/s",
        "vs_baseline": round(reviews_per_s / 100_000, 4),
        "platform": jax.devices()[0].platform,
        "legacy_3template_reviews_per_s": round(legacy_rate, 1),
        "pass_times_s": pass_times,
        "pass_iqr_s": iqr,
        "methodology": f"median of {n_passes} passes (all listed); "
                       "phases from median pass",
        "phase_s": phases,
    }
    # staged-pipeline proof artifact: per-stage busy/occupancy + queue
    # high-water + device-idle proxy from the MEDIAN pass.  When the
    # schedule pipelined, stage_busy_sum_s > wall_s is the overlap
    # evidence (host stages ran concurrently with each other and the
    # device) — the BENCH acceptance signal for this round.
    out["pipeline"] = {"mode": PIPELINE_MODE,
                       "schedule": ("pipelined"
                                    if phases.get("pipelined")
                                    else "serial")}
    out["flatten_lane"] = FLATTEN_LANE
    out["collect"] = COLLECT_LANE
    out["flatten_workers"] = run.flatten_workers
    out["shard_chunks"] = run.shard_chunks
    out["n_devices"] = run.n_devices
    if workers_skip:
        out["flatten_workers_requested"] = FLATTEN_WORKERS
        out["skipped_workers_reason"] = workers_skip
    if pipe_stats:
        out["pipeline"].update(pipe_stats)
    bench_history_append({
        "note": f"auto-appended by bench.py (pipeline={PIPELINE_MODE}, "
                f"schedule={out['pipeline']['schedule']}, "
                f"flatten_lane={FLATTEN_LANE})",
        "value": out["value"],
        "legacy": out["legacy_3template_reviews_per_s"],
        "platform": out["platform"],
        "pass_iqr_s": iqr,
        "date": time.strftime("%Y-%m-%d"),
        "flatten_lane": FLATTEN_LANE,
        "collect": COLLECT_LANE,
        "host_cpus": os.cpu_count(),
    })
    export_trace()
    print(json.dumps(out))


if __name__ == "__main__":
    sys.argv[1:] = _parse_pipeline_flag(sys.argv[1:])
    if "--burst" in sys.argv:
        sys.argv.remove("--burst")
        burst_main(int(sys.argv[1]) if len(sys.argv) > 1 else 240)
    elif len(sys.argv) > 1 and sys.argv[1] == "sweep":
        sweep_main(int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000,
                   int(sys.argv[3]) if len(sys.argv) > 3 else 32_768)
    elif len(sys.argv) > 1 and sys.argv[1] == "replay":
        # replay bench (record -> candidate replay: bit-identity +
        # zero-fresh-lowering pins): writes REPLAY_BENCH.json
        import importlib.util as _ilu

        _spec = _ilu.spec_from_file_location(
            "bench_replay",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "bench_replay.py"))
        _br = _ilu.module_from_spec(_spec)
        _spec.loader.exec_module(_br)
        sys.exit(_br.main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet":
        # fleet packing bench (K small clusters packed vs sequential):
        # one entry point beside sweep/burst; writes FLEET_BENCH.json
        import importlib.util as _ilu

        _spec = _ilu.spec_from_file_location(
            "bench_fleet",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "bench_fleet.py"))
        _bf = _ilu.module_from_spec(_spec)
        _spec.loader.exec_module(_bf)
        sys.exit(_bf.main(sys.argv[2:]))
    else:
        main()

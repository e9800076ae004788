"""Audit sweep: the 1M-object enforcement point.

Reference flow (pkg/audit/manager.go:258-973, SURVEY.md §3.2):
list every auditable object (chunked) → review each against all constraints →
keep top-K violations per constraint (LimitQueue) → write constraint status +
export + logs.

TPU-native middle: each chunk flattens to columns and the whole
constraint × chunk grid evaluates in one sharded device pass
(parallel/sharded.ShardedEvaluator); only the ≤K kept violations per
constraint are rendered to messages through the exact interpreter.  Fallback
(non-lowered) kinds run the interpreter loop behind the same seam.

Flags mirrored from the reference (manager.go:55-71): audit-interval (60s),
constraint-violations-limit (20), audit-chunk-size (500),
audit-match-kind-only.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional, Sequence

import numpy as np

from gatekeeper_tpu.apis.constraints import AUDIT_EP, Constraint
from gatekeeper_tpu.audit.render_memo import RenderMemo
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.base import ReviewCfg
from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
from gatekeeper_tpu.ops.native import released_thread_time
from gatekeeper_tpu.target.review import AugmentedUnstructured
from gatekeeper_tpu.utils.rawjson import RawJSON, peek_identity
from gatekeeper_tpu.utils.unstructured import gvk_of, split_api_version


@dataclass
class AuditConfig:
    interval_s: float = 60.0
    violations_limit: int = 20  # --constraint-violations-limit
    chunk_size: int = 500  # --audit-chunk-size
    match_kind_only: bool = False  # --audit-match-kind-only
    from_cache: bool = False  # --audit-from-cache
    # sweep schedule (--pipeline): 'auto' takes the staged host pipeline
    # (pipeline/executor.py — flatten, dispatch, collect and fold on
    # their own threads with bounded queues, so chunk K's flatten
    # overlaps chunk K-1's collect/fold) when the host has >1 effective
    # core; 'on'/'off' force it; 'differential' runs BOTH schedules and
    # asserts bit-identical output (totals, kept order, messages)
    pipeline: str = "auto"
    # threads in the flatten stage; 0 = auto (2 on hosts with >=4
    # effective cores, else 1).  The C columnizer already shards one
    # chunk over an internal pthread pool with the GIL released, so
    # cross-chunk workers mainly overlap the GIL-held assembly slices;
    # >1 worker makes vocab-intern ORDER depend on thread timing (ids
    # stay self-consistent and verdicts/messages identical — the warm
    # pass freezes the vocab before timed sweeps anyway) and emission
    # order stays canonical either way (the executor restores input
    # order).
    pipeline_flatten_workers: int = 0
    # bound of each inter-stage queue (chunks buffered between stages);
    # the collect stage's input bound is submit_window, not this
    pipeline_queue_cap: int = 2
    # exact totals = reference parity: totalViolations counts every violation
    # *result* (a pod with 2 privileged containers contributes 2), which
    # requires rendering every hit through the interpreter.  False counts
    # violating objects from the device grid — faster on violation-dense
    # clusters, at the cost of undercounting multi-violation objects.
    exact_totals: bool = True
    # how many chunks may be in flight on the device before the oldest is
    # collected: a deep window keeps uploads and dispatches queued ahead
    # of the first blocking collect.  Results are tiny (top-k + packed
    # bits), inputs are freed as the device drains the queue, so it
    # costs little HBM.
    submit_window: int = 64
    # resilience (resilience/policy.py): a chunk whose submit/collect/fold
    # raises is re-submitted up to chunk_retries times, then SKIPPED —
    # the run finishes with partial results and an explicit `incomplete`
    # marker instead of aborting the pass.  Stage workers in the
    # pipelined schedule restart and re-run their item up to
    # pipeline_stage_retries times; past that the executor aborts and
    # the sweep degrades to the serial schedule mid-pass.
    chunk_retries: int = 1
    pipeline_stage_retries: int = 1
    # sweep input (--audit-source): 'relist' pages the cluster through
    # the lister every pass (the reference shape); 'snapshot' audits the
    # resident columnar snapshot (gatekeeper_tpu/snapshot/) — a full
    # pass evaluates resident columns with zero list/flatten cost, and
    # `audit_tick` evaluates only the watch-dirtied row set (O(churn)).
    # Snapshot mode ignores match_kind_only (the router already scopes
    # evaluation to kinds some template can match).
    audit_source: str = "relist"
    # snapshot mode: every Nth interval runs the full-resync
    # differential (fresh relist + re-flatten asserted bit-identical to
    # the resident snapshot) instead of an incremental tick; 0 = never
    resync_every: int = 10
    # rotate the resync differential over 1/K of the RowIdMap keyspace
    # per resync interval (--snapshot-resync-rotate): each rotated
    # resync re-flattens only its deterministic key-hash slice, so the
    # bit-identity proof amortizes to ~1/K cost per interval and K
    # consecutive resyncs cover every row (a one-shot 40k-object
    # re-flatten on the 1-core host is ~19s; rotated at K=8 each
    # interval pays ~1/8 of that).  Rotated resyncs prove the STORE
    # (columns + vocab + membership); the cluster-global verdict
    # differential (top-k is a whole-cluster property) runs only when
    # rotation is off.  0/1 = off (the one-shot full differential)
    resync_rotate: int = 0
    # data-parallel chunk sharding (--shard-chunks): pack K consecutive
    # same-group chunks into ONE mesh-wide dispatch — the object axis
    # already shards over the mesh's 'data' axis (parallel/sharded.py
    # shard_batch_arrays), so with K ~= n_devices each chip evaluates
    # ~chunk_size objects while the per-dispatch fixed costs (masks,
    # wire pack, device_put commands, jit call) amortize K-fold.
    # Verdicts are bit-identical to unsharded: objects keep their
    # canonical listed order inside the packed chunk, so totals,
    # top-k kept selection and rendered messages are unchanged
    # (asserted by the simulated-mesh parity tests).  0/1 = off
    # (every chunk dispatches alone — the single-chip reference path)
    shard_chunks: int = 0
    # expansion generator stage (--audit-expand): generator objects
    # (Deployment etc.) listed by the sweep expand through the batched
    # mutlane.ExpansionStage and their resultants (implied Pods, with
    # Source=Generated mutation applied) are audited at sweep scale with
    # the template's enforcementAction override — policies on the
    # generated GVK see violations BEFORE any Pod exists (shift-left).
    # Generated objects bypass match_kind_only (their kinds come from
    # the templates, not the lister).
    expand_generated: bool = False


@dataclass
class Violation:
    constraint: Constraint
    message: str
    enforcement_action: str
    group: str
    version: str
    kind: str
    name: str
    namespace: str
    details: Any = None


@dataclass
class AuditRun:
    timestamp: str = ""
    total_objects: int = 0
    total_violations: dict = field(default_factory=dict)  # (kind,name) -> int
    kept: dict = field(default_factory=dict)  # (kind,name) -> list[Violation]
    duration_s: float = 0.0
    # partial-result marker: True when any chunk was dropped after
    # exhausting its retries or the lister died mid-sweep — totals/kept
    # then UNDERCOUNT and downstream consumers (status writeback, export,
    # `--once` output) see the run flagged instead of silently short
    incomplete: bool = False
    failed_chunks: int = 0
    retried_chunks: int = 0
    # effective ingest/dispatch geometry of the pass, recorded so
    # `--once` output is self-describing (no cross-referencing of flags
    # to know what a run measured)
    flatten_workers: int = 0
    n_devices: int = 0
    shard_chunks: int = 0


def violation_rows(bits_or_hits, ci: int, n: int) -> np.ndarray:
    """Violating object indices of local constraint ``ci`` from either
    collect shape: bit-packed verdict rows (the masks lane) or a
    device-reduced ``HitRows`` coordinate list (``--collect=reduced``;
    duck-typed so this module stays jax-free for the sidecar control
    plane).  The single accessor every exact/snapshot fold shares — both
    collect lanes are bit-identical through it by construction."""
    rows = getattr(bits_or_hits, "rows", None)
    if rows is not None:
        return rows(ci)
    return np.nonzero(np.unpackbits(bits_or_hits[ci], count=n))[0]


def _sweep_ready(pending) -> bool:
    """True when a submitted sweep's result needs no further wait
    (non-blocking).  Empty submits ({}) are always ready; RPC futures
    (RemoteEvaluator) answer via ``done()``; local sweeps via the jax
    arrays' ``is_ready()``."""
    done = getattr(pending, "done", None)
    if callable(done):  # grpc future from RemoteEvaluator.sweep_submit
        try:
            return bool(done())
        except Exception:
            return True  # the error surfaces at sweep_collect
    res = getattr(pending, "result", None)
    if res is None:
        return True
    arrs = res if isinstance(res, tuple) else (res,)
    try:
        return all(a.is_ready() for a in arrs)
    except AttributeError:  # test evaluators returning plain numpy
        return True


_UNSEEN = object()  # render(): the chunk has not asked for this object yet


class AuditManager:
    """One audit plane instance (the reference's audit Deployment pod)."""

    def __init__(
        self,
        client: Client,
        lister: Callable[[], Iterable[dict]],
        config: Optional[AuditConfig] = None,
        evaluator=None,  # parallel.sharded.ShardedEvaluator (optional)
        status_writer: Optional[Callable] = None,
        export_system=None,
        event_sink: Optional[Callable] = None,
        log_violations: bool = False,
        metrics=None,  # metrics.registry.MetricsRegistry (optional)
        snapshot=None,  # snapshot.ClusterSnapshot (audit_source=snapshot)
        expansion_system=None,  # expansion.ExpansionSystem (expand stage)
        spiller=None,  # snapshot.SnapshotSpiller (--snapshot-spill)
        cluster: str = "",  # fleet scope: labels staleness gauges
        residency=None,  # snapshot.DeviceResidency (resident tick lane)
    ):
        self.client = client
        self.lister = lister
        self.config = config or AuditConfig()
        self.evaluator = evaluator
        if evaluator is not None:
            # the sweep's match masks find a namespaced object's Namespace
            # where this client's interpreter does (target.Matcher.match)
            evaluator.namespace_of = client.target.cache.get
        self.status_writer = status_writer
        self.export_system = export_system
        self.event_sink = event_sink
        self.log_violations = log_violations
        self.metrics = metrics
        self.snapshot = snapshot
        # fleet mode (fleet/evaluator.py): a non-empty cluster id adds
        # a {cluster}-labeled copy of the last-run gauges so the
        # per-cluster audit-staleness SLO objectives (observability/
        # slo.py per_cluster_objectives) can age each cluster's audit
        # independently off one shared registry
        self.cluster = cluster
        # device-resident snapshot lane (snapshot/device_residency.py):
        # when set, _snapshot_eval prefers resident chunks (gather-index
        # H2D only) and falls back to host columns per group whenever
        # the residency declines (no device, extdata, eviction)
        self.residency = residency
        self.expansion_system = expansion_system
        # expansion generator stage state: the batched stage (lazy), the
        # per-sweep generator-object tee, the Namespace inventory the
        # expand needs, and — snapshot mode — per-parent-gid generated
        # verdicts so the stage stays O(churn) like the base rows
        self._expansion_stage = None
        self._gen_buf: Optional[list] = None
        self._gen_ns: dict = {}
        self._gen_kinds: set = set()
        self._gen_verdicts: dict = {}
        # snapshot mode: the target's NamespaceCache version the kept
        # verdicts were matched under (_follow_namespace_labels)
        self._ns_labels_seen: Optional[int] = None
        # snapshot spill writer (snapshot/persist.py): a clean resync
        # requests a background spill, run_forever's exit flushes a
        # final one (the drain guarantee); None = persistence off
        self.spiller = spiller
        if spiller is not None:
            self.attach_spiller(spiller)
        # human-readable first difference of the last resync differential
        # (None = bit-identical), for tests/ops introspection
        self.last_resync_diff: Optional[str] = None
        # rotated-resync rotor position (wraps mod resync_rotate)
        self._resync_phase = 0
        self._stop = threading.Event()
        # per-phase seconds for the host-side fold/render of device sweeps
        # (the evaluator tracks its own flatten/masks/wire/dispatch/collect)
        self.perf: dict = {}
        # last pass's rendered messages, by every input of a render
        # (audit/render_memo.py); alive across passes
        self._render_memo = RenderMemo()
        # per-stage breakdown of the last pipelined sweep (JSON-ready dict
        # from pipeline.executor.PipelineRun.summary + the collect stage's
        # head-of-line wait); None when the last sweep ran the serial
        # schedule
        self.pipe_stats: Optional[dict] = None

    def _perf_add(self, key: str, value: float) -> None:
        self.perf[key] = self.perf.get(key, 0.0) + value

    # --- spill persistence (snapshot/persist.py) -------------------------
    def attach_spiller(self, spiller) -> None:
        """Wire a SnapshotSpiller: the manager feeds it the expansion
        stage's generated verdicts (they ride the spill's aux section so
        a warm boot's totals include them without re-expanding clean
        parents) and flushes it at drain."""
        self.spiller = spiller
        spiller.aux_fn = lambda: {
            "gen_verdicts": dict(self._gen_verdicts)}

    def restore_spill_aux(self, aux: dict) -> None:
        """Adopt a loaded spill's aux section (persist.load's 'aux')."""
        gen = aux.get("gen_verdicts")
        if gen:
            self._gen_verdicts = dict(gen)

    # --- loop (reference: auditManagerLoop, manager.go:831) -------------
    def run_forever(self):
        if self._snapshot_mode():
            # initial full pass builds the snapshot and evaluates every
            # row; steady state is incremental ticks over the dirty set,
            # with the full-resync differential every resync_every-th
            # interval proving the snapshot still equals a fresh relist.
            # A spill-loaded snapshot (persist.load) boots WARM: rows
            # are clean with persisted verdicts, so the first pass is an
            # incremental tick — zero relist, zero flatten, zero
            # re-evaluation of clean rows
            if getattr(self.snapshot, "warm_loaded", False):
                self.audit_tick()
            else:
                self.audit()
            n = 0
            every = max(0, getattr(self.config, "resync_every", 0))
            while not self._stop.wait(self.config.interval_s):
                n += 1
                if every and n % every == 0 and \
                        not self._resync_deferred():
                    self.audit_resync()
                else:
                    self.audit_tick()
            if self.spiller is not None:
                # drain flush: a clean SIGTERM never loses the resident
                # state it just paid to build (synchronous — the process
                # is leaving anyway and the DrainCoordinator budget
                # covers it)
                self.spiller.spill_now()
            return
        while not self._stop.wait(self.config.interval_s):
            self.audit()

    def stop(self):
        self._stop.set()

    # --- one sweep (reference: audit(), manager.go:258) -----------------
    def audit(self) -> AuditRun:
        """One sweep under its root span: the per-stage busy/wall/idle
        numbers are recorded as attributes here, so a trace timeline
        carries them."""
        from gatekeeper_tpu.observability import tracing

        with tracing.span("audit.sweep") as sp:
            if self._snapshot_mode():
                sp.set_attribute("source", "snapshot")
                run = self._audit_snapshot_impl(full=True)
            else:
                run = self._audit_impl()
            sp.set_attribute("objects", run.total_objects)
            sp.set_attribute("duration_s", round(run.duration_s, 3))
            sp.set_attribute("violations",
                             sum(run.total_violations.values()))
            # effective ingest/dispatch geometry — the trace timeline
            # names what it measured without cross-referencing flags
            sp.set_attribute("flatten_workers", run.flatten_workers)
            sp.set_attribute("n_devices", run.n_devices)
            sp.set_attribute("shard_chunks", run.shard_chunks)
            if run.incomplete:
                sp.set_attribute("incomplete", True)
            if self.pipe_stats:
                sp.set_attribute("wall_s", self.pipe_stats.get("wall_s"))
                sp.set_attribute(
                    "stage_busy_sum_s",
                    self.pipe_stats.get("stage_busy_sum_s"))
                sp.set_attribute(
                    "device_wait_s", self.pipe_stats.get("device_wait_s"))
                sp.set_attribute(
                    "overlap_ratio", self.pipe_stats.get("overlap_ratio"))
            return run

    def _annotate_run(self, run: AuditRun) -> None:
        """Stamp the effective ingest/dispatch geometry onto the run."""
        run.flatten_workers = int(
            getattr(self.evaluator, "flatten_workers", 0) or 0)
        mesh = getattr(self.evaluator, "mesh", None)
        run.n_devices = int(mesh.size) if mesh is not None else 0
        run.shard_chunks = max(
            0, int(getattr(self.config, "shard_chunks", 0) or 0))

    def _audit_impl(self) -> AuditRun:
        t0 = time.time()
        run = AuditRun(timestamp=_now_rfc3339())
        self._annotate_run(run)
        constraints = [
            c for c in self.client.constraints()
            if c.actions_for(AUDIT_EP)
        ]
        if self.export_system is not None:
            self.export_system.publish_audit_started(run.timestamp)
        if not constraints:
            run.duration_s = time.time() - t0
            self._finish(run)
            return run

        kind_filter = None
        if self.config.match_kind_only:
            kind_filter = self._kinds_of(constraints)

        gen_stage = self._gen_stage()
        self._gen_reset(gen_stage is not None)
        self._begin_pass(constraints)

        limit = self.config.violations_limit
        kept: dict = {(c.kind, c.name): [] for c in constraints}
        totals: dict = {(c.kind, c.name): 0 for c in constraints}

        from gatekeeper_tpu.pipeline import resolve_schedule

        batch_driver = next(
            (d for d in self.client.drivers if hasattr(d, "query_batch")),
            None,
        )
        device = self.evaluator is not None and batch_driver is not None
        use_router = (
            device
            and getattr(self.evaluator, "renders", False) is False
        )
        # staged-pipeline eligibility: a LOCAL evaluator exposing the
        # split flatten/dispatch stages.  The sidecar lane (renders=True,
        # grpc futures) and the no-evaluator interpreter lane stay serial.
        device_capable = (
            use_router
            and hasattr(self.evaluator, "sweep_flatten")
            and hasattr(self.evaluator, "sweep_dispatch")
        )
        schedule = resolve_schedule(
            getattr(self.config, "pipeline", "auto"), device_capable)
        self.pipe_stats = None
        self.perf["pipelined"] = 1.0 if schedule == "pipelined" else 0.0

        counter = [0]
        if schedule == "differential":
            # serial is the reference schedule; the pipelined pass must
            # reproduce it bit-for-bit (totals, kept order, messages)
            self._sweep_serial(constraints, kind_filter, use_router,
                               device, kept, totals, limit, counter, run)
            kept_p: dict = {k: [] for k in kept}
            totals_p: dict = {k: 0 for k in totals}
            self._sweep_pipelined(constraints, kind_filter, use_router,
                                  kept_p, totals_p, limit, [0], run)
            diff = self._schedules_differ(kept, totals, kept_p, totals_p)
            if diff:
                raise RuntimeError(
                    f"pipeline differential mismatch: {diff}")
            self.perf["pipeline_differential_ok"] = 1.0
        elif schedule == "pipelined":
            try:
                self._sweep_pipelined(constraints, kind_filter, use_router,
                                      kept, totals, limit, counter, run)
            except Exception as e:
                # graceful degradation: a pipeline whose stage kept
                # crashing past its restart budget aborts cleanly — the
                # sweep reruns on the one-thread serial schedule instead
                # of losing the pass (chunks re-list from the source, so
                # nothing is dropped)
                from gatekeeper_tpu.utils.logging import log_event

                log_event("warning",
                          "pipelined sweep failed; degrading to the "
                          "serial schedule",
                          event_type="audit_degraded", error=str(e))
                if self.metrics is not None:
                    from gatekeeper_tpu.metrics import registry as M

                    self.metrics.inc_counter(
                        M.RESILIENCE_DEGRADED,
                        {"component": "audit", "to": "serial"})
                for k in kept:
                    kept[k] = []
                for k in totals:
                    totals[k] = 0
                counter[0] = 0
                self.pipe_stats = None
                self.perf["pipelined"] = 0.0
                self.perf["degraded_to_serial"] = (
                    self.perf.get("degraded_to_serial", 0.0) + 1.0)
                self._sweep_serial(constraints, kind_filter, use_router,
                                   device, kept, totals, limit, counter,
                                   run)
        else:
            self._sweep_serial(constraints, kind_filter, use_router,
                               device, kept, totals, limit, counter, run)
        run.total_objects = counter[0]

        if gen_stage is not None and self._gen_buf:
            # the generator stage: expanded resultants audit AFTER the
            # base pass so base kept-ordering stays schedule-identical
            self._sweep_generated(gen_stage, self._gen_buf, constraints,
                                  kept, totals, limit, run)

        run.total_violations = totals
        run.kept = kept
        run.duration_s = time.time() - t0
        self._report(run, constraints)
        return run

    def _report(self, run: AuditRun, constraints) -> None:
        """The pass's epilogue, after its last fold: constraint statuses,
        metrics, the export / log / event sinks."""
        from gatekeeper_tpu.observability import tracing

        t0 = time.perf_counter()
        with tracing.span("audit.report"):
            self._write_statuses(run, constraints)
            self._publish_metrics(run)
            self._finish(run)
        self._perf_add("report", time.perf_counter() - t0)
        self._render_memo.end_pass()

    # --- snapshot lane (gatekeeper_tpu/snapshot/) -------------------------
    def _snapshot_mode(self) -> bool:
        return (getattr(self.config, "audit_source", "relist")
                == "snapshot" and self.snapshot is not None)

    def audit_tick(self) -> AuditRun:
        """Incremental snapshot audit: evaluate ONLY the dirty row set
        (rows the watch patched since the last evaluation) — O(churn),
        not O(cluster).  Cluster-wide totals/kept come from the
        persistent per-row verdict store (clean rows keep their last
        results)."""
        from gatekeeper_tpu.observability import tracing

        with tracing.span("audit.tick") as sp:
            run = self._audit_snapshot_impl(full=False)
            sp.set_attribute("objects", run.total_objects)
            sp.set_attribute("duration_s", round(run.duration_s, 3))
            gc = getattr(getattr(self.evaluator, "driver", None),
                         "gen_coord", None)
            if gc is not None:
                # which template generation this tick evaluated under —
                # a tick spanning a swap shows the post-swap id and its
                # rows re-chunked (snapshot.rechunk), never a relist
                sp.set_attribute("generation", gc.gen_id)
            if run.incomplete:
                sp.set_attribute("incomplete", True)
            return run

    def _snapshot_ready(self, constraints) -> bool:
        """Adopt the constraint set, rebuild if stale, apply queued watch
        events.  Returns True when a rebuild happened."""
        snap = self.snapshot
        rebuilt = False
        rechunks = getattr(snap, "rechunk_count", 0)
        if snap.set_constraints(constraints):
            from gatekeeper_tpu.utils.logging import log_event

            n = snap.rebuild(self.lister)
            rebuilt = True
            # row ids may outlive a rebuild but the verdict store was
            # reset — generated verdicts reset with it (the full pass
            # recomputes them for every row)
            self._gen_verdicts.clear()
            log_event("info", "snapshot rebuilt",
                      event_type="snapshot_rebuilt", rows=n,
                      generation=snap.generation)
        elif getattr(snap, "rechunk_count", 0) != rechunks:
            from gatekeeper_tpu.utils.logging import log_event

            # a template/constraint (generation) change was absorbed by
            # re-chunking resident rows — zero relist; the verdict store
            # reset with the plan, so generated verdicts reset too and
            # the all-dirty tick re-derives everything
            self._gen_verdicts.clear()
            log_event("info", "snapshot rechunked (no relist)",
                      event_type="snapshot_rechunked",
                      rows=snap.live_count(),
                      generation=snap.generation)
        snap.pump()
        self._follow_namespace_labels()
        return rebuilt

    def _follow_namespace_labels(self) -> None:
        """A verdict under a ``namespaceSelector`` follows the labels of
        the row's Namespace as the target's cache holds them (what
        ``Client.add_data`` synced), and no watch event on the row says
        that they moved.  When they have since this manager last looked,
        the groups that carry such a matcher are evaluated whole; the
        others, and every tick between two such changes, stay O(churn)."""
        from gatekeeper_tpu.ir.masks import reads_namespace_labels

        version = self.client.target.cache.version
        if version != self._ns_labels_seen:
            self._ns_labels_seen = version
            self.snapshot.mark_groups_dirty(
                lambda store: reads_namespace_labels(store.cons))

    def _audit_snapshot_impl(self, full: bool) -> AuditRun:
        t0 = time.time()
        run = AuditRun(timestamp=_now_rfc3339())
        self._annotate_run(run)
        constraints = [
            c for c in self.client.constraints()
            if c.actions_for(AUDIT_EP)
        ]
        if self.export_system is not None:
            self.export_system.publish_audit_started(run.timestamp)
        if not constraints:
            run.duration_s = time.time() - t0
            self._finish(run)
            return run
        snap = self.snapshot
        self._snapshot_ready(constraints)
        self._begin_pass(constraints)
        rows = snap.all_rows() if full else snap.dirty_rows()
        self.perf["snapshot_rows_evaluated"] = (
            self.perf.get("snapshot_rows_evaluated", 0.0)
            + sum(len(v) for v in rows.values()))
        # tick H2D meter: bytes this tick shipped host->device, summed
        # over the resident lane's honest counter (gather indices, cache
        # misses, residency patches) and the host lane's wire pack — a
        # warm clean-rows resident tick reads ZERO
        ev = self.evaluator
        h2d0 = (ev.perf.get("resident_h2d_bytes", 0.0)
                + ev.perf.get("wire_bytes", 0.0)) if ev is not None else 0.0
        self._snapshot_eval(rows, run)
        # generator stage rides the same dirty set: only (re)evaluated
        # parents re-expand, clean parents keep their generated verdicts
        self._snapshot_generated(rows, constraints, run)
        run.total_objects = snap.live_count()
        totals, kept = self._snapshot_collect(constraints)
        run.total_violations = totals
        run.kept = kept
        run.duration_s = time.time() - t0
        if ev is not None:
            tick_h2d = (ev.perf.get("resident_h2d_bytes", 0.0)
                        + ev.perf.get("wire_bytes", 0.0)) - h2d0
            self.perf["tick_h2d_bytes"] = tick_h2d
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                labels = {"cluster": self.cluster} if self.cluster \
                    else None
                self.metrics.set_gauge(M.TICK_H2D_BYTES,
                                       float(tick_h2d), labels)
        snap.publish_metrics()
        self._report(run, constraints)
        return run

    def _snapshot_eval(self, rows_by_store, run) -> None:
        """Evaluate snapshot rows group by group: resident columns slice
        straight into device sweep chunks (zero flatten), non-lowered
        kinds run their drivers' exact lane over the same rows; each
        evaluated row's verdict-store entries are REPLACED.  A chunk that
        exhausts its retries keeps its rows dirty and its previous
        (stale-but-complete) entries, and flags the run incomplete."""
        from collections import deque

        snap = self.snapshot
        ev = self.evaluator
        retries = max(0, getattr(self.config, "chunk_retries", 1))
        # chunk sharding (see AuditConfig.shard_chunks): snapshot rows
        # slice into K-chunk-wide dispatches so the mesh data axis sees
        # K x chunk_size objects per submit; verdict-store totals/kept
        # are per-row and chunk-split-independent, so this is purely a
        # dispatch-geometry change
        shard_k = max(1, int(getattr(self.config, "shard_chunks", 0) or 1))
        chunk_size = max(1, self.config.chunk_size) * shard_k
        max_inflight = max(1, self.config.submit_window)
        from gatekeeper_tpu.observability import tracing

        for store, rowlist in rows_by_store.items():
            cons_g = store.cons
            # resident lane: sync the device mirror ONCE per store per
            # tick (scatter-patch for dirty rows, nothing when clean);
            # None means this group serves host columns this tick
            rg = None
            if self.residency is not None and ev is not None \
                    and store.lowered:
                rg = self.residency.prepare(store)
            window: deque = deque()

            def submit_chunk(gids, positions, objects, _rg=rg):
                if _rg is not None:
                    flat = ev.sweep_flatten_resident(
                        _rg, positions, return_bits=True)
                    if flat is not None:
                        return ev.sweep_dispatch(flat)
                    # generation swapped mid-tick: host path handles it
                batch = store.slice_rows(positions,
                                         pad_n=ev._pad(len(positions)))
                flat = ev.sweep_flatten_from_batch(
                    cons_g, batch, objects, return_bits=True,
                    alias=store.alias)
                return ev.sweep_dispatch(flat)

            def chunk_failed(exc):
                run.failed_chunks += 1
                run.incomplete = True
                from gatekeeper_tpu.utils.logging import log_event

                log_event("warning",
                          "snapshot audit chunk dropped after exhausting "
                          "retries (rows stay dirty; previous verdicts "
                          "kept)", event_type="audit_chunk_failed",
                          phase="snapshot", error=str(exc))
                if self.metrics is not None:
                    from gatekeeper_tpu.metrics import registry as M

                    self.metrics.inc_counter(M.RESILIENCE_CHUNKS_FAILED)

            def fold_oldest():
                pending, gids, positions, objects, chunk_i = \
                    window.popleft()
                with tracing.span("audit.chunk.collect_fold",
                                  chunk=chunk_i, objects=len(gids)):
                    last = None
                    swept = None
                    for attempt in range(retries + 1):
                        try:
                            if attempt > 0:
                                run.retried_chunks += 1
                                pending = submit_chunk(gids, positions,
                                                       objects)
                            swept = ev.sweep_collect(pending)
                            break
                        except Exception as e:  # noqa: PERF203
                            last = e
                    else:
                        chunk_failed(last)
                        return
                    try:
                        t0 = time.perf_counter()
                        self._fold_snapshot_chunk(swept, cons_g, gids,
                                                  objects)
                        snap.mark_clean(gids)
                        self._perf_add("fold_render", time.perf_counter() - t0)
                    except Exception as e:
                        chunk_failed(e)

            for ci, i in enumerate(range(0, len(rowlist), chunk_size)):
                chunk = rowlist[i: i + chunk_size]
                gids = [g for g, _p in chunk]
                positions = [p for _g, p in chunk]
                objects = [store.row_obj(p) for p in positions]
                pending = None
                if store.lowered and ev is not None:
                    with tracing.span("audit.chunk.submit", chunk=ci,
                                      objects=len(gids)):
                        last = None
                        for attempt in range(retries + 1):
                            try:
                                if attempt > 0:
                                    run.retried_chunks += 1
                                pending = submit_chunk(gids, positions,
                                                       objects)
                                break
                            except Exception as e:  # noqa: PERF203
                                last = e
                        else:
                            chunk_failed(last)
                            continue
                window.append((pending, gids, positions, objects, ci))
                while window and (len(window) > max_inflight
                                  or _sweep_ready(window[0][0])):
                    fold_oldest()
            while window:
                fold_oldest()

    # --- fleet seam (gatekeeper_tpu/fleet/evaluator.py) ------------------
    def fold_snapshot_segment(self, swept, cons_g, gids, objects) -> None:
        """Fold ONE cluster's segment of a fleet-packed dispatch into
        this manager's verdict store and mark its rows clean — the
        packed twin of the per-chunk collect+fold in
        :meth:`_snapshot_eval`.  ``swept`` carries segment-rebased hit
        rows (``fleet.evaluator._SegmentHits`` duck-types the bits
        slot), so the fold is bit-identical to an unpacked chunk of the
        same rows: device hits replace verdict-store entries (exact
        mode renders every hit now), non-lowered constraints run the
        drivers' exact lane over the segment's objects."""
        self._fold_snapshot_chunk(swept, cons_g, gids, objects)
        self.snapshot.mark_clean(gids)

    def snapshot_collect(self, constraints) -> tuple:
        """(totals, kept) off the verdict store — the fleet scheduler's
        per-cluster derivation (same path the snapshot tick uses)."""
        return self._snapshot_collect(constraints)

    def _begin_pass(self, constraints) -> None:
        """Size the render memo for the pass and put the renderer's and
        :meth:`_violation`'s counters into ``perf``, a 0 too: a pass of
        nothing but hits still reports how many renders it ran."""
        self._render_memo.begin_pass(len(constraints),
                                     self.config.violations_limit)
        for key in ("n_renders", "render_memo_hits", "render_memo_bypass",
                    "violation_peeked", "violation_loaded"):
            self.perf[key] = self.perf.get(key, 0)
        self._perf_add("render", 0.0)

    def _render_fn(self, source=SOURCE_ORIGINAL, reviews=None):
        """``render(con, obj, cache_key=None)``: the exact-engine render
        for one (constraint, object) hit, the one path of the relist fold,
        the snapshot lane and the generator stage, so messages/details are
        bit-identical across audit sources.  ``cache_key`` names the
        object within the caller's chunk; ``reviews`` is the caller's
        review cache under those keys.

        The render memo stands in front of the interpreter.  Its key holds
        every input of a render: the driver's ``render_token`` for the
        constraint (template modules, the Constraint object, the data
        epoch where the template reads ``data``), ``source``, and the
        bytes of an object that was an unloaded ``RawJSON`` when the chunk
        first asked for it (after that only this fold loads it, to read
        it).  The review is built here from the object and ``source``
        alone, so it carries no namespace object, and ``cfg`` is the one
        built here, which asks for no trace and no stats.  Anything else
        renders as ever and is counted in ``perf["render_memo_bypass"]``:
        a loaded or plain-dict object, a driver or template without a
        token."""
        target = self.client.target
        driver = next(
            (d for d in self.client.drivers if hasattr(d, "query_batch")),
            None,
        )
        cfg = ReviewCfg(enforcement_point=AUDIT_EP)
        if reviews is None:
            reviews = {}
        raws: dict = {}  # cache_key -> the object's bytes, None = bypass
        memo = self._render_memo
        token_of = getattr(driver, "render_token", None)

        def render(con, obj, cache_key=None):
            perf = self.perf
            raw = raws.get(cache_key, _UNSEEN)  # None is never filed
            if raw is _UNSEEN:
                raw = obj.raw if type(obj) is RawJSON \
                    and not obj._loaded else None
                if cache_key is not None:
                    raws[cache_key] = raw
            key = None
            if raw is not None and token_of is not None:
                token = token_of(con)
                if token is not None:
                    key = (token, source, raw)
                    results = memo.get(key)
                    if results is not None:
                        perf["render_memo_hits"] = \
                            perf.get("render_memo_hits", 0) + 1
                        return results
            if key is None:
                perf["render_memo_bypass"] = \
                    perf.get("render_memo_bypass", 0) + 1
            perf["n_renders"] = perf.get("n_renders", 0) + 1
            t0 = time.perf_counter()
            review = reviews.get(cache_key) if cache_key is not None \
                else None
            if review is None:
                review = target.handle_review(AugmentedUnstructured(
                    object=obj, source=source))
                if cache_key is not None:
                    reviews[cache_key] = review
            if hasattr(driver, "render_query"):
                results = driver.render_query(
                    target.name, con, review, cfg).results
            else:
                results = driver._interp.query(
                    target.name, [con], review, cfg).results
            self._attr_render(con, time.perf_counter() - t0)
            if key is not None:
                memo.put(key, results)
            return results

        return render

    def _attr_render(self, con, dt: float) -> None:
        """One exact-engine render's seconds (the host-side cost of a
        device hit): into ``perf["render"]`` beside ``n_renders``, and
        to its template exactly — no apportioning needed, the call IS
        template-scoped."""
        from gatekeeper_tpu.observability import costattr

        self._perf_add("render", dt)
        attr = costattr.active()
        if attr is not None:
            attr.record(con.kind, costattr.EP_AUDIT,
                        costattr.PHASE_RENDER, dt, rows=1)

    def _fold_snapshot_chunk(self, swept, cons_g, gids, objects) -> None:
        """Replace the verdict-store entries of an evaluated row set:
        device hits from the bit-packed verdict rows (exact-totals mode
        renders every hit now; otherwise messages render lazily at kept
        time), non-lowered constraints via their drivers' exact lane."""
        snap = self.snapshot
        exact = self.config.exact_totals
        for gid in gids:
            snap.verdicts.clear_gid(gid)
        render = self._render_fn()
        k = len(gids)
        if isinstance(swept, dict):
            for kind, (kcons, idx, valid, counts, bits) in swept.items():
                for ci, con in enumerate(kcons):
                    ckey = con.key()
                    hit = violation_rows(bits, ci, k)
                    for oi in hit.tolist():
                        if exact:
                            results = render(con, objects[oi],
                                             cache_key=oi)
                            msgs = tuple(
                                (r.msg,
                                 (r.metadata or {}).get("details"))
                                for r in results)
                            snap.verdicts.set(ckey, gids[oi],
                                              len(results), msgs)
                        else:
                            snap.verdicts.set(ckey, gids[oi], 1, None)
        rest = [c for c in cons_g
                if not isinstance(swept, dict) or c.kind not in swept]
        if rest:
            per_row = self._eval_rows_via_drivers(rest, objects)
            for oi, per_con in per_row.items():
                for ckey, results in per_con.items():
                    snap.verdicts.set(ckey, gids[oi], len(results),
                                      tuple(results))

    def _eval_rows_via_drivers(self, constraints, objects,
                               source=SOURCE_ORIGINAL) -> dict:
        """Exact-lane evaluation with per-row capture:
        {oi: {con_key: [(msg, details), ...]}} — the snapshot's analog of
        :meth:`_eval_via_drivers` (same drivers, same matcher prefilter,
        results keyed per row for the verdict store)."""
        out: dict = {}
        if not constraints:
            return out
        target = self.client.target
        reviews = [
            target.handle_review(
                AugmentedUnstructured(object=o, source=source))
            for o in objects
        ]
        wanted = {c.key() for c in constraints}
        by_driver: dict = {}
        for con in constraints:
            d = self.client._template_driver.get(con.kind)
            if d is None:
                continue
            by_driver.setdefault(id(d), (d, []))[1].append(con)
        cfg = ReviewCfg(enforcement_point=AUDIT_EP)
        for d, cons in by_driver.values():
            if hasattr(d, "query_batch"):
                responses = d.query_batch(target.name, cons, reviews, cfg)
                for oi, resp in enumerate(responses):
                    for r in resp.results:
                        ckey = (r.constraint.get("kind", ""),
                                (r.constraint.get("metadata") or {})
                                .get("name", ""))
                        if ckey not in wanted:
                            continue
                        out.setdefault(oi, {}).setdefault(
                            ckey, []).append((r.msg, r.details))
                continue
            for oi, review in enumerate(reviews):
                for con in cons:
                    if not target.to_matcher(con.match).match(review):
                        continue
                    qr = d.query(target.name, [con], review, cfg)
                    if qr.results:
                        out.setdefault(oi, {}).setdefault(
                            con.key(), []).extend(
                            (r.msg, r.details) for r in qr.results)
        return out

    def _snapshot_collect(self, constraints) -> tuple:
        """(totals, kept) derived from the verdict store: totals sum
        every row's contribution; kept takes the first ``limit`` rows in
        stable row-id order (messages render lazily on first derivation
        and are cached back into the store)."""
        snap = self.snapshot
        limit = self.config.violations_limit
        totals = {c.key(): 0 for c in constraints}
        kept: dict = {c.key(): [] for c in constraints}
        render = self._render_fn()
        for con in constraints:
            ckey = con.key()
            for gid, count, msgs in snap.verdicts.rows(ckey):
                totals[ckey] += count
                if len(kept[ckey]) >= limit:
                    continue
                obj = snap.obj_of(gid)
                if msgs is None:
                    results = render(con, obj, cache_key=gid)
                    msgs = tuple(
                        (r.msg, (r.metadata or {}).get("details"))
                        for r in results)
                    snap.verdicts.set_msgs(ckey, gid, msgs)
                for msg, details in msgs:
                    if len(kept[ckey]) < limit:
                        kept[ckey].append(
                            self._violation(con, obj, msg, details))
        # generated resultants (expansion generator stage): per-parent
        # entries recomputed whenever the parent row was (re)evaluated,
        # clean parents keep their last generated verdicts — the same
        # O(churn) contract the base rows have
        dead = []
        for gid, per_con in self._gen_verdicts.items():
            if snap.obj_of(gid) is None:
                dead.append(gid)  # parent deleted since the tick
                continue
            for ckey, (count, violations) in per_con.items():
                if ckey not in totals:
                    continue
                totals[ckey] += count
                for v in violations:
                    if len(kept[ckey]) < limit:
                        kept[ckey].append(v)
        for gid in dead:
            self._gen_verdicts.pop(gid, None)
        return totals, kept

    def _eval_objects_capture(self, constraints, objects, source) -> tuple:
        """({oi: {con_key: [(msg, details)]}}, lowered_kinds) — evaluate
        arbitrary objects with per-object capture: device grid + exact
        render for lowered kinds, driver exact lane for the rest.  The
        expansion stage's evaluator for generated resultants."""
        import numpy as np

        out: dict = {}
        swept: dict = {}
        ev = self.evaluator
        device = (ev is not None
                  and getattr(ev, "renders", False) is False
                  and hasattr(ev, "sweep_flatten"))
        if device and objects:
            flat = ev.sweep_flatten(constraints, objects,
                                    return_bits=True, source=source)
            if flat:
                swept = ev.sweep_collect(ev.sweep_dispatch(flat))
        render = self._render_fn(source=source)
        k = len(objects)
        if isinstance(swept, dict):
            for _kind, (kcons, idx, valid, counts, bits) in swept.items():
                for ci, con in enumerate(kcons):
                    hit = violation_rows(bits, ci, k)
                    for oi in hit.tolist():
                        results = render(con, objects[oi], cache_key=oi)
                        out.setdefault(oi, {}).setdefault(
                            con.key(), []).extend(
                            (r.msg, (r.metadata or {}).get("details"))
                            for r in results)
        rest = [c for c in constraints if c.kind not in swept]
        if rest:
            for oi, per_con in self._eval_rows_via_drivers(
                    rest, objects, source=source).items():
                for ckey, results in per_con.items():
                    out.setdefault(oi, {}).setdefault(
                        ckey, []).extend(results)
        return out, set(swept.keys()) if isinstance(swept, dict) else set()

    def _snapshot_generated(self, rows_by_store, constraints, run) -> None:
        """Recompute the generated-resultant verdicts of every parent row
        that was just (re)evaluated: expand through the batched stage,
        evaluate resultants with Source=Generated, store per parent gid.
        A parent that stopped being a generator (or was deleted) simply
        loses its entry."""
        stage = self._gen_stage()
        if stage is None:
            if self._gen_verdicts:
                self._gen_verdicts.clear()
            return
        from gatekeeper_tpu.match.match import SOURCE_GENERATED
        from gatekeeper_tpu.utils.logging import log_event
        from gatekeeper_tpu.utils.unstructured import gvk_of

        snap = self.snapshot
        templates = self.expansion_system.templates()
        gens: list = []
        for store, rowlist in rows_by_store.items():
            for gid, pos in rowlist:
                obj = store.row_obj(pos)
                self._gen_verdicts.pop(gid, None)
                if obj is None:
                    continue
                for t in templates:
                    if t.applies_to(obj):
                        gens.append((gid, obj))
                        break
        if not gens:
            return
        cons_by_key = {c.key(): c for c in constraints}
        exact = self.config.exact_totals
        chunk_size = max(1, self.config.chunk_size)
        for i in range(0, len(gens), chunk_size):
            part = gens[i:i + chunk_size]
            namespaces = []
            for _gid, obj in part:
                ns = (obj.get("metadata") or {}).get("namespace", "") or ""
                namespaces.append(snap.namespace(ns) if ns else None)
            results = stage.expand_batch([o for _g, o in part],
                                         namespaces)
            resultants: list = []  # (parent gid, obj, template, action)
            for (gid, obj), res in zip(part, results):
                if res.error is not None:
                    log_event("warning",
                              "audit expansion failed for a generator "
                              "object", event_type="audit_expand_failed",
                              name=(obj.get("metadata") or {})
                              .get("name", ""), error=str(res.error))
                    continue
                resultants.extend(
                    (gid, r.obj, r.template_name, r.enforcement_action)
                    for r in res.resultants)
            if not resultants:
                continue
            captured, lowered = self._eval_objects_capture(
                constraints, [r[1] for r in resultants],
                SOURCE_GENERATED)
            for oi, (gid, robj, tname, action) in enumerate(resultants):
                for ckey, results in captured.get(oi, {}).items():
                    con = cons_by_key.get(ckey)
                    if con is None:
                        continue
                    # totals parity with the relist generator stage:
                    # non-exact device-lowered kinds count violating
                    # OBJECTS, everything else counts results
                    count = (len(results)
                             if exact or con.kind not in lowered else 1)
                    violations = [
                        self._violation(con, robj, msg, details,
                                        override=(tname, action))
                        for msg, details in results]
                    slot = self._gen_verdicts.setdefault(
                        gid, {}).setdefault(ckey, [0, []])
                    slot[0] += count
                    slot[1].extend(violations)

    def audit_resync(self) -> AuditRun:
        """The periodic full-resync differential (snapshot mode): drain
        the dirty set, then re-list + re-flatten fresh and assert the
        resident snapshot is bit-identical — columns (per-row signatures
        over the same vocab), vocab (the fresh flatten interns nothing
        new), and verdicts (totals + kept against a fresh relist sweep
        through the serial schedule).  Divergence marks the run
        incomplete and invalidates the snapshot: the next sweep
        rebuilds."""
        from gatekeeper_tpu.observability import tracing

        t0 = time.time()
        rotate = max(0, getattr(self.config, "resync_rotate", 0))
        rotor = None
        if rotate > 1:
            rotor = (self._resync_phase % rotate, rotate)
            self._resync_phase = (self._resync_phase + 1) % rotate
        with tracing.span("snapshot.resync") as sp:
            if rotor is not None:
                sp.set_attribute("rotor_phase", rotor[0])
                sp.set_attribute("rotor_k", rotor[1])
            run = self._audit_snapshot_impl(full=False)
            snap = self.snapshot
            diff = snap.resync_differential(self.lister, rotor=rotor)
            if diff is None and rotor is None:
                constraints = [
                    c for c in self.client.constraints()
                    if c.actions_for(AUDIT_EP)
                ]
                kept_f: dict = {c.key(): [] for c in constraints}
                totals_f: dict = {c.key(): 0 for c in constraints}
                fr = AuditRun(timestamp=run.timestamp)
                batch_driver = next(
                    (d for d in self.client.drivers
                     if hasattr(d, "query_batch")), None)
                device = (self.evaluator is not None
                          and batch_driver is not None)
                use_router = (
                    device
                    and getattr(self.evaluator, "renders", False) is False)
                gen_stage = self._gen_stage()
                self._gen_reset(gen_stage is not None)
                self._sweep_serial(constraints, None, use_router, device,
                                   kept_f, totals_f,
                                   self.config.violations_limit, [0], fr)
                if gen_stage is not None and self._gen_buf:
                    # the reference sweep must expand too, or the
                    # differential would flag every generated verdict
                    self._sweep_generated(gen_stage, self._gen_buf,
                                          constraints, kept_f, totals_f,
                                          self.config.violations_limit,
                                          fr)
                self._gen_reset(False)
                diff = self._verdicts_differ_canonical(
                    run.kept, run.total_violations, kept_f, totals_f,
                    self.config.violations_limit)
            self.last_resync_diff = diff
            dt = time.time() - t0
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                self.metrics.set_gauge(M.SNAPSHOT_RESYNC_SECONDS, dt)
            if diff is not None:
                sp.set_attribute("diverged", diff)
                run.incomplete = True
                snap.invalidate()
                from gatekeeper_tpu.utils.logging import log_event

                log_event("warning",
                          "snapshot resync differential diverged; "
                          "snapshot invalidated (next sweep rebuilds)",
                          event_type="snapshot_resync_diverged",
                          difference=diff)
                if self.metrics is not None:
                    from gatekeeper_tpu.metrics import registry as M

                    self.metrics.inc_counter(
                        M.RESILIENCE_DEGRADED,
                        {"component": "snapshot", "to": "rebuild"})
            self.perf["resync_ok"] = 0.0 if diff else 1.0
            # rotated resyncs prove the store slice-by-slice; record the
            # scope so operators can tell a 1/K proof from the full one
            self.perf["resync_scope"] = (1.0 / rotor[1]) if rotor \
                else 1.0
            if diff is None and self.spiller is not None:
                # a just-proven-consistent snapshot is the best state to
                # persist: capture now (under-lock memcpy), write on the
                # spiller's worker — the next tick is untouched
                self.spiller.request()
            return run

    @staticmethod
    def _verdicts_differ_canonical(kept_a, totals_a, kept_b, totals_b,
                                   limit):
        """None when two runs' verdicts agree; kept lists compare as
        CANONICAL (sorted) sets — chunk order legitimately differs
        between the snapshot's row order and a relist's list order, and
        when a constraint's violations exceed the kept limit the top-K
        *selection* under different orders is not canonical (only the
        kept COUNT is compared there; totals stay exact always)."""
        if totals_a != totals_b:
            keys = [k for k in totals_a
                    if totals_a.get(k) != totals_b.get(k)]
            return (f"totals differ for {keys[:3]}: "
                    f"{[totals_a.get(k) for k in keys[:3]]} vs "
                    f"{[totals_b.get(k) for k in keys[:3]]}")
        if set(kept_a) != set(kept_b):
            return "kept constraint sets differ"
        for key in kept_a:
            va = sorted((v.message, v.kind, v.name, v.namespace,
                         v.enforcement_action) for v in kept_a[key])
            vb = sorted((v.message, v.kind, v.name, v.namespace,
                         v.enforcement_action) for v in kept_b[key])
            if len(va) != len(vb):
                return f"kept counts differ for {key}"
            if len(va) < limit and va != vb:
                return f"kept violations differ for {key}"
        return None

    # --- overload brownout (resilience/overload.py) ----------------------
    def _brownout_yield(self) -> None:
        """Brownout level-2 hook: while the webhook admission queue is
        under heavy pressure, the sweep yields the device lane before
        submitting its next chunk (bounded per call — audit slows, never
        stalls).  A no-op without an installed OverloadController, and
        released entirely while a breaching audit-staleness objective
        holds ``audit_yield_release`` (yield_device_lane checks it)."""
        from gatekeeper_tpu.resilience import overload

        overload.yield_device_lane(cluster=self.cluster)

    def _resync_deferred(self) -> bool:
        """``resync_defer`` degradation action: a breaching
        audit-staleness objective defers the periodic full-resync
        differential (an expensive relist + full re-evaluation) so the
        interval budget goes to catching the dirty set up.  Deferrals
        are counted — a resync deferred is visible, not silent."""
        from gatekeeper_tpu.resilience import overload

        if not overload.degradation_active(overload.RESYNC_DEFER,
                                           self.cluster):
            return False
        if self.metrics is not None:
            from gatekeeper_tpu.metrics import registry as M

            self.metrics.inc_counter(
                M.RESILIENCE_DEGRADED,
                {"component": "audit", "to": "resync_defer"})
        return True

    # --- expansion generator stage (mutlane/expand_stage.py) -------------
    def _gen_stage(self):
        """The batched expansion stage, or None when the generator stage
        is off / has nothing to do."""
        if not getattr(self.config, "expand_generated", False):
            return None
        if self.expansion_system is None or \
                not self.expansion_system.templates():
            return None
        if self._expansion_stage is None:
            from gatekeeper_tpu.mutlane import ExpansionStage

            self._expansion_stage = ExpansionStage(
                self.expansion_system, metrics=self.metrics)
        return self._expansion_stage

    def _gen_reset(self, active: bool) -> None:
        """Arm (or disarm) the per-sweep generator tee."""
        self._gen_buf = [] if active else None
        self._gen_ns = {}
        self._gen_kinds = set()
        if active:
            for t in self.expansion_system.templates():
                for entry in t.apply_to:
                    self._gen_kinds.update(entry.get("kinds") or [])

    def _gen_tee(self, obj, kind: str) -> None:
        """Observe one listed object: collect Namespaces (the expand's
        namespace context) and generator objects (some template's
        applyTo covers them).  RawJSON objects only parse when their
        kind pre-qualifies."""
        if self._gen_buf is None:
            return
        if kind == "Namespace":
            name = (obj.get("metadata") or {}).get("name", "") or ""
            if name:
                self._gen_ns[name] = obj
            return
        if kind in self._gen_kinds:
            for t in self.expansion_system.templates():
                if t.applies_to(obj):
                    self._gen_buf.append(obj)
                    break

    def _gen_namespace_of(self, obj):
        ns = (obj.get("metadata") or {}).get("namespace", "") or ""
        return self._gen_ns.get(ns) if ns else None

    def _expand_bases(self, stage, bases) -> tuple:
        """Expand a chunk of generator bases through the batched stage;
        returns (resultants, errors) where each resultant is
        ``(obj, template_name, enforcement_override, ns_obj)``."""
        namespaces = [self._gen_namespace_of(b) for b in bases]
        results = stage.expand_batch(bases, namespaces)
        resultants: list = []
        errors: list = []
        for base, ns_obj, res in zip(bases, namespaces, results):
            if res.error is not None:
                errors.append((base, res.error))
                continue
            for r in res.resultants:
                resultants.append((r.obj, r.template_name,
                                   r.enforcement_action, ns_obj))
        return resultants, errors

    def _sweep_generated(self, stage, bases, constraints, kept, totals,
                         limit, run=None) -> None:
        """The generator stage of a relist sweep: expand the tee'd
        generator objects in chunks, then audit every resultant at sweep
        scale — device grid for lowered kinds (flattened with
        Source=Generated so source-scoped matches hold), driver exact
        lane for the rest — folding into the same kept/totals with the
        template's enforcementAction override and the reference's
        ``[Implied by <template>]`` message prefix."""
        from gatekeeper_tpu.match.match import SOURCE_GENERATED
        from gatekeeper_tpu.observability import tracing
        from gatekeeper_tpu.utils.logging import log_event

        chunk_size = max(1, self.config.chunk_size)
        retries = max(0, getattr(self.config, "chunk_retries", 1))
        device = (self.evaluator is not None
                  and getattr(self.evaluator, "renders", False) is False
                  and hasattr(self.evaluator, "sweep_flatten"))
        router = None
        if device:
            from gatekeeper_tpu.parallel.sharded import make_kind_router

            router = make_kind_router(constraints)

        n_resultants = 0
        with tracing.span("expansion.stage", phase="audit",
                          bases=len(bases)) as sp:
            for i in range(0, len(bases), chunk_size):
                resultants, errors = self._expand_bases(
                    stage, bases[i:i + chunk_size])
                for base, err in errors:
                    # mirrors the webhook's ExpansionError handling:
                    # surfaced, never silently dropped, run keeps going
                    log_event("warning",
                              "audit expansion failed for a generator "
                              "object", event_type="audit_expand_failed",
                              name=(base.get("metadata") or {})
                              .get("name", ""), error=str(err))
                n_resultants += len(resultants)
                self._eval_generated_chunks(
                    resultants, constraints, kept, totals, limit, run,
                    router, device, chunk_size, retries,
                    SOURCE_GENERATED)
            sp.set_attribute("resultants", n_resultants)

    def _eval_generated_chunks(self, resultants, constraints, kept,
                               totals, limit, run, router, device,
                               chunk_size, retries, source) -> None:
        """Evaluate expanded resultants grouped the way the base sweep
        groups objects (kind-bucketed router on the device path)."""
        from gatekeeper_tpu.utils.unstructured import gvk_of

        def fold(objs, cons, overrides):
            last = None
            for attempt in range(retries + 1):
                try:
                    if run is not None and attempt > 0:
                        run.retried_chunks += 1
                    if device:
                        flat = self.evaluator.sweep_flatten(
                            cons, objs,
                            return_bits=self.config.exact_totals,
                            source=source,
                            budget=lambda con: limit - len(
                                kept.get(con.key(), ())))
                        swept = self.evaluator.sweep_collect(
                            self.evaluator.sweep_dispatch(flat))
                        self._process_swept(swept, objs, cons, kept,
                                            totals, limit, source=source,
                                            overrides=overrides)
                    else:
                        self._audit_chunk(objs, cons, kept, totals,
                                          limit, source=source,
                                          overrides=overrides)
                    return
                except Exception as e:  # noqa: PERF203
                    last = e
            if run is not None:
                run.failed_chunks += 1
                run.incomplete = True
            from gatekeeper_tpu.utils.logging import log_event

            log_event("warning",
                      "generated-object audit chunk dropped after "
                      "exhausting retries",
                      event_type="audit_chunk_failed", phase="generated",
                      error=str(last))

        if router is not None:
            bufs: dict = {}
            for obj, tname, action, _ns in resultants:
                _, _, k = gvk_of(obj)
                g = router(k)
                if not g:
                    continue  # no template's match reaches this kind
                bufs.setdefault(g, []).append((obj, tname, action))
            for g, entries in bufs.items():
                cons_g = [c for c in constraints if c.kind in g]
                for j in range(0, len(entries), chunk_size):
                    part = entries[j:j + chunk_size]
                    fold([e[0] for e in part], cons_g,
                         [(e[1], e[2]) for e in part])
        else:
            for j in range(0, len(resultants), chunk_size):
                part = resultants[j:j + chunk_size]
                fold([e[0] for e in part], constraints,
                     [(e[1], e[2]) for e in part])

    # --- sweep chunk source (shared by both schedules) -------------------
    def _chunk_source(self, constraints, kind_filter, use_router, counter):
        """The chunk stream both schedules consume: the canonical
        per-group chunking (:meth:`_chunk_source_impl`), optionally
        coalesced by ``shard_chunks`` — K consecutive chunks of the SAME
        constraint group pack into one mesh-wide dispatch whose object
        axis shards over the mesh's 'data' axis.  Objects keep their
        listed order inside a packed chunk (kept selection order is
        unchanged); only cross-GROUP emission order shifts, which no
        output depends on (groups hold disjoint constraint sets)."""
        src = self._chunk_source_impl(constraints, kind_filter,
                                      use_router, counter)
        k = max(1, int(getattr(self.config, "shard_chunks", 0) or 1))
        if k <= 1:
            yield from src
            return
        pend: dict = {}  # group key -> [objects, cons, chunks packed]
        for objs, cons in src:
            key = tuple((c.kind, c.name) for c in cons)
            buf = pend.get(key)
            if buf is None:
                pend[key] = [list(objs), cons, 1]
                continue
            buf[0].extend(objs)
            buf[2] += 1
            if buf[2] >= k:
                del pend[key]
                yield buf[0], buf[1]
        for objs, cons, _count in pend.values():  # partial tails
            yield objs, cons

    def _chunk_source_impl(self, constraints, kind_filter, use_router,
                           counter):
        """Yield ``(objects, constraint_subset)`` sweep chunks in the ONE
        canonical order both schedules share — the pipelined fold and the
        serial fold therefore see identical chunk sequences, which is what
        makes their outputs bit-identical.

        kind-bucketed routing (device path): objects stream into
        per-kind-group chunks (parallel/sharded.make_kind_router — the
        match-kinds prefilter of manager.go:427-483 applied per
        template), so a Service chunk never flattens/ships/evaluates
        container columns, and objects no template can match skip the
        device entirely.  ``counter[0]`` accumulates listed (post
        kind-filter) objects."""
        if self._gen_buf is not None:
            # one tee per sweep pass: the differential schedule runs
            # this generator twice — a stale buffer would double-expand
            self._gen_buf = []
        chunk_size = self.config.chunk_size
        # listed by the native call / one at a time / taken off the
        # cyclic collector's lists by the native call
        counts = [0, 0, 0]
        try:
            if use_router:
                from gatekeeper_tpu.observability import tracing
                from gatekeeper_tpu.ops.listroute import route_chunks
                from gatekeeper_tpu.parallel.sharded import make_kind_router

                cons_of_group: dict = {}
                # the armed expansion tee sees every listed object with
                # its kind: such a pass stays on the per-object loop
                tee = self._gen_tee if self._gen_buf is not None else None
                for g, buf in route_chunks(
                        self.lister(), make_kind_router(constraints),
                        chunk_size, counter, counts, kind_filter, tee):
                    tracing.set_attribute("list_fast", counts[0])
                    tracing.set_attribute("list_slow", counts[1])
                    tracing.set_attribute("list_untracked", counts[2])
                    cg = cons_of_group.get(g)
                    if cg is None:
                        cg = [c for c in constraints if c.kind in g]
                        cons_of_group[g] = cg
                    if len(buf) >= chunk_size:  # not a tail
                        self._brownout_yield()
                    yield buf, cg
            else:
                chunk: list = []
                for obj in self.lister():
                    counts[1] += 1
                    if self._gen_buf is not None or kind_filter is not None:
                        _, _, k = gvk_of(obj)
                        self._gen_tee(obj, k)
                        if kind_filter is not None and k not in kind_filter:
                            continue
                    chunk.append(obj)
                    counter[0] += 1
                    if len(chunk) >= chunk_size:
                        self._brownout_yield()
                        yield chunk, constraints
                        chunk = []
                if chunk:
                    yield chunk, constraints
        finally:
            # all three on every pass, a 0 too: the share of the listing
            # that ran as native calls (benchmark: list.fast_share) and
            # the share the collector no longer walks
            # (python_gc.untracked_share)
            self._perf_add("list_fast", counts[0])
            self._perf_add("list_slow", counts[1])
            self._perf_add("list_untracked", counts[2])

    # --- serial schedule (eager-poll, the one-core-safe path) ------------
    def _sweep_serial(self, constraints, kind_filter, use_router, device,
                      kept, totals, limit, counter, run=None):
        """Eager-poll pipelined chunking on ONE thread: the host lists +
        flattens + dispatches chunks (jit dispatch is async, so the device
        drains the queue while the host keeps flattening); after each
        submit, any in-flight chunk whose device result IS ALREADY READY
        (non-blocking ``is_ready`` poll) is collected + folded
        immediately.  The host thread therefore never blocks while
        listing continues — by the final drain only the tail chunks are
        still executing, and their wait overlaps their predecessors'
        fold/render.  On a one-core host this beats stage THREADS
        (measured: two GIL-hungry threads thrash — flatten wall-time
        doubled); single-threaded, total time ~= host CPU work with
        device+wire waits hidden.  ``submit_window`` still bounds
        in-flight chunks (host memory + device HBM)."""
        from collections import deque

        window: deque = deque()  # (pending, objects, constraint subset)
        max_inflight = max(1, self.config.submit_window)

        # reduced-collect kept budget: each dispatch tells the device how
        # many kept slots per constraint remain, so drained constraints
        # ship ZERO kept coordinates.  Read at dispatch time the budget
        # is always >= the fold-time remainder (folds only shrink it), so
        # the device selection stays a superset of what the fold keeps —
        # output is bit-identical to the unbudgeted masks fold.
        budget_fn = None
        if device and hasattr(self.evaluator, "sweep_flatten"):
            budget_fn = (lambda con:
                         limit - len(kept.get(con.key(), ())))

        # drain waiter: a host<->device link may buffer uploads until
        # something BLOCKS on a result — is_ready() alone then never
        # fires mid-listing, and every chunk's wait piles into the final
        # drain.  A daemon thread that ONLY calls jax.block_until_ready
        # (a GIL-released C++ wait, zero Python work) keeps the queue
        # draining continuously, so the main thread's eager poll finds
        # ready results while it still has flatten work to hide them
        # behind.
        waitq = None
        waiter = None
        if device and getattr(self.evaluator, "renders", False) is False:
            # local ShardedEvaluator only: the sidecar lane's pendings are
            # grpc futures (renders=True) — no jax arrays to drain, and
            # the sidecar-mode control plane is deliberately jax-free
            # (__main__.py "only the local path touches jax")
            import queue

            import jax as _jax

            waitq = queue.Queue()

            def _wait_loop():
                while True:
                    p = waitq.get()
                    if p is None:
                        return
                    try:
                        _jax.block_until_ready(p.result)
                    except Exception:
                        pass  # surfaces at sweep_collect on the main thread

            waiter = threading.Thread(target=_wait_loop, daemon=True,
                                      name="audit-drain-waiter")
            waiter.start()

        retries = max(0, getattr(self.config, "chunk_retries", 1))

        def chunk_failed(exc, phase):
            """Retry budget exhausted: skip the chunk, flag the run."""
            if run is not None:
                run.failed_chunks += 1
                run.incomplete = True
            from gatekeeper_tpu.utils.logging import log_event

            log_event("warning",
                      "audit chunk dropped after exhausting retries",
                      event_type="audit_chunk_failed", phase=phase,
                      error=str(exc))
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                self.metrics.inc_counter(M.RESILIENCE_CHUNKS_FAILED)

        def chunk_retry(exc, phase):
            if run is not None:
                run.retried_chunks += 1
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                self.metrics.inc_counter(M.RESILIENCE_RETRIES,
                                         {"dependency": "audit_chunk"})

        from gatekeeper_tpu.observability import tracing

        def fold_oldest():
            # retry covers the non-mutating phases ONLY (submit/collect):
            # once the fold touches kept/totals a re-run would double
            # count, so a fold failure drops the chunk instead
            pending, objs, cons, chunk_i = window.popleft()
            with tracing.span("audit.chunk.collect_fold", chunk=chunk_i,
                              objects=len(objs)):
                last = None
                swept = None
                for attempt in range(retries + 1):
                    try:
                        if attempt > 0:
                            # a failed collect can't be re-fetched: the whole
                            # chunk re-submits through flatten/dispatch
                            chunk_retry(last, "collect")
                            pending = self.evaluator.sweep_submit(
                                cons, objs,
                                return_bits=self.config.exact_totals,
                                **({"budget": budget_fn}
                                   if budget_fn is not None else {}))
                        swept = self.evaluator.sweep_collect(pending)
                        break
                    except Exception as e:  # noqa: PERF203
                        last = e
                else:
                    chunk_failed(last, "collect")
                    return
                try:
                    t0 = time.perf_counter()
                    self._process_swept(swept, objs, cons, kept, totals,
                                        limit)
                    self._perf_add("fold_render", time.perf_counter() - t0)
                except Exception as e:
                    chunk_failed(e, "fold")

        def submit(objects, cons, chunk_i):
            if device:
                with tracing.span("audit.chunk.submit", chunk=chunk_i,
                                  objects=len(objects)):
                    last = None
                    for attempt in range(retries + 1):
                        try:
                            if attempt > 0:
                                chunk_retry(last, "submit")
                            pending = self.evaluator.sweep_submit(
                                cons, objects,
                                return_bits=self.config.exact_totals,
                                **({"budget": budget_fn}
                                   if budget_fn is not None else {}))
                            break
                        except Exception as e:  # noqa: PERF203
                            last = e
                    else:
                        chunk_failed(last, "submit")
                        return
                    window.append((pending, objects, cons, chunk_i))
                    if waitq is not None and \
                            getattr(pending, "result", None) is not None:
                        waitq.put(pending)
                while window and (len(window) > max_inflight
                                  or _sweep_ready(window[0][0])):
                    fold_oldest()
            else:
                # interpreter lane: evaluate into CHUNK-LOCAL dicts and
                # merge only on success, so a mid-chunk failure (and its
                # retry) can never double count
                with tracing.span("audit.chunk.interp", chunk=chunk_i,
                                  objects=len(objects)):
                    last = None
                    for attempt in range(retries + 1):
                        try:
                            if attempt > 0:
                                chunk_retry(last, "interp")
                            kept_c = {c.key(): [] for c in cons}
                            totals_c = {c.key(): 0 for c in cons}
                            self._audit_chunk(objects, cons, kept_c,
                                              totals_c, limit)
                            for key, n in totals_c.items():
                                totals[key] += n
                            for key, vs in kept_c.items():
                                for v in vs:
                                    if len(kept[key]) < limit:
                                        kept[key].append(v)
                            return
                        except Exception as e:  # noqa: PERF203
                            last = e
                    chunk_failed(last, "interp")

        try:
            src = iter(self._chunk_source(constraints, kind_filter,
                                          use_router, counter))
            chunk_i = -1
            while True:
                t0 = time.perf_counter()
                c0 = time.thread_time()
                r0 = released_thread_time()
                try:
                    with tracing.span("audit.chunk.list",
                                      chunk=chunk_i + 1):
                        item = next(src, None)
                except Exception as e:
                    # the lister died mid-iteration — a generator cannot
                    # resume, so finish with what was listed and mark the
                    # pass incomplete instead of aborting it
                    if run is not None:
                        run.incomplete = True
                    from gatekeeper_tpu.utils.logging import log_event

                    log_event("warning",
                              "audit lister failed mid-sweep; finishing "
                              "with partial results",
                              event_type="audit_lister_failed",
                              error=str(e))
                    item = None
                self._perf_add("list", time.perf_counter() - t0)
                self._perf_add("list_cpu", time.thread_time() - c0)
                self._perf_add("list_released",
                               released_thread_time() - r0)
                if item is None:
                    break
                chunk_i += 1
                submit(*item, chunk_i)
            while window:  # drain: blocking collect of the tail chunks
                fold_oldest()
        finally:
            # always stop the waiter — a lister/submit/fold exception must
            # not leak a thread blocked on waitq.get() pinning queued
            # device buffers for the life of the process
            if waiter is not None:
                waitq.put(None)
                waiter.join()

    # --- pipelined schedule (staged executor) ----------------------------
    def _sweep_pipelined(self, constraints, kind_filter, use_router,
                         kept, totals, limit, counter, run=None):
        """Staged host pipeline: ``list -> flatten -> dispatch -> collect
        -> fold_render`` with one thread per stage and bounded inter-stage
        queues (pipeline/executor.py).  Chunk K's flatten (the C
        columnizer's three phases release the GIL; its items loop, array
        allocation, intern merge and assembly hold it) overlaps chunk
        K-1's collect/fold, so host work hides device/wire waits and
        vice versa; the collect stage's input bound
        is ``submit_window`` (in-flight device chunks: host memory + HBM),
        and the fold stage consumes chunks in submission order so output
        is bit-identical to the serial schedule."""
        from gatekeeper_tpu.pipeline import Stage, StagedPipeline

        import jax as _jax

        ev = self.evaluator
        cfg = self.config
        rb = cfg.exact_totals

        # reduced-collect kept budget (see _sweep_serial): evaluated at
        # DISPATCH on the dispatch stage thread while the fold stage
        # mutates kept — dict/list length reads are atomic under the GIL
        # and budgets only shrink, so a stale read over-ships, never
        # under-ships
        def budget_fn(con):
            return cfg.violations_limit - len(kept.get(con.key(), ()))

        def fl(item):
            objs, cons = item
            return (ev.sweep_flatten(cons, objs, return_bits=rb,
                                     budget=budget_fn), objs, cons)

        def disp(item):
            flat, objs, cons = item
            return ev.sweep_dispatch(flat), objs, cons

        def coll(item):
            pending, objs, cons = item
            res = getattr(pending, "result", None)
            if res is not None:
                # the stage's ONLY blocking wait: device + wire time for
                # the head-of-line chunk (a GIL-released C++ wait) — its
                # busy_s is the run's device-wait measurement
                try:
                    _jax.block_until_ready(res)
                except Exception:
                    pass  # surfaces at sweep_collect below
            return ev.sweep_collect(pending), objs, cons

        def fold(item):
            swept, objs, cons = item
            t0 = time.perf_counter()
            self._process_swept(swept, objs, cons, kept, totals, limit)
            self._perf_add("fold_render", time.perf_counter() - t0)
            return None

        from gatekeeper_tpu.pipeline import effective_cpu_count

        fw = cfg.pipeline_flatten_workers
        if fw <= 0:  # auto: a second flatten worker once cores allow it
            fw = 2 if effective_cpu_count() >= 4 else 1
        # crashed-worker restarts: flatten/dispatch/collect re-run their
        # item (idempotent, no run state touched); fold_render mutates
        # kept/totals so it gets NO retry budget — its failure aborts the
        # pipeline and the sweep degrades to the serial schedule
        sr = max(0, getattr(cfg, "pipeline_stage_retries", 1))
        pipe = StagedPipeline([
            Stage("flatten", fl, workers=fw,
                  queue_cap=cfg.pipeline_queue_cap, max_retries=sr),
            Stage("dispatch", disp, queue_cap=cfg.pipeline_queue_cap,
                  max_retries=sr),
            Stage("collect", coll,
                  queue_cap=max(1, cfg.submit_window), max_retries=sr),
            Stage("fold_render", fold, queue_cap=cfg.pipeline_queue_cap),
        ], source_cap=cfg.pipeline_queue_cap,
            released_clock=released_thread_time)
        p0 = time.process_time()
        pr = pipe.run(self._chunk_source(constraints, kind_filter,
                                         use_router, counter))
        # every thread of the process while the pipeline ran, the
        # columnizer's pthreads and XLA's included: over pipe_wall, the
        # cores the pass kept busy
        self._perf_add("pipe_process_cpu", time.process_time() - p0)
        n_retries = sum(s.retries for s in pr.stages)
        if n_retries:
            if run is not None:
                run.retried_chunks += n_retries
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                self.metrics.inc_counter(
                    M.RESILIENCE_RETRIES,
                    {"dependency": "audit_pipeline"},
                    value=float(n_retries))
        stats = pr.summary()
        # the collect stage is busy exactly while it waits for the
        # head-of-line chunk's result and fetches it.  HOST time: what the
        # device itself did in that wait only a device trace can say.
        device_wait = pr.stage("collect").busy_s
        stats["device_wait_s"] = round(device_wait, 3)
        self.pipe_stats = stats
        # the pass's account, summed over passes.  The calling thread's:
        # list + pipe_source_stall + pipe_drain == pipe_wall.  Each
        # stage's: busy - cpu is time its thread held a chunk and did not
        # run (GIL wait, or a call that released it), cpu - released is
        # the CPU it ran holding the GIL (an upper bound: numpy's and
        # XLA's own released stretches are in it), wait and stall are
        # time it had no chunk or could not hand one on — together they
        # tell a GIL-bound pipeline from a starved one.
        self._perf_add("pipe_wall", pr.wall_s)
        self._perf_add("pipe_device_wait", device_wait)
        self._perf_add("list", pr.source_busy_s)
        self._perf_add("list_cpu", pr.source_cpu_s)
        self._perf_add("list_released", pr.source_released_s)
        self._perf_add("pipe_source_stall", pr.source_stall_s)
        self._perf_add("pipe_drain", pr.drain_s)
        for st in pr.stages:
            for what in ("busy", "wait", "stall", "cpu", "released"):
                self._perf_add(f"pipe_{st.name}_{what}",
                               getattr(st, what + "_s"))
            self.perf[f"pipe_{st.name}_workers"] = float(st.workers)

    @staticmethod
    def _schedules_differ(kept_a, totals_a, kept_b, totals_b):
        """None when two schedules produced bit-identical output, else a
        human-readable first difference (differential mode)."""
        if totals_a != totals_b:
            keys = [k for k in totals_a
                    if totals_a.get(k) != totals_b.get(k)]
            return (f"totals differ for {keys[:3]}: "
                    f"{[totals_a.get(k) for k in keys[:3]]} vs "
                    f"{[totals_b.get(k) for k in keys[:3]]}")
        for key in kept_a:
            va = [(v.message, v.kind, v.name, v.namespace,
                   v.enforcement_action) for v in kept_a[key]]
            vb = [(v.message, v.kind, v.name, v.namespace,
                   v.enforcement_action) for v in kept_b.get(key, [])]
            if va != vb:
                return f"kept violations differ for {key}"
        return None

    def _publish_metrics(self, run: AuditRun) -> None:
        if self.metrics is None:
            return
        from gatekeeper_tpu.metrics import registry as M

        self.metrics.observe(M.AUDIT_DURATION, run.duration_s)
        now = time.time()
        self.metrics.set_gauge(M.AUDIT_LAST_RUN, now - run.duration_s)
        # end-of-sweep timestamp: the SLO engine's audit-staleness
        # objective ages against this (declared since PR 3, never set)
        self.metrics.set_gauge(M.AUDIT_LAST_RUN_END, now)
        self.metrics.set_gauge(M.AUDIT_LAST_RUN_INCOMPLETE,
                               1.0 if run.incomplete else 0.0)
        if self.cluster:
            # fleet: the per-cluster staleness series the cluster-scoped
            # objectives sample (the unlabeled gauges above keep their
            # process-wide meaning: last sweep of ANY cluster)
            lab = {"cluster": self.cluster}
            self.metrics.set_gauge(M.AUDIT_LAST_RUN,
                                   now - run.duration_s, lab)
            self.metrics.set_gauge(M.AUDIT_LAST_RUN_END, now, lab)
            self.metrics.set_gauge(M.AUDIT_LAST_RUN_INCOMPLETE,
                                   1.0 if run.incomplete else 0.0, lab)
        if not self.pipe_stats:
            return
        for name, s in self.pipe_stats.get("stages", {}).items():
            lab = {"stage": name}
            self.metrics.set_gauge(M.PIPELINE_STAGE_SECONDS,
                                   s["busy_s"], lab)
            self.metrics.set_gauge(M.PIPELINE_STAGE_OCCUPANCY,
                                   s["occupancy"], lab)
            self.metrics.set_gauge(M.PIPELINE_QUEUE_HIGHWATER,
                                   s["queue_highwater"], lab)
        self.metrics.set_gauge(M.PIPELINE_DEVICE_WAIT,
                               self.pipe_stats.get("device_wait_s", 0.0))
        # sweep-level aggregates: wall vs summed stage busy is the
        # overlap proof
        self.metrics.set_gauge(M.PIPELINE_WALL,
                               self.pipe_stats.get("wall_s", 0.0))
        self.metrics.set_gauge(
            M.PIPELINE_STAGE_BUSY_SUM,
            self.pipe_stats.get("stage_busy_sum_s", 0.0))

    def _kinds_of(self, constraints: Sequence[Constraint]) -> set:
        """--audit-match-kind-only prefilter (manager.go:427-483): only valid
        when every constraint names concrete kinds."""
        kinds: set = set()
        for c in constraints:
            entries = (c.match or {}).get("kinds") or []
            if not entries:
                return None  # a constraint matches all kinds: no prefilter
            for e in entries:
                ks = e.get("kinds") or []
                if not ks or "*" in ks:
                    return None
                kinds.update(ks)
        return kinds

    # --- chunk evaluation ------------------------------------------------

    def _audit_chunk(self, objects, constraints, kept, totals, limit,
                     source=SOURCE_ORIGINAL, overrides=None):
        """No-evaluator path: every constraint goes through its template's
        own driver (batched where the driver supports it)."""
        target = self.client.target
        reviews = [
            target.handle_review(
                AugmentedUnstructured(object=o, source=source)
            )
            for o in objects
        ]
        self._eval_via_drivers(constraints, objects, reviews, kept, totals,
                               limit, overrides=overrides)

    def _eval_via_drivers(self, constraints, objects, reviews, kept, totals,
                          limit, overrides=None):
        """Evaluate constraints through their own template's driver: the
        batch path for batch-capable drivers, a matcher-prefiltered per-object
        query loop otherwise.  This is the lane for every constraint the
        device sweep did not cover — non-lowered Rego templates, CEL
        templates (owned by a different driver), and referential templates
        whose inventory tables are inexact for the current data version."""
        if not constraints:
            return
        target = self.client.target
        by_driver: dict[int, tuple] = {}
        for con in constraints:
            d = self.client._template_driver.get(con.kind)
            if d is None:
                continue  # no template: constraint cannot be evaluated
            by_driver.setdefault(id(d), (d, []))[1].append(con)
        for d, cons in by_driver.values():
            if hasattr(d, "query_batch"):
                self._chunk_via_query_batch(d, cons, objects, reviews, kept,
                                            totals, limit,
                                            overrides=overrides)
                continue
            for oi, obj in enumerate(objects):
                review = reviews[oi]
                for con in cons:
                    if not target.to_matcher(con.match).match(review):
                        continue
                    qr = d.query(
                        target.name, [con], review,
                        ReviewCfg(enforcement_point=AUDIT_EP)
                    )
                    key = con.key()
                    totals[key] += len(qr.results)
                    for r in qr.results:
                        if len(kept[key]) < limit:
                            kept[key].append(
                                self._violation(con, obj, r.msg, r.details,
                                                override=(overrides[oi]
                                                          if overrides
                                                          else None)))

    @staticmethod
    def fold_swept(swept, n_objects, render, limit, exact, budget=None):
        """Yield (constraint, total, kept[(oi, msg, details)]) per
        constraint of a device sweep result — the single definition of the
        kept/total fold, shared by the in-process audit and the Evaluate
        sidecar (their parity is asserted in tests/test_sidecar.py).

        ``render(con, oi)`` -> list of exact-engine Results for one hit.
        ``exact``: totals count RESULTS via bit-packed hit rows; otherwise
        totals are the device's violating-object counts and only top-k
        hits render.  ``budget(con)`` -> remaining run-level kept slots for
        a constraint (defaults to ``limit``): a constraint whose run budget
        is exhausted renders NOTHING for this chunk — without it every
        chunk re-renders up to ``limit`` hits per constraint through the
        exact interpreter only to drop them at the run-level cap
        (~(n_chunks-1)x wasted render work on violation-dense corpora).
        In the exact path a row whose device count is its result total
        (``SweptRows.results``, not -1) renders its lowest-index hits only
        until the kept slots are filled, which keeps what rendering every
        hit kept; any other row renders every hit for its total."""
        for kind, entry in swept.items():
            kcons, idx, valid, counts, bits = entry
            counted = getattr(entry, "results", False)
            for ci, con in enumerate(kcons):
                kept_list: list = []
                cap = limit if budget is None else min(limit, budget(con))
                if exact and bits is not None:
                    # a row the device counted renders only until its kept
                    # slots are filled; any other row renders every hit,
                    # whatever the kept budget, for its total
                    device_total = int(counts[ci]) \
                        if counted and counts[ci] >= 0 else None
                    total = 0
                    for oi in violation_rows(bits, ci, n_objects).tolist():
                        if device_total is not None and len(kept_list) >= cap:
                            break
                        results = render(con, oi)
                        total += len(results)
                        for r in results:
                            if len(kept_list) < cap:
                                kept_list.append(
                                    (oi, r.msg,
                                     (r.metadata or {}).get("details")))
                    if device_total is not None:
                        total = device_total
                else:
                    total = int(counts[ci])
                    for j in range(idx.shape[1]):
                        if not valid[ci, j] or len(kept_list) >= cap:
                            continue
                        oi = int(idx[ci, j])
                        for r in render(con, oi):
                            if len(kept_list) < cap:
                                kept_list.append(
                                    (oi, r.msg,
                                     (r.metadata or {}).get("details")))
                yield con, total, kept_list

    def _process_swept(self, swept, objects, constraints, kept, totals,
                       limit, source=SOURCE_ORIGINAL, overrides=None):
        """Fold one chunk's device results into the run state and run the
        fallback kinds through the exact engine.  ``source``/``overrides``
        carry the expansion generator stage's context (Generated reviews,
        per-object (template, enforcementAction) overrides)."""
        if getattr(self.evaluator, "renders", False):
            # sidecar lane: the sweep RPC already rendered kept violations
            # and covered every constraint (incl. non-lowered kinds)
            for (ckind, cname), (total, kept_list) in swept.items():
                key = (ckind, cname)
                if key not in totals:
                    continue
                totals[key] += total
                con = self.client.get_constraint(ckind, cname)
                for oi, msg, details in kept_list:
                    if con is not None and len(kept[key]) < limit:
                        kept[key].append(
                            self._violation(con, objects[oi], msg, details))
            return
        from gatekeeper_tpu.observability import tracing

        target = self.client.target
        review_cache: dict = {}

        def get_review(oi):
            # per-index lazy: a chunk renders only its kept hits, so
            # building every review up front is O(chunk) waste
            r = review_cache.get(oi)
            if r is None:
                r = target.handle_review(AugmentedUnstructured(
                    object=objects[oi], source=source))
                review_cache[oi] = r
            return r

        def get_reviews():
            return [get_review(oi) for oi in range(len(objects))]

        exact = self.config.exact_totals
        render_obj = self._render_fn(source, review_cache)
        span_keys = ("render_memo_hits", "violation_peeked",
                     "violation_loaded")
        before = [self.perf.get(key, 0) for key in span_keys]

        def render(con, oi):
            return render_obj(con, objects[oi], oi)

        for con, total, kept_list in self.fold_swept(
                swept, len(objects), render, limit, exact,
                budget=lambda con: limit - len(kept[con.key()])):
            key = con.key()
            totals[key] += total
            for oi, msg, details in kept_list:
                if len(kept[key]) < limit:
                    kept[key].append(
                        self._violation(con, objects[oi], msg, details,
                                        override=(overrides[oi]
                                                  if overrides else None)))
        # everything the device sweep did not cover (non-lowered kinds, CEL
        # templates owned by another driver, inventory-inexact referential
        # kinds) goes through its own driver's exact path
        rest = [c for c in constraints if c.kind not in swept]
        if rest:
            self._eval_via_drivers(rest, objects, get_reviews(), kept,
                                   totals, limit, overrides=overrides)
        # on the chunk's fold span (pipeline.stage.fold_render, or
        # audit.chunk.collect_fold on the serial schedule)
        for key, n0 in zip(span_keys, before):
            tracing.set_attribute(key, self.perf.get(key, 0) - n0)

    def _chunk_via_query_batch(self, driver, constraints, objects, reviews,
                               kept, totals, limit, overrides=None):
        responses = driver.query_batch(
            self.client.target.name, constraints, reviews,
            ReviewCfg(enforcement_point=AUDIT_EP),
        )
        for oi, resp in enumerate(responses):
            for r in resp.results:
                ckind = r.constraint.get("kind", "")
                cname = (r.constraint.get("metadata") or {}).get("name", "")
                key = (ckind, cname)
                if key not in totals:
                    continue
                totals[key] += 1
                if len(kept[key]) < limit:
                    con = self.client.get_constraint(ckind, cname)
                    kept[key].append(
                        self._violation(con, objects[oi], r.msg, r.details,
                                        override=(overrides[oi]
                                                  if overrides else None))
                    )

    def _violation(self, con, obj, msg, details,
                   override=None) -> Violation:
        """The kept violation of ``con`` on ``obj``.  The four strings
        that name the object come off the bytes of an unloaded ``RawJSON``
        where ``peek_identity`` settles them, so a violation whose render
        the memo answered loads nothing; otherwise from the object, which
        then loads.  ``perf`` counts the two ways."""
        perf = self.perf
        ident = peek_identity(obj)
        if ident is not None:
            perf["violation_peeked"] = perf.get("violation_peeked", 0) + 1
            api_version, kind, name, namespace = ident
            group, version = split_api_version(api_version)
        else:
            perf["violation_loaded"] = perf.get("violation_loaded", 0) + 1
            group, version, kind = gvk_of(obj)
            meta = obj.get("metadata") or {}
            name = meta.get("name", "") or ""
            namespace = meta.get("namespace", "") or ""
        actions = con.actions_for(AUDIT_EP)
        action = actions[0] if actions else con.enforcement_action
        if override is not None:
            # expansion generator stage: the [Implied by <template>]
            # message prefix and the template's enforcementAction
            # override (reference: expansion/aggregate.go semantics)
            template_name, override_action = override
            from gatekeeper_tpu.expansion.aggregate import \
                CHILD_MSG_PREFIX

            msg = f"{CHILD_MSG_PREFIX % template_name} {msg}"
            if override_action:
                action = override_action
        return Violation(
            constraint=con,
            message=msg,
            enforcement_action=action,
            group=group,
            version=version,
            kind=kind,
            name=name,
            namespace=namespace,
            details=details,
        )

    # --- status writeback (reference: writeAuditResults, manager.go:947) -
    def _write_statuses(self, run: AuditRun, constraints):
        for con in constraints:
            key = con.key()
            status = {
                "auditTimestamp": run.timestamp,
                "totalViolations": run.total_violations.get(key, 0),
                # explicit partial-result marker (chunks were dropped
                # after retries or the lister died): totals undercount.
                # Only written when set so complete runs keep the
                # reference status shape byte-for-byte
                **({"incomplete": True} if run.incomplete else {}),
                "violations": [
                    {
                        "message": v.message,
                        "enforcementAction": v.enforcement_action,
                        "group": v.group,
                        "version": v.version,
                        "kind": v.kind,
                        "name": v.name,
                        "namespace": v.namespace,
                    }
                    for v in run.kept.get(key, [])
                ],
            }
            if self.status_writer is not None:
                self.status_writer(con, status)
            else:
                con.raw.setdefault("status", {}).update(status)

    def _finish(self, run: AuditRun):
        if self.export_system is not None:
            for key, violations in run.kept.items():
                for v in violations:
                    self.export_system.publish_violation(run.timestamp, v)
            self.export_system.publish_audit_ended(run.timestamp)
        if self.log_violations:
            from gatekeeper_tpu.utils.logging import log_audit_violation

            for violations in run.kept.values():
                for v in violations:
                    log_audit_violation(v, run.timestamp)
        if self.event_sink is not None:
            self.event_sink(run)


def _now_rfc3339() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())

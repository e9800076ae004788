"""Device-resident snapshot columns: HBM as the cluster cache.

The snapshot store (snapshot/store.py) already keeps per-group tall
ColumnBatches resident HOST-side and ticks O(churn) — but every sweep
chunk still pays slice_rows (host gather) + pack_transfer_cols (host
pack) + device_put (H2D wire) for rows that have not changed since the
last tick (119MB H2D per 1M-object sweep at the full library, all of
it re-upload of clean rows).  This module promotes residency one level:

- each routed :class:`GroupStore`'s tall batch lives ON DEVICE as the
  same dtype-packed transfer buffers a sweep dispatch would build
  (``pack_transfer_cols`` with ``stats=None`` — a schema-only layout
  that patch slivers reproduce exactly), uploaded once per layout
  generation;
- the per-(constraint, row) match masks live on device too (bool
  [C, cap]), with a host mirror the differential lane asserts against;
- watch patches apply as device ``scatter``: the dirty rows flatten
  into a sliver batch (the store's normal patch lane), pack under the
  SAME layout, and land with ``buf.at[rows].set(sliver)`` — H2D is
  O(churn), never O(cluster);
- an audit chunk over resident rows ships only a row-index gather
  vector (cached per chunk shape, so a warm full tick over unchanged
  membership uploads ZERO bytes) and the fused sweep gathers columns +
  masks on device (parallel/sharded.py ``_sweep_fn_resident*``).

Bit-identity to the host-column path holds by construction: the
gathered device rows are the same values ``slice_rows`` would gather,
pad slots gather row 0 but carry a False mask column (exactly the
False pad masks of a host chunk), and masks are computed per
(constraint, object) by the same ``constraint_masks`` the dispatch
path runs — per-object pure, so patch-time masks equal chunk-time
masks.  That holds for every matcher but ``namespaceSelector``, which
reads the labels of another object (the row's Namespace, as the
target's NamespaceCache holds it at the time of the pass): a group
with one declines the lane, with the reason logged once, and its host
chunks compute their masks anew every pass.
``tests/test_device_residency.py`` pins verdict bit-identity across
clean, dirty-sliver and post-evict ticks, and that a relabelled
Namespace moves the verdicts of its objects.

Degradation: the built-in ``device_residency_evict`` action
(resilience/overload.py) demotes every resident group back to host
columns on an SLO breach — ``prepare`` polls it, frees the device
buffers, and falls back until the action releases (re-upload is lazy).
An evaluator whose mesh is on the CPU declines the same way (mode
"auto"), with the reason logged once; mode "on" keeps the lane testable
there.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from gatekeeper_tpu.ir.program import pack_batch_cols, slim_cols
from gatekeeper_tpu.parallel.sharded import pack_transfer_cols

# residency modes (--snapshot-residency): 'auto' promotes only when an
# accelerator backs the mesh (CPU hosts keep host columns, logged once);
# 'on' forces promotion (the CPU differential/test shape); 'off'
# disables the lane entirely
RESIDENCY_MODES = ("auto", "on", "off")


def _layout_equal(a: tuple, b: tuple) -> bool:
    return a == b


class ResidentGroup:
    """Device mirror of one GroupStore: packed column buffers + masks.

    ``cols_dev`` maps dtype string -> device array [cap, W] (the
    pack_transfer_cols buffers); ``mask_dev`` is bool [C, cap] in
    constraint-grid order (sorted lowered kinds, then the group's
    constraint order per kind — the order every dispatch uses);
    ``mask_host`` is its host mirror, the differential reference."""

    __slots__ = ("store", "kinds", "by_kind", "uids", "cols_layout",
                 "cap", "c_total", "cols_dev", "mask_dev", "mask_host",
                 "mutation_mark", "layout_version", "idx_cache",
                 "resident_bytes", "needs")

    def __init__(self, store, kinds, by_kind, uids, needs):
        self.store = store
        self.kinds = kinds
        self.by_kind = by_kind
        self.uids = uids
        self.needs = needs
        self.cols_layout: tuple = ()
        self.cap = 0
        self.c_total = sum(len(by_kind[k]) for k in kinds)
        self.cols_dev: dict = {}
        self.mask_dev = None
        self.mask_host: Optional[np.ndarray] = None
        self.mutation_mark = -1
        self.layout_version = -1
        # tuple(positions) -> device int32 gather vector (pad slots -1);
        # a warm full tick's chunk boundaries are deterministic, so the
        # second pass hits every entry and uploads nothing
        self.idx_cache: dict = {}
        self.resident_bytes = 0

    def chunk_idx(self, positions, pad_n: int) -> tuple:
        """(idx_dev [pad_n] int32, uploaded_bytes) — cached per position
        tuple; -1 marks pad slots (their mask column is forced False on
        device, so what they gather never matters)."""
        import jax

        key = (tuple(positions), pad_n)
        hit = self.idx_cache.get(key)
        if hit is not None:
            return hit, 0
        idx = np.full(pad_n, -1, np.int32)
        idx[: len(positions)] = positions
        dev = jax.device_put(idx)
        if len(self.idx_cache) > 4096:
            self.idx_cache.clear()
        self.idx_cache[key] = dev
        return dev, idx.nbytes


class DeviceResidency:
    """Owner of the device-resident snapshot groups of ONE evaluator.

    ``prepare(store)`` is the single seam the audit/fleet sweeps call
    per group per tick: it syncs the device mirror (full upload on
    layout change, scatter-patch for dirty rows, nothing when clean)
    and returns the :class:`ResidentGroup`, or None when the lane is
    unavailable (no device, multi-chip mesh, extdata joins, a
    ``namespaceSelector``, eviction degradation active) — callers then
    take the host-column path unchanged."""

    def __init__(self, evaluator, metrics=None, mode: str = "auto",
                 cluster: str = ""):
        if mode not in RESIDENCY_MODES:
            raise ValueError(f"unknown residency mode {mode!r} "
                             f"(want one of {RESIDENCY_MODES})")
        self.evaluator = evaluator
        self.metrics = metrics
        self.mode = mode
        self.cluster = cluster
        self._lock = threading.RLock()
        self._groups: dict = {}  # id(store) -> ResidentGroup
        self._logged_reasons: set = set()
        self.h2d_bytes = 0       # bytes this residency actually uploaded
        self.upload_count = 0    # full group uploads
        self.patch_count = 0     # scatter-patch syncs
        self.evictions = 0
        self._evicted_by_slo = False

    # --- availability ----------------------------------------------------
    def _log_fallback(self, reason: str, **fields) -> None:
        if reason in self._logged_reasons:
            return
        self._logged_reasons.add(reason)
        from gatekeeper_tpu.utils.logging import log_event

        log_event("info", "snapshot residency falling back to host "
                  f"columns: {reason}",
                  event_type="residency_fallback", reason=reason,
                  **fields)

    def available(self) -> bool:
        """Whether the resident lane may serve at all right now."""
        if self.mode == "off":
            return False
        ev = self.evaluator
        if ev is None or ev.mesh.size != 1:
            self._log_fallback("multi-chip mesh (resident gather is "
                              "single-chip; see ROADMAP NEXT)")
            return False
        if self.mode == "auto" \
                and ev.mesh.devices.flat[0].platform == "cpu":
            self._log_fallback("no accelerator (mode=auto on a CPU "
                              "mesh)")
            return False
        from gatekeeper_tpu.resilience.overload import (
            DEVICE_RESIDENCY_EVICT, degradation_active)

        if degradation_active(DEVICE_RESIDENCY_EVICT, self.cluster):
            if not self._evicted_by_slo:
                self._evicted_by_slo = True
                self.evict_all("slo degradation "
                               "(device_residency_evict active)")
            return False
        self._evicted_by_slo = False
        return True

    # --- eviction --------------------------------------------------------
    def evict_all(self, reason: str = "") -> int:
        """Drop every device mirror (HBM freed as the arrays release);
        host columns keep serving and re-upload happens lazily on the
        next eligible ``prepare``.  Returns the number of groups
        evicted."""
        with self._lock:
            n = len(self._groups)
            self._groups.clear()
        if n:
            self.evictions += n
            from gatekeeper_tpu.utils.logging import log_event

            log_event("info", f"snapshot residency evicted {n} group(s)"
                      + (f": {reason}" if reason else ""),
                      event_type="residency_evicted", groups=n,
                      reason=reason)
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                self.metrics.inc_counter(M.RESIDENCY_EVICTIONS,
                                         value=float(n))
                self.metrics.set_gauge(M.SNAPSHOT_RESIDENT_BYTES,
                                       float(self.resident_bytes()))
        return n

    def invalidate(self) -> None:
        """Generation-swap seam (drivers/generation.py): new programs
        mean new schemas/layouts — drop the mirrors now instead of
        letting each group's uid check discover it one tick later."""
        self.evict_all("generation swap")

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(rg.resident_bytes for rg in self._groups.values())

    # --- sync ------------------------------------------------------------
    def _mask_rows(self, rg: ResidentGroup, batch, objects) -> np.ndarray:
        """[C, len(objects)] bool in constraint-grid order — the same
        ``constraint_masks`` call over the group the dispatch path makes,
        so per-object mask values are identical whether computed at patch
        time (here) or chunk time (the host reference lane)."""
        from gatekeeper_tpu.ir import masks as masks_mod

        return masks_mod.constraint_masks(
            [con for kind in rg.kinds for con in rg.by_kind[kind]], batch,
            self.evaluator.driver.vocab, objects)[:, : len(objects)]

    def _pack(self, store, positions, pad_n: int, rg: ResidentGroup):
        """(bufs, layout, batch, objects) for a row set, under the
        residency's stats-free layout (schema-only: no narrowing, no
        elision — the layout every sliver of the group reproduces)."""
        batch = store.slice_rows(positions, pad_n)
        objects = [store.row_obj(p) for p in positions]
        cols = slim_cols(pack_batch_cols(batch), rg.needs)
        bufs, layout = pack_transfer_cols(cols, pad_n, stats=None)
        return bufs, layout, batch, objects

    def _upload(self, store, rg: ResidentGroup) -> None:
        """Full upload: the tall packed buffers + the complete mask
        mirror.  Paid once per layout generation (boot, capacity growth,
        ragged widening, compaction, generation swap)."""
        import jax

        from gatekeeper_tpu.observability import tracing

        live = store.live_positions()
        with tracing.span("snapshot.residency.upload", rows=len(live),
                          cap=store.cap):
            # pack EVERY slot by position (dead slots ship stale bytes
            # under a False mask): device row index == store position,
            # the invariant chunk gathers and scatter-patches rely on
            bufs, layout, _batch, _objs = self._pack(
                store, list(range(store.n_rows)), store.cap, rg)
            rg.cols_dev = {dt: jax.device_put(b)
                           for dt, b in bufs.items()}
            rg.cols_layout = layout
            rg.cap = store.cap
            mask = np.zeros((rg.c_total, store.cap), bool)
            if live:
                lbatch = store.slice_rows(live, len(live))
                lobjs = [store.row_obj(p) for p in live]
                mask[:, live] = self._mask_rows(rg, lbatch, lobjs)
            rg.mask_host = mask
            rg.mask_dev = jax.device_put(mask)
            nbytes = sum(b.nbytes for b in bufs.values()) + mask.nbytes
            rg.resident_bytes = nbytes
            rg.idx_cache.clear()
            rg.mutation_mark = store.mutations
            rg.layout_version = store.layout_version
            store.patched.clear()
            self.h2d_bytes += nbytes
            self.upload_count += 1
        self.evaluator._perf_add("resident_h2d_bytes", float(nbytes))

    def _patch(self, store, rg: ResidentGroup) -> None:
        """Scatter-patch the dirty rows: sliver columns + sliver masks
        land with device ``.at[rows].set`` — H2D is O(patched rows)."""
        import jax.numpy as jnp

        from gatekeeper_tpu.observability import tracing

        patched = sorted(p for p in store.patched if p < store.n_rows)
        live = [p for p in patched if store.live[p]]
        dead = [p for p in patched if not store.live[p]]
        with tracing.span("snapshot.residency.patch", rows=len(patched)):
            nbytes = 0
            if live:
                bufs, layout, batch, objects = self._pack(
                    store, live, len(live), rg)
                if not _layout_equal(layout, rg.cols_layout):
                    # defensive: a sliver whose pack layout drifted from
                    # the tall layout (should be impossible under
                    # stats=None) re-uploads instead of corrupting rows
                    self._log_fallback("sliver layout drift (full "
                                      "re-upload)")
                    self._upload(store, rg)
                    return
                rows = np.asarray(live, np.intp)
                for dt, b in bufs.items():
                    rg.cols_dev[dt] = rg.cols_dev[dt].at[rows].set(b)
                    nbytes += b.nbytes
                m = self._mask_rows(rg, batch, objects)
                rg.mask_host[:, rows] = m
                rg.mask_dev = rg.mask_dev.at[:, rows].set(jnp.asarray(m))
                nbytes += m.nbytes + rows.nbytes
            if dead:
                rows = np.asarray(dead, np.intp)
                rg.mask_host[:, rows] = False
                rg.mask_dev = rg.mask_dev.at[:, rows].set(False)
                nbytes += rows.nbytes
            rg.mutation_mark = store.mutations
            store.patched.clear()
            self.h2d_bytes += nbytes
            self.patch_count += 1
        self.evaluator._perf_add("resident_h2d_bytes", float(nbytes))
        self.evaluator._perf_add("resident_dirty_rows", float(len(patched)))

    def prepare(self, store) -> Optional[ResidentGroup]:
        """Sync and return the device mirror for one GroupStore, or None
        when the host-column path must serve (reason logged once)."""
        if not self.available():
            return None
        if store.batch is None or not store.lowered:
            return None
        from gatekeeper_tpu.ir.masks import reads_namespace_labels

        if reads_namespace_labels(store.cons):
            # a resident row's mask is computed when the row changes; this
            # matcher's answer changes with the Namespace's labels, which
            # are another row's
            self._log_fallback("namespaceSelector (the mask follows the "
                               "Namespace's labels; group keeps host "
                               "columns)")
            return None
        ev = self.evaluator
        progs = ev.driver._programs
        _bk, lowered, _schema = ev.sweep_schema(store.cons,
                                               programs=progs)
        kinds = tuple(sorted(lowered))
        if not kinds:
            return None
        from gatekeeper_tpu.ir.program import extdata_key_cols

        for kind in kinds:
            keymap, _ok = extdata_key_cols(progs[kind].program)
            if keymap:
                # external-data joins build per-chunk ext: tables off
                # the host batch — the resident lane has no host batch;
                # those groups keep host columns (ROADMAP NEXT)
                self._log_fallback("external-data joins (group keeps "
                                  "host columns)", kind=kind)
                return None
        uids = tuple(progs[kind].uid for kind in kinds)
        with self._lock:
            rg = self._groups.get(id(store))
            if rg is not None and (rg.store is not store
                                   or rg.uids != uids):
                rg = None
            if rg is None:
                by_kind = {k: [c for c in store.cons if c.kind == k]
                           for k in kinds}
                rg = ResidentGroup(
                    store, kinds, by_kind, uids,
                    ev._needs_union(kinds, store.alias, programs=progs))
                self._groups[id(store)] = rg
            if (rg.layout_version != store.layout_version
                    or rg.cap != store.cap or not rg.cols_dev):
                self._upload(store, rg)
            elif store.patched or rg.mutation_mark != store.mutations:
                self._patch(store, rg)
            if self.metrics is not None:
                from gatekeeper_tpu.metrics import registry as M

                self.metrics.set_gauge(M.SNAPSHOT_RESIDENT_BYTES,
                                       float(self.resident_bytes()))
            return rg

    def stats(self) -> dict:
        return {"mode": self.mode,
                "groups": len(self._groups),
                "resident_bytes": self.resident_bytes(),
                "h2d_bytes": self.h2d_bytes,
                "uploads": self.upload_count,
                "patches": self.patch_count,
                "evictions": self.evictions}

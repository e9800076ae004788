"""Device-resident snapshot columns: the HBM-as-cluster-cache lane.

THE differential: an AuditManager ticking through the resident lane
(mode "on" — promoted even on the CPU host, where the device buffers
are just committed arrays) must be verdict-bit-identical to the
host-column reference manager across

1. the clean full tick (one upload, then index-gather-only dispatch);
2. the dirty-sliver tick (watch churn lands as device scatter-patch);
3. the post-evict tick (the ``device_residency_evict`` degradation
   demotes to host columns mid-flight, release re-promotes lazily);

plus the zero-H2D pin — a warm clean-rows tick reports
``tick_h2d_bytes == 0`` — the mask-mirror differential, the
eviction/generation seams, and the one matcher whose answer is not the
row's own: under a ``namespaceSelector`` the lane declines, so a Namespace
relabelled between ticks moves the verdicts of its objects.

Wall-budget note: one module corpus (6-template slice, 100 objects)
behind a module-scoped compile cache dir, same shape as
test_snapshot_persist.py.
"""

from __future__ import annotations

import copy
import glob
import os

import numpy as np
import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.generation import CompileCache
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.resilience.overload import (DEVICE_RESIDENCY_EVICT,
                                                DegradationRegistry,
                                                activate_degradations)
from gatekeeper_tpu.snapshot import (ClusterSnapshot, DeviceResidency,
                                     SnapshotConfig, WatchIngester,
                                     gvks_of)
from gatekeeper_tpu.sync.source import FakeCluster
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import (library_dir, load_library,
                                            make_cluster_objects)
from gatekeeper_tpu.utils.unstructured import load_yaml_file

_KEEP = 6  # template-subset client: bounded compile wall (tier-1)


def _all_kinds():
    paths = sorted(
        glob.glob(os.path.join(library_dir(), "general", "*",
                               "template.yaml")) +
        glob.glob(os.path.join(library_dir(), "pod-security-policy", "*",
                               "template.yaml")))
    return [load_yaml_file(p)[0]["spec"]["crd"]["spec"]["names"]["kind"]
            for p in paths]


def _snap_manager(client, evaluator, lister, snapshot, residency=None):
    return AuditManager(
        client, lister=lister,
        config=AuditConfig(audit_source="snapshot", chunk_size=48,
                           exact_totals=False, pipeline="off"),
        evaluator=evaluator, snapshot=snapshot, residency=residency)


def _assert_identical(run_a, run_b, limit=20):
    diff = AuditManager._verdicts_differ_canonical(
        run_a.kept, run_a.total_violations,
        run_b.kept, run_b.total_violations, limit)
    assert diff is None, diff


def _churn_labels(cluster, objects, tag, idx):
    for j in idx:
        o = copy.deepcopy(objects[j])
        o.setdefault("metadata", {}).setdefault("labels", {})["churn"] = \
            tag
        cluster.apply(o)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("resid-cache")
    skip = tuple(_all_kinds()[_KEEP:])
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel,
                    compile_cache=CompileCache(str(cache_dir)))
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[AUDIT_EP])
    load_library(client, skip_kinds=skip)
    objects = make_cluster_objects(100, seed=7)
    cluster = FakeCluster()
    for o in objects:
        cluster.apply(copy.deepcopy(o))
    # single-device mesh: the resident lane is single-chip by design
    # (conftest forces 8 host devices for the multichip tests)
    evaluator = ShardedEvaluator(tpu, make_mesh(1), violations_limit=20)

    def lister():
        return iter(cluster.list())

    ctx = {"client": client, "tpu": tpu, "objects": objects,
           "cluster": cluster, "lister": lister, "evaluator": evaluator}
    yield ctx


def _paired_managers(corpus, residency):
    """Two snapshots over the same cluster: one resident, one host."""
    ev = corpus["evaluator"]
    snap_r = ClusterSnapshot(ev, SnapshotConfig())
    snap_h = ClusterSnapshot(ev, SnapshotConfig())
    mgr_r = _snap_manager(corpus["client"], ev, corpus["lister"], snap_r,
                          residency=residency)
    mgr_h = _snap_manager(corpus["client"], ev, corpus["lister"], snap_h)
    ing_r = WatchIngester(snap_r, corpus["cluster"],
                          gvks_of(corpus["cluster"].list())).start()
    ing_h = WatchIngester(snap_h, corpus["cluster"],
                          gvks_of(corpus["cluster"].list())).start()
    return snap_r, snap_h, mgr_r, mgr_h, ing_r, ing_h


# --- 1-3. THE differential: clean / dirty-sliver / post-evict ticks ---------

def test_resident_tick_differential_clean_dirty_evict(corpus):
    residency = DeviceResidency(corpus["evaluator"], mode="on")
    snap_r, snap_h, mgr_r, mgr_h, ing_r, ing_h = \
        _paired_managers(corpus, residency)
    try:
        # full rebuild both lanes (the resident lane's first upload)
        run_r = mgr_r.audit()
        run_h = mgr_h.audit()
        _assert_identical(run_r, run_h)
        assert residency.upload_count >= 1
        assert residency.resident_bytes() > 0

        # clean tick: nothing changed — dispatch is gather-index only,
        # and the SECOND clean tick's indices are cached: zero H2D
        tick_r0 = mgr_r.audit_tick()
        _assert_identical(tick_r0, mgr_h.audit_tick())
        tick_r1 = mgr_r.audit_tick()
        _assert_identical(tick_r1, mgr_h.audit_tick())
        assert mgr_r.perf["tick_h2d_bytes"] == 0, \
            "warm clean-rows resident tick uploaded bytes"

        # dirty-sliver tick: churn a handful of rows; the resident lane
        # scatter-patches exactly those and stays bit-identical
        patches0 = residency.patch_count
        _churn_labels(corpus["cluster"], corpus["objects"], "r1",
                      range(7))
        ing_r.pump()
        ing_h.pump()
        tick_r2 = mgr_r.audit_tick()
        tick_h2 = mgr_h.audit_tick()
        _assert_identical(tick_r2, tick_h2)
        assert residency.patch_count > patches0
        assert mgr_r.perf["tick_h2d_bytes"] > 0  # the sliver's bytes

        # a delete lands as a False mask column, not a re-upload
        gone = copy.deepcopy(corpus["objects"][3])
        corpus["cluster"].delete(gone)
        ing_r.pump()
        ing_h.pump()
        _assert_identical(mgr_r.audit_tick(), mgr_h.audit_tick())

        # post-evict tick: the SLO degradation demotes to host columns
        # (still bit-identical), release re-promotes lazily
        reg = DegradationRegistry()
        with activate_degradations(reg):
            reg.activate(DEVICE_RESIDENCY_EVICT, "test-objective")
            assert not residency.available()
            assert residency.evictions >= 1
            assert residency.resident_bytes() == 0
            _assert_identical(mgr_r.audit_tick(), mgr_h.audit_tick())
            reg.release(DEVICE_RESIDENCY_EVICT, "test-objective")
            uploads0 = residency.upload_count
            # re-promotion is lazy: the next tick that actually sweeps
            # a group re-uploads its mirror
            _churn_labels(corpus["cluster"], corpus["objects"], "r2",
                          range(2))
            ing_r.pump()
            ing_h.pump()
            _assert_identical(mgr_r.audit_tick(), mgr_h.audit_tick())
            assert residency.upload_count > uploads0  # re-promoted
    finally:
        ing_r.stop()
        ing_h.stop()


# --- 4. mask-mirror differential -------------------------------------------

def test_resident_mask_mirror_matches_host_masks(corpus):
    """The device mask's host mirror equals the masks the host dispatch
    path would compute per (constraint, row) — per-object purity is the
    scatter-patch lane's correctness argument."""
    from gatekeeper_tpu.ir import masks as masks_mod

    ev = corpus["evaluator"]
    residency = DeviceResidency(ev, mode="on")
    snap = ClusterSnapshot(ev, SnapshotConfig())
    mgr = _snap_manager(corpus["client"], ev, corpus["lister"], snap,
                        residency=residency)
    mgr.audit()
    assert residency._groups, "no group promoted"
    checked = 0
    for store in snap._groups.values():
        rg = residency.prepare(store)
        if rg is None:
            continue
        live = store.live_positions()
        batch = store.slice_rows(live, len(live))
        objs = [store.row_obj(p) for p in live]
        any_gen = any("generateName" in (o.get("metadata") or {})
                      for o in objs)
        ref_rows = [masks_mod.constraint_masks(
            rg.by_kind[kind], batch, ev.driver.vocab, objs,
            any_generate_name=any_gen) for kind in rg.kinds]
        ref = np.concatenate(ref_rows, axis=0)[:, : len(objs)]
        np.testing.assert_array_equal(rg.mask_host[:, live], ref)
        # device mirror == host mirror (committed arrays on CPU)
        np.testing.assert_array_equal(np.asarray(rg.mask_dev),
                                      rg.mask_host)
        # dead/pad columns are all-False
        dead = [p for p in range(store.cap) if p not in set(live)]
        assert not rg.mask_host[:, dead].any()
        checked += 1
    assert checked > 0


# --- 5. seams: auto-fallback, off mode, swap invalidation -------------------

def test_residency_auto_mode_declines_on_cpu_host(corpus):
    import jax

    residency = DeviceResidency(corpus["evaluator"], mode="auto")
    if jax.default_backend() == "cpu":
        assert not residency.available()
        snap = ClusterSnapshot(corpus["evaluator"], SnapshotConfig())
        mgr = _snap_manager(corpus["client"], corpus["evaluator"],
                            corpus["lister"], snap, residency=residency)
        mgr.audit()  # serves fine through the host path
        assert residency.upload_count == 0
    else:  # accelerator host: auto promotes
        assert residency.available()


def _selector_world():
    """One template, two rows that select by the Namespace's ``tenant``
    label, two Namespaces with a Pod each; the Namespaces synced."""
    tpu = TpuDriver(cel_driver=CELDriver())
    client = Client(target=K8sValidationTarget(), drivers=[tpu],
                    enforcement_points=[AUDIT_EP])
    client.add_template(load_yaml_file(os.path.join(
        library_dir(), "general", "requiredlabels", "template.yaml"))[0])
    for tenant in ("a", "b"):
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sRequiredLabels",
            "metadata": {"name": f"owner-{tenant}"},
            "spec": {"match": {
                "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
                "namespaceSelector": {"matchLabels": {"tenant": tenant}}},
                "parameters": {"labels": [{"key": "owner"}]}}})
    cluster = FakeCluster()
    for ns, tenant in (("ns-1", "a"), ("ns-2", "b")):
        ns_obj = {"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": ns, "labels": {"tenant": tenant}}}
        client.add_data(ns_obj)
        cluster.apply(ns_obj)
        cluster.apply({"apiVersion": "v1", "kind": "Pod",
                       "metadata": {"name": f"pod-{ns}", "namespace": ns},
                       "spec": {"containers": []}})
    return client, tpu, cluster


def _violators(run) -> dict:
    return {name: sorted(v.name for v in vs)
            for (_kind, name), vs in run.kept.items()}


@pytest.mark.parametrize("lane", ["resident", "host"])
def test_a_relabelled_namespace_moves_its_objects_verdicts(lane):
    """A row's mask under a ``namespaceSelector`` follows the labels of
    another row, so a mirror patched only where rows change would go
    stale: the resident lane declines such a group (nothing is uploaded,
    the reason is logged) and both lanes answer with the Namespace labels
    synced before the tick."""
    client, tpu, cluster = _selector_world()
    ev = ShardedEvaluator(tpu, make_mesh(1), violations_limit=20)
    residency = (DeviceResidency(ev, mode="on") if lane == "resident"
                 else None)
    snap = ClusterSnapshot(ev, SnapshotConfig())
    mgr = _snap_manager(client, ev, lambda: iter(cluster.list()), snap,
                        residency=residency)
    ing = WatchIngester(snap, cluster, gvks_of(cluster.list())).start()
    try:
        assert _violators(mgr.audit()) == {"owner-a": ["pod-ns-1"],
                                           "owner-b": ["pod-ns-2"]}
        swept = mgr.perf["snapshot_rows_evaluated"]
        assert _violators(mgr.audit_tick()) == {"owner-a": ["pod-ns-1"],
                                                "owner-b": ["pod-ns-2"]}
        assert mgr.perf["snapshot_rows_evaluated"] == swept  # O(churn)
        moved = {"apiVersion": "v1", "kind": "Namespace",
                 "metadata": {"name": "ns-2", "labels": {"tenant": "a"}}}
        client.add_data(moved)
        cluster.apply(moved)
        ing.pump()
        # only the Namespace's own row is dirty; its Pod's verdict moves
        assert _violators(mgr.audit_tick()) == {
            "owner-a": ["pod-ns-1", "pod-ns-2"], "owner-b": []}
        assert ev.perf["mask_ns_missing"] == 0
        # synced again with the labels it has: nothing to follow
        swept = mgr.perf["snapshot_rows_evaluated"]
        client.add_data(copy.deepcopy(moved))
        assert _violators(mgr.audit_tick()) == {
            "owner-a": ["pod-ns-1", "pod-ns-2"], "owner-b": []}
        assert mgr.perf["snapshot_rows_evaluated"] == swept
        if residency is not None:
            assert residency.upload_count == 0 and not residency._groups
            assert any("namespaceSelector" in reason
                       for reason in residency._logged_reasons)
    finally:
        ing.stop()


def test_residency_off_mode_and_bad_mode(corpus):
    assert not DeviceResidency(corpus["evaluator"],
                               mode="off").available()
    with pytest.raises(ValueError):
        DeviceResidency(corpus["evaluator"], mode="bogus")


def test_generation_coordinator_invalidates_residency():
    from gatekeeper_tpu.drivers.generation import GenerationCoordinator

    class _Res:
        def __init__(self):
            self.calls = 0

        def invalidate(self):
            self.calls += 1

    import threading

    gc = GenerationCoordinator.__new__(GenerationCoordinator)
    gc._lock = threading.RLock()
    gc._residencies = []
    res = _Res()
    gc.attach_residency(res)
    assert gc._residencies == [res]

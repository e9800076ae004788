"""Watch-driven incremental audit acceptance tests (ISSUE 6).

1. Row-stable global ids (``ops.flatten.RowIdMap``) — unit-tested
   independently of the snapshot.
2. Mock-apiserver watch bookmarks + forced 410-Gone compaction hook, so
   relist recovery is testable without a real apiserver.
3. ``fault_point("kube.watch")`` chaos: injected 410 exercises the
   relist-recovery path, repeated stream errors exercise the watch
   circuit breaker — events flow again after the faults clear.
4. The churn differential: seeded adds/modifies/deletes over the library
   corpus where incremental snapshot verdicts are asserted bit-identical
   to a fresh relist after every burst, the resync differential proves
   column-level identity, compaction preserves row ids, and a chaos run
   with ``kube.watch`` faults active stays identical end-to-end.
5. The webhook's warm namespace cache reads resident snapshot rows.
"""

import copy
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.ops.flatten import RowIdMap
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.resilience.faults import FaultPlan, inject
from gatekeeper_tpu.snapshot import (ClusterSnapshot, SnapshotConfig,
                                     WatchIngester, gvks_of)
from gatekeeper_tpu.sync.kube import KubeCluster, KubeConfig
from gatekeeper_tpu.sync.mock_apiserver import MockApiServer
from gatekeeper_tpu.sync.source import ADDED, DELETED, FakeCluster
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import (iter_cluster_objects,
                                            load_library,
                                            make_cluster_objects)

POD_GVK = ("", "v1", "Pod")


def pod(name, ns="default", labels=None):
    meta = {"name": name, "namespace": ns}
    if labels:
        meta["labels"] = labels
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
            "spec": {"containers": [{"name": "c", "image": "x"}]}}


def wait_for(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


# --- 1. RowIdMap ----------------------------------------------------------

def test_rowid_map_stable_and_monotone():
    m = RowIdMap()
    a, created_a = m.assign("uid-a")
    b, created_b = m.assign("uid-b")
    assert (a, created_a) == (0, True)
    assert (b, created_b) == (1, True)
    # re-assign of a known uid is a lookup, not a new id
    assert m.assign("uid-a") == (0, False)
    assert m.get("uid-b") == 1
    assert "uid-a" in m and "uid-zzz" not in m
    assert m.uids() == ["uid-a", "uid-b"]
    assert len(m) == 2 and m.high_water == 2


def test_rowid_map_forget_retires_ids_forever():
    m = RowIdMap()
    m.assign("x")
    m.assign("y")
    assert m.forget("x") == 0
    assert m.forget("x") is None  # idempotent
    assert "x" not in m and len(m) == 1
    # a re-created object is a NEW row: fresh id, never a reissue
    nx, created = m.assign("x")
    assert created and nx == 2
    assert m.high_water == 3


# --- 2. mock apiserver: bookmarks + compaction hook ----------------------

@pytest.fixture()
def server():
    srv = MockApiServer().start()
    yield srv
    srv.stop()


@pytest.fixture()
def kube(server):
    kc = KubeCluster(KubeConfig(server=server.url), page_limit=50,
                     watch_backoff_s=0.05, watch_timeout_s=20.0,
                     watch_breaker_threshold=2,
                     watch_breaker_reset_s=0.1)
    yield kc
    kc.close()


def test_mock_watch_stream_replays_cache_then_bookmarks(server):
    server.put_object(pod("p0"))
    resp = urllib.request.urlopen(
        f"{server.url}/api/v1/pods?watch=1&resourceVersion=0", timeout=5)
    try:
        lines = iter(resp)
        first = json.loads(next(lines))
        second = json.loads(next(lines))
    finally:
        resp.close()
    # watch-cache replay: the event missed since rv=0 streams first...
    assert first["type"] == "ADDED"
    assert first["object"]["metadata"]["name"] == "p0"
    # ...then the sync BOOKMARK carrying the post-replay rv
    assert second["type"] == "BOOKMARK"
    assert int(second["object"]["metadata"]["resourceVersion"]) >= 1


def test_mock_compaction_hook_answers_410_for_old_rv(server):
    for i in range(3):
        server.put_object(pod(f"p{i}"))
    server.compact()  # compaction floor = current rv
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"{server.url}/api/v1/pods?watch=1&resourceVersion=1",
            timeout=5)
    assert ei.value.code == 410
    # a watch from at/after the floor is fine (only history compacted)
    resp = urllib.request.urlopen(
        f"{server.url}/api/v1/pods?watch=1&resourceVersion=999999",
        timeout=5)
    resp.close()


def test_compact_plus_break_forces_relist_recovery(server, kube):
    """compact() + break_watches() = the apiserver compacted past our
    resume rv: the client relists and surfaces the outage-window churn
    (a DELETED diff for the vanished object)."""
    server.put_object(pod("stay"))
    server.put_object(pod("goner"))
    events = []
    kube.subscribe(POD_GVK, events.append, replay=True)
    assert wait_for(lambda: len(
        [e for e in events if e.type == ADDED]) >= 2)
    with server._lock:
        server._objects.pop(("Pod", "default", "goner"))
    server.compact()
    server.break_watches("Pod")
    assert wait_for(lambda: any(
        e.type == DELETED and e.obj["metadata"]["name"] == "goner"
        for e in events))
    server.put_object(pod("after"))  # the recovered stream is live
    assert wait_for(lambda: any(
        e.type == ADDED and e.obj["metadata"]["name"] == "after"
        for e in events))


# --- 3. kube.watch chaos: injected 410 + breaker --------------------------

def test_kube_watch_fault_410_replays_through_relist(server, kube):
    server.put_object(pod("a"))
    events = []
    plan = FaultPlan([{"site": "kube.watch", "mode": "error",
                       "status": 410, "times": 1}])
    with inject(plan):
        kube.subscribe(POD_GVK, events.append, replay=True)
        assert wait_for(lambda: any(
            e.type == ADDED and e.obj["metadata"]["name"] == "a"
            for e in events))
        assert wait_for(lambda: plan.fired("kube.watch") >= 1)
        server.put_object(pod("post-410"))
        assert wait_for(lambda: any(
            e.obj["metadata"]["name"] == "post-410" for e in events))
    # the injected 410 is an ANSWER, not a failure: breaker stays closed
    assert kube._watch_breaker.allow()


def test_kube_watch_fault_errors_trip_breaker_then_recover(server, kube):
    server.put_object(pod("b"))
    events = []
    plan = FaultPlan([{"site": "kube.watch", "mode": "error",
                       "status": 500, "times": 3}])
    with inject(plan):
        kube.subscribe(POD_GVK, events.append, replay=True)
        assert wait_for(lambda: plan.fired("kube.watch") >= 3,
                        timeout=15.0)
    # threshold 2 < 3 consecutive failures: the breaker opened and paced
    # the reconnects; once faults clear the stream heals and events flow
    server.put_object(pod("healed"))
    assert wait_for(lambda: any(
        e.obj["metadata"]["name"] == "healed" for e in events),
        timeout=15.0)


# --- 4. the churn differential --------------------------------------------

def _library_client():
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[AUDIT_EP])
    load_library(client)
    return client, tpu


@pytest.fixture(scope="module")
def corpus():
    client, tpu = _library_client()
    objects = make_cluster_objects(150, seed=13)
    for o in objects:
        if o.get("kind") == "Ingress":
            client.add_data(o)
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=20)
    return client, tpu, objects, evaluator


def _fake_cluster(objects):
    cluster = FakeCluster()
    for o in objects:
        cluster.apply(copy.deepcopy(o))
    return cluster


def _managers(client, evaluator, cluster, snap_cfg=None, **cfg_kw):
    cfg_kw.setdefault("exact_totals", False)
    cfg_kw.setdefault("chunk_size", 64)
    cfg_kw.setdefault("pipeline", "off")

    def lister():
        return iter(cluster.list())

    snapshot = ClusterSnapshot(evaluator, snap_cfg or SnapshotConfig())
    snap_mgr = AuditManager(
        client, lister=lister,
        config=AuditConfig(audit_source="snapshot", **cfg_kw),
        evaluator=evaluator, snapshot=snapshot)
    relist_mgr = AuditManager(
        client, lister=lister, config=AuditConfig(**cfg_kw),
        evaluator=evaluator)
    return snapshot, snap_mgr, relist_mgr


def _assert_identical(snap_run, relist_run, limit=20):
    assert snap_run.total_objects == relist_run.total_objects
    diff = AuditManager._verdicts_differ_canonical(
        snap_run.kept, snap_run.total_violations,
        relist_run.kept, relist_run.total_violations, limit)
    assert diff is None, diff


def _churn(cluster, objects, fresh_iter, round_i, n_events, seed_names):
    """One seeded burst: ~1/3 modify, ~1/3 add, ~1/3 delete."""
    for j in range(n_events):
        which = j % 3
        k = round_i * n_events + j
        if which == 0:
            o = copy.deepcopy(objects[k % len(objects)])
            o.setdefault("metadata", {}).setdefault(
                "labels", {})["churn"] = f"r{round_i}-{j}"
            cluster.apply(o)
        elif which == 1:
            o = next(fresh_iter)
            o["metadata"]["name"] += f"-churn-{round_i}-{j}"
            cluster.apply(o)
        else:
            name = seed_names[k % len(seed_names)]
            victim = next((ob for ob in cluster.list()
                           if ob["metadata"].get("name") == name), None)
            if victim is not None:
                cluster.delete(victim)


def test_snapshot_full_pass_identical_to_relist(corpus):
    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects)
    snapshot, snap_mgr, relist_mgr = _managers(client, evaluator, cluster)
    snap_run = snap_mgr.audit()  # builds the snapshot, evaluates all rows
    relist_run = relist_mgr.audit()
    assert sum(relist_run.total_violations.values()) > 0  # non-vacuous
    _assert_identical(snap_run, relist_run)
    assert snapshot.stats()["rows"] == len(cluster.list())
    # a second full pass re-evaluates resident columns: still identical
    _assert_identical(snap_mgr.audit(), relist_run)


def test_snapshot_full_pass_identical_exact_totals(corpus):
    """The exact-totals lane (render every hit at fold time) agrees with
    a fresh relist in the same mode."""
    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects[:90])
    _snap, snap_mgr, relist_mgr = _managers(
        client, evaluator, cluster, exact_totals=True)
    _assert_identical(snap_mgr.audit(), relist_mgr.audit())


def test_churn_differential_bit_identical_every_burst(corpus):
    """THE acceptance criterion: seeded adds/modifies/deletes, and after
    every burst the incremental tick's verdicts equal a fresh relist
    sweep; the tick evaluates only the dirty rows (O(churn)); the resync
    differential proves per-row column identity at the end."""
    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects)
    snapshot, snap_mgr, relist_mgr = _managers(client, evaluator, cluster)
    ingester = WatchIngester(snapshot, cluster,
                            gvks_of(cluster.list())).start()
    try:
        snap_mgr.audit()  # initial build + full evaluation
        names = [o["metadata"]["name"] for o in objects]
        fresh = iter_cluster_objects(200, seed=77)
        for round_i in range(4):
            _churn(cluster, objects, fresh, round_i, 15, names)
            ingester.pump()
            dirty = snapshot.dirty_count()
            assert 0 < dirty < snapshot.live_count()  # O(churn), not O(n)
            evaluated0 = snap_mgr.perf.get("snapshot_rows_evaluated", 0)
            tick_run = snap_mgr.audit_tick()
            evaluated = snap_mgr.perf["snapshot_rows_evaluated"] \
                - evaluated0
            assert evaluated <= dirty
            relist_run = relist_mgr.audit()
            _assert_identical(tick_run, relist_run)
        assert snapshot.resync_differential(
            lambda: iter(cluster.list())) is None
        resync_run = snap_mgr.audit_resync()
        assert snap_mgr.last_resync_diff is None
        assert not resync_run.incomplete
    finally:
        ingester.stop()


def test_compaction_preserves_row_ids_and_verdicts(corpus):
    """A delete-heavy churn pushes tombstones past the threshold: the
    stores compact (positions move, ids do not) and the next tick +
    resync are still bit-identical to a fresh relist."""
    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects[:100])
    snapshot, snap_mgr, relist_mgr = _managers(
        client, evaluator, cluster,
        snap_cfg=SnapshotConfig(compact_tombstone_fraction=0.15,
                                compact_min_rows=8))
    ingester = WatchIngester(snapshot, cluster,
                            gvks_of(cluster.list())).start()
    try:
        snap_mgr.audit()
        ids_before = {k: snapshot.ids.get(k)
                      for k in snapshot.ids.uids()}
        # delete a third of the cluster
        victims = cluster.list()[::3]
        for v in victims:
            cluster.delete(v)
        ingester.pump()
        # compaction fired somewhere: no store is left over-threshold
        for store in snapshot._groups.values():
            assert not store.needs_compaction(snapshot.config)
        # surviving keys keep their EXACT pre-compaction ids
        for key in snapshot.ids.uids():
            assert snapshot.ids.get(key) == ids_before[key]
        tick_run = snap_mgr.audit_tick()
        _assert_identical(tick_run, relist_mgr.audit())
        assert snapshot.resync_differential(
            lambda: iter(cluster.list())) is None
    finally:
        ingester.stop()


def test_resync_divergence_invalidates_and_rebuilds(corpus):
    """A corrupted resident row makes the resync differential report a
    difference: the run is marked incomplete, the snapshot invalidated,
    and the next resync (post-rebuild) is clean again."""
    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects[:60])
    snapshot, snap_mgr, _relist = _managers(client, evaluator, cluster)
    snap_mgr.audit()
    store = next(s for s in snapshot.routed_stores() if s.n_rows)
    store.batch.kind_sid[0] += 1  # flip one identity column value
    run = snap_mgr.audit_resync()
    assert snap_mgr.last_resync_diff is not None
    assert run.incomplete and snapshot.stale
    run2 = snap_mgr.audit_resync()  # rebuilds first, then proves identity
    assert snap_mgr.last_resync_diff is None
    assert not run2.incomplete and not snapshot.stale


def test_chaos_churn_over_kube_watch_faults(corpus, server):
    """The chaos acceptance run: the snapshot is fed by a REAL KubeCluster
    watch against the mock apiserver while ``kube.watch`` faults (an
    injected 410 and transient stream errors) plus a forced server-side
    compaction break the stream mid-churn — the incremental verdicts
    still match a fresh relist bit-identically."""
    client, _tpu, objects, evaluator = corpus
    corpus_objs = [copy.deepcopy(o) for o in objects[:80]]
    for o in corpus_objs:
        server.put_object(o)
    kube = KubeCluster(KubeConfig(server=server.url), page_limit=200,
                       watch_backoff_s=0.05, watch_timeout_s=20.0,
                       watch_breaker_threshold=3,
                       watch_breaker_reset_s=0.1)
    gvks = gvks_of(corpus_objs)

    def lister():
        return iter(o for gvk in gvks for o in kube.list(gvk))

    snapshot = ClusterSnapshot(evaluator, SnapshotConfig())
    cfg = dict(exact_totals=False, chunk_size=64, pipeline="off")
    snap_mgr = AuditManager(
        client, lister=lister,
        config=AuditConfig(audit_source="snapshot", **cfg),
        evaluator=evaluator, snapshot=snapshot)
    relist_mgr = AuditManager(client, lister=lister,
                              config=AuditConfig(**cfg),
                              evaluator=evaluator)
    plan = FaultPlan([
        {"site": "kube.watch", "mode": "error", "status": 410,
         "after": len(gvks), "every": 7, "times": 2},
        {"site": "kube.watch", "mode": "error", "status": 500,
         "after": len(gvks) + 3, "every": 11, "times": 2},
    ])
    ingester = None
    try:
        with inject(plan):
            ingester = WatchIngester(snapshot, kube, gvks).start()
            snap_mgr.audit()
            # churn behind the watch: modify + add + delete
            for j, o in enumerate(corpus_objs[:12]):
                o2 = copy.deepcopy(o)
                o2.setdefault("metadata", {}).setdefault(
                    "labels", {})["churn"] = f"c{j}"
                server.put_object(o2)
            extra = [o for o in iter_cluster_objects(6, seed=5)]
            for j, o in enumerate(extra):
                o["metadata"]["name"] += f"-chaos-{j}"
                server.put_object(o)
            for o in corpus_objs[60:66]:
                server.delete_object(o["kind"],
                                     o["metadata"].get("namespace", ""),
                                     o["metadata"]["name"])
            server.compact()
            for kind in sorted({o["kind"] for o in corpus_objs[:20]}):
                server.break_watches(kind)
            expected = sum(len(kube.list(g)) for g in gvks)

            def caught_up():
                ingester.pump()
                return (snapshot.live_count() == expected
                        and snapshot.pending_count() == 0)

            assert wait_for(caught_up, timeout=30.0)
            tick_run = snap_mgr.audit_tick()
            _assert_identical(tick_run, relist_mgr.audit())
            assert snapshot.resync_differential(lister) is None
        assert plan.fired("kube.watch") >= 2  # the chaos actually bit
    finally:
        if ingester is not None:
            ingester.stop()
        kube.close()


# --- 4c. rotated resync (ISSUE 10 satellite) -------------------------------

def test_resync_rotation_partitions_keyspace_and_stays_clean(corpus):
    """``--snapshot-resync-rotate K``: the K key-hash slices partition
    the keyspace exactly (every key in one slice, no slice empty at
    this corpus size), each rotated resync proves only its slice, and a
    clean snapshot passes a full rotation."""
    from gatekeeper_tpu.snapshot.store import obj_key, resync_slice

    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects[:90])
    snapshot, snap_mgr, _relist = _managers(client, evaluator, cluster,
                                            resync_rotate=4)
    snap_mgr.audit()
    keys = [obj_key(o) for o in cluster.list()]
    per_slice = [sum(1 for k in keys if resync_slice(k, p, 4))
                 for p in range(4)]
    assert sum(per_slice) == len(keys)  # a partition, not a sample
    assert all(n > 0 for n in per_slice)
    for _ in range(4):  # one full rotation: every slice proves clean
        run = snap_mgr.audit_resync()
        assert snap_mgr.last_resync_diff is None
        assert not run.incomplete
        assert snap_mgr.perf["resync_scope"] == 0.25


def test_resync_rotation_catches_divergence_within_k_intervals(corpus):
    """Corrupt ONE resident row: the rotated resync flags it no later
    than the pass whose slice holds the row (within K intervals),
    invalidates the snapshot, and the post-rebuild rotation is clean."""
    client, _tpu, objects, evaluator = corpus
    cluster = _fake_cluster(objects[:60])
    snapshot, snap_mgr, _relist = _managers(client, evaluator, cluster,
                                            resync_rotate=3)
    snap_mgr.audit()
    store = next(s for s in snapshot.routed_stores() if s.n_rows)
    store.batch.kind_sid[0] += 1  # flip one identity column value
    caught_at = None
    for i in range(3):
        snap_mgr.audit_resync()
        if snap_mgr.last_resync_diff is not None:
            caught_at = i
            break
    assert caught_at is not None, \
        "a full rotation must visit the corrupted row's slice"
    assert snapshot.stale  # invalidated: the next sweep rebuilds
    snap_mgr.audit()  # rebuild
    for _ in range(3):  # post-rebuild rotation proves clean again
        run = snap_mgr.audit_resync()
        assert snap_mgr.last_resync_diff is None
        assert not run.incomplete


# --- 5. webhook warm cache -------------------------------------------------

def test_webhook_namespace_lookup_served_from_snapshot(corpus):
    from gatekeeper_tpu.webhook.policy import ValidationHandler

    client, _tpu, _objects, evaluator = corpus
    ns_obj = {"apiVersion": "v1", "kind": "Namespace",
              "metadata": {"name": "prod",
                           "labels": {"env": "production"}}}
    cluster = FakeCluster()
    cluster.apply(ns_obj)
    snapshot = ClusterSnapshot(evaluator, SnapshotConfig())
    snapshot.set_constraints([c for c in client.constraints()
                              if c.actions_for(AUDIT_EP)])
    snapshot.rebuild(lambda: iter(cluster.list()))
    calls = []

    def fallback(name):
        calls.append(name)
        return None

    handler = ValidationHandler(client, namespace_lookup=fallback,
                                snapshot=snapshot)
    got = handler._lookup_namespace("prod")
    assert got["metadata"]["labels"] == {"env": "production"}
    assert calls == []  # warm hit: the apiserver-backed source never ran
    # unknown namespace falls through to the source
    assert handler._lookup_namespace("nope") is None
    assert calls == ["nope"]
    # a STALE snapshot never answers (rebuild pending): fall through
    snapshot.invalidate()
    handler._lookup_namespace("prod")
    assert calls == ["nope", "prod"]



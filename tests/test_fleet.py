"""Fleet mode: one evaluator, N clusters (gatekeeper_tpu/fleet/).

1. THE fleet differential: K=4 clusters (mixed sizes, overlapping and
   disjoint template sets) swept PACKED vs independently — per-cluster
   verdicts, kept messages and row ids bit-identical, with the packed
   lane paying fewer device dispatches.
2. Runtime sharing: the second same-library cluster attaches with zero
   fresh lowerings and ZERO fused retraces; a distinct-but-overlapping
   library's runtime boots entirely from the shared on-disk compile
   cache.
3. Per-cluster snapshot spill under one root: loading a fleet = N
   spills against one shared vocab replay (warm restart evaluates
   nothing); a cluster-id mismatch is a counted miss + clean relist
   and never deletes the foreign spill.
4. Cluster-axis QoS: one noisy cluster's user flood cannot displace
   another cluster's system lane; displacement targets the noisy
   cluster's heaviest tenant deterministically.
5. Satellites: `/v1/mutate` raw-bytes ingest (outcome parity + the
   column differential lane), the flight recorder / `gator decisions`
   `cluster` axis, and packed vs sequential at K=4 same-library
   clusters (dispatch reduction >= 2x).

Wall-budget note: one module-scoped fleet (5-template library slice,
<=48 objects per cluster) and a shared compile-cache dir; the packed
vs sequential test reuses the same cache (tier-1 budget was freed by
moving two overlapping heavy tests to the slow lane — see
test_pipeline.py / test_tracing_integration.py).
"""

from __future__ import annotations

import copy
import glob
import json
import os

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.generation import CompileCache
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.fleet import FleetEvaluator, check_cluster_id
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.snapshot import (ClusterSnapshot, SnapshotConfig,
                                     SnapshotSpill, templates_digest)
from gatekeeper_tpu.snapshot.persist import MISS_CLUSTER
from gatekeeper_tpu.sync.source import FakeCluster
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import (library_dir, load_library,
                                            make_cluster_objects)
from gatekeeper_tpu.utils.unstructured import load_yaml_file

_KEEP = 5  # library-A slice: bounded compile+trace wall (tier-1)


def _all_kinds():
    paths = sorted(
        glob.glob(os.path.join(library_dir(), "general", "*",
                               "template.yaml")) +
        glob.glob(os.path.join(library_dir(), "pod-security-policy", "*",
                               "template.yaml")))
    return [load_yaml_file(p)[0]["spec"]["crd"]["spec"]["names"]["kind"]
            for p in paths]


def _builder(cache_dir, skip):
    def build():
        cel = CELDriver()
        tpu = TpuDriver(cel_driver=cel,
                        compile_cache=CompileCache(str(cache_dir)))
        client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                        enforcement_points=[AUDIT_EP])
        load_library(client, skip_kinds=skip)
        ev = ShardedEvaluator(tpu, make_mesh(), violations_limit=20)
        return client, tpu, ev

    return build


def _source(n, seed):
    src = FakeCluster()
    for o in make_cluster_objects(n, seed=seed):
        src.apply(copy.deepcopy(o))
    return src


def _independent_reference(fc):
    """This cluster swept ALONE through the standard snapshot audit
    path over a FRESH snapshot (fresh relist + flatten) — the fleet
    differential's oracle.  Returns (run, {con key: [row gids]})."""
    rt = fc.runtime
    snap = ClusterSnapshot(rt.evaluator, SnapshotConfig())
    mgr = AuditManager(
        rt.client, lister=fc.lister,
        config=AuditConfig(audit_source="snapshot", chunk_size=64,
                           exact_totals=False, pipeline="off"),
        evaluator=rt.evaluator, snapshot=snap)
    run = mgr.audit()
    gids = {ck: [g for g, _c, _m in snap.verdicts.rows(ck)]
            for ck in run.total_violations}
    return run, gids


def _assert_identical(run_a, run_b, limit=20):
    diff = AuditManager._verdicts_differ_canonical(
        run_a.kept, run_a.total_violations,
        run_b.kept, run_b.total_violations, limit)
    assert diff is None, diff


@pytest.fixture(scope="module")
def fleet_ctx(tmp_path_factory):
    """The module-scoped fleet story: a+b share library A (the sharing
    pins), c runs an overlapping subset, d a disjoint slice; packed
    sweep vs per-cluster independent references; spills; restart."""
    import gatekeeper_tpu.ir.lower_rego as LR

    cache_dir = tmp_path_factory.mktemp("fleet-cache")
    spill_root = tmp_path_factory.mktemp("fleet-spill")
    kinds = _all_kinds()
    skip_a = tuple(kinds[_KEEP:])             # templates 0..4
    skip_c = tuple(kinds[3:])                 # 0..2 (overlap with A)
    skip_d = tuple(kinds[:_KEEP] + kinds[8:])  # 5..7 (disjoint from A)

    lowers = [0]
    orig = LR.lower_template

    def counting(*a, **k):
        lowers[0] += 1
        return orig(*a, **k)

    import gatekeeper_tpu.drivers.tpu_driver as TD

    TD.lower_template = counting
    try:
        fleet = FleetEvaluator(chunk_size=64, exact_totals=False,
                               spill_root=str(spill_root))
        sources = {
            "a": _source(48, seed=1), "b": _source(48, seed=7),
            "c": _source(32, seed=3), "d": _source(24, seed=5)}
        fleet.add_cluster("a", sources["a"], "libA",
                          _builder(cache_dir, skip_a))
        lowers_a = lowers[0]
        # warm library A's executables at the 48-row geometry
        fleet.sweep(full=True)
        rt_a = fleet.clusters["a"].runtime
        tc0, low0 = rt_a.evaluator.trace_count, lowers[0]
        fcb = fleet.add_cluster("b", sources["b"], "libA",
                                _builder(cache_dir, skip_a))
        run_b_first = fcb.sweep_independent(full=True)
        second_cluster = {
            "lowers_delta": lowers[0] - low0,
            "traces_delta": rt_a.evaluator.trace_count - tc0,
            "shared_boots": fleet.shared_boots,
            "same_runtime": fcb.runtime is rt_a,
        }
        low1 = lowers[0]
        fcc = fleet.add_cluster("c", sources["c"], "libC",
                                _builder(cache_dir, skip_c))
        subset_library = {
            "fresh_lowers": lowers[0] - low1,
            "cache": dict(fcc.runtime.driver._compile_cache.stats()),
        }
        fleet.add_cluster("d", sources["d"], "libD",
                          _builder(cache_dir, skip_d))

        # THE packed fleet pass over all four clusters (every row
        # re-dirtied so the pass evaluates the full corpus)
        for fc in fleet.clusters.values():
            for _store, rows in fc.snapshot.all_rows().items():
                fc.snapshot._dirty.update(g for g, _p in rows)
        d0 = {rt.key: rt.evaluator.dispatch_count
              for rt in fleet.runtimes()}
        packed_runs = fleet.sweep(full=True)
        packed_dispatches = sum(
            rt.evaluator.dispatch_count - d0[rt.key]
            for rt in fleet.runtimes())
        packed_gids = {
            cid: {ck: [g for g, _c, _m in
                       fc.snapshot.verdicts.rows(ck)]
                  for ck in packed_runs[cid].total_violations}
            for cid, fc in fleet.clusters.items()}

        # independent references (fresh snapshots, standard path)
        refs = {}
        ref_gids = {}
        for cid, fc in fleet.clusters.items():
            refs[cid], ref_gids[cid] = _independent_reference(fc)

        fleet.spill_all()
        ctx = {
            "fleet": fleet, "sources": sources,
            "cache_dir": str(cache_dir), "spill_root": str(spill_root),
            "skip_a": skip_a, "lowers_a_boot": lowers_a,
            "second_cluster": second_cluster,
            "subset_library": subset_library,
            "packed_runs": packed_runs,
            "packed_gids": packed_gids,
            "packed_dispatches": packed_dispatches,
            "refs": refs, "ref_gids": ref_gids,
            "run_b_first": run_b_first,
        }
        yield ctx
        fleet.stop()
    finally:
        TD.lower_template = orig


# --- 0. unit ---------------------------------------------------------------

def test_cluster_id_validation():
    assert check_cluster_id("prod-eu.1_a") == "prod-eu.1_a"
    for bad in ("", "..", "a/b", "a b", "x\n"):
        with pytest.raises(ValueError):
            check_cluster_id(bad)


# --- 1. THE fleet differential --------------------------------------------

def test_fleet_packed_matches_independent_per_cluster(fleet_ctx):
    """K=4 clusters packed vs independently: per-cluster totals, kept
    messages AND verdict-store row ids bit-identical."""
    for cid in ("a", "b", "c", "d"):
        _assert_identical(fleet_ctx["packed_runs"][cid],
                          fleet_ctx["refs"][cid])
        assert fleet_ctx["packed_gids"][cid] == \
            fleet_ctx["ref_gids"][cid], f"row ids differ for {cid}"


def test_fleet_packing_reduces_dispatches(fleet_ctx):
    """The packed pass dispatched fewer device chunks than the four
    clusters' chunk counts sum to (same-library same-group chunks
    coalesced), and actually packed multi-cluster dispatches."""
    fleet = fleet_ctx["fleet"]
    assert fleet.packed_dispatches > 0
    # a+b (same runtime, 2 groups each at chunk 64) would pay 4
    # dispatches independently; packed they share
    assert fleet_ctx["packed_dispatches"] < 4 + 2 + 2


def test_fleet_sweep_runs_annotated(fleet_ctx):
    for cid, run in fleet_ctx["packed_runs"].items():
        assert not run.incomplete
        assert run.total_objects == \
            fleet_ctx["fleet"].clusters[cid].snapshot.live_count()


def test_fleet_statuses_are_per_cluster(fleet_ctx):
    """Status writeback lands in each cluster's own sink — the
    runtime's Constraint objects are shared, so con.raw mutation would
    make the last-swept cluster win."""
    for cid, run in fleet_ctx["packed_runs"].items():
        fc = fleet_ctx["fleet"].clusters[cid]
        assert fc.statuses, f"no statuses for {cid}"
        for key, status in fc.statuses.items():
            assert status["totalViolations"] == \
                run.total_violations.get(key, 0)


# --- 2. runtime sharing ----------------------------------------------------

def test_second_same_library_cluster_boots_free(fleet_ctx):
    """The acceptance pin: cluster b (same library as a) attached with
    zero fresh lowerings and ZERO fused retraces, and its first sweep
    reused a's executables (same runtime, trace_count unchanged)."""
    sc = fleet_ctx["second_cluster"]
    assert sc["same_runtime"]
    assert sc["shared_boots"] >= 1
    assert sc["lowers_delta"] == 0, "second cluster paid a lowering"
    assert sc["traces_delta"] == 0, "second cluster retraced"
    # and its verdicts came out (the sweep actually ran)
    assert fleet_ctx["run_b_first"].total_objects == 48


def test_overlapping_library_shares_disk_cache(fleet_ctx):
    """Cluster c's library is a SUBSET of a's: a distinct runtime, but
    every lowering answered by the shared on-disk CompileCache (the
    vocab prefix-replay rule composes across load orders)."""
    sub = fleet_ctx["subset_library"]
    assert sub["fresh_lowers"] == 0
    assert sub["cache"]["hits"] >= 3


# --- 3. per-cluster spill --------------------------------------------------

def test_fleet_spill_restart_warm(fleet_ctx):
    """Loading a fleet = N spills against one shared vocab replay: a
    restarted two-cluster fleet boots warm (zero rows evaluated on the
    first pass) with verdicts identical to the pre-restart packed
    sweep."""
    spill_root = fleet_ctx["spill_root"]
    assert sorted(os.listdir(spill_root)) == ["a", "b", "c", "d"]
    fleet2 = FleetEvaluator(chunk_size=64, exact_totals=False,
                            spill_root=spill_root)
    try:
        fleet2.add_cluster("a", fleet_ctx["sources"]["a"], "libA",
                           _builder(fleet_ctx["cache_dir"],
                                    fleet_ctx["skip_a"]))
        fleet2.add_cluster("b", fleet_ctx["sources"]["b"], "libA",
                           _builder(fleet_ctx["cache_dir"],
                                    fleet_ctx["skip_a"]))
        fa, fb = fleet2.clusters["a"], fleet2.clusters["b"]
        assert fa.warm_booted and fb.warm_booted
        runs = fleet2.sweep(full=None)
        assert fa.manager.perf.get("snapshot_rows_evaluated", 0) == 0
        assert fb.manager.perf.get("snapshot_rows_evaluated", 0) == 0
        _assert_identical(runs["a"], fleet_ctx["packed_runs"]["a"])
        _assert_identical(runs["b"], fleet_ctx["packed_runs"]["b"])
    finally:
        fleet2.stop()


def test_fleet_warm_root_round_trip(fleet_ctx, tmp_path):
    """``warm_root`` wires persisted warm EXECUTION state (sweep
    traces) per library runtime: save_warm_all() persists, and a fresh
    fleet's runtime replays it at build time — a full sweep of the same
    geometry then retraces nothing."""
    warm_root = str(tmp_path / "warm")
    fleet1 = FleetEvaluator(chunk_size=64, exact_totals=False,
                            warm_root=warm_root)
    try:
        fleet1.add_cluster("wa", _source(24, seed=17), "libA",
                           _builder(fleet_ctx["cache_dir"],
                                    fleet_ctx["skip_a"]))
        rt1 = fleet1.clusters["wa"].runtime
        assert rt1.warm_cache is not None
        assert not rt1.warm_replayed["hit"]  # nothing persisted yet
        fleet1.sweep(full=True)
        assert fleet1.save_warm_all() == 1
    finally:
        fleet1.stop()
    fleet2 = FleetEvaluator(chunk_size=64, exact_totals=False,
                            warm_root=warm_root)
    try:
        fleet2.add_cluster("wa", _source(24, seed=17), "libA",
                           _builder(fleet_ctx["cache_dir"],
                                    fleet_ctx["skip_a"]))
        rt2 = fleet2.clusters["wa"].runtime
        assert rt2.warm_replayed["hit"]
        assert rt2.warm_replayed["sweep_traces"] > 0
        tc0 = rt2.evaluator.trace_count
        fleet2.sweep(full=True)
        assert rt2.evaluator.trace_count == tc0  # geometry replayed
    finally:
        fleet2.stop()


def test_spill_cluster_mismatch_counted_not_deleted(fleet_ctx):
    """Pointing cluster x at b's spill dir: a counted ``cluster`` miss
    and a clean relist; the foreign spill survives untouched."""
    fleet = fleet_ctx["fleet"]
    rt = fleet.clusters["b"].runtime
    spill = SnapshotSpill(os.path.join(fleet_ctx["spill_root"], "b"),
                          cluster_id="x")
    snap = ClusterSnapshot(rt.evaluator, SnapshotConfig())
    out = spill.load(snap, rt.audit_constraints(),
                     templates=templates_digest(rt.client))
    assert out is None
    assert spill.miss_reasons == {MISS_CLUSTER: 1}
    assert snap.stale  # untouched: the boot relists
    assert os.path.exists(os.path.join(fleet_ctx["spill_root"], "b",
                                       "snapshot.json"))


# --- 4. cluster-axis QoS ---------------------------------------------------

def test_noisy_cluster_cannot_displace_other_clusters_system_lane():
    """Cluster identity rides the tenant key (cluster:tenant): a noisy
    cluster's user flood fills the queue, yet (1) another cluster's
    system ticket displaces the NOISY cluster's heaviest tenant, and
    (2) the noisy cluster's next user ticket cannot displace the queued
    system ticket — system sheds last, per cluster or across them."""
    from gatekeeper_tpu.resilience.qos import (QoSConfig, QoSQueue,
                                               Ticket,
                                               tenant_of_request)

    cfg = QoSConfig()
    lv_user = cfg.classify("team-a", "")
    lv_system = cfg.classify("kube-system", "")
    assert lv_system.order < lv_user.order
    q = QoSQueue(cfg)
    seq = 0
    # noisy cluster: two tenants' user tickets fill the queue (depth 4)
    for ns, cost in (("team-a", 100.0), ("team-a", 100.0),
                     ("team-b", 10.0), ("team-b", 10.0)):
        t = Ticket(seq, tenant_of_request({"namespace": ns},
                                          cluster="noisy"),
                   lv_user, cost)
        admitted, victim, reason = q.enqueue(t, 4, 1e9)
        assert admitted and victim is None, reason
        seq += 1
    # quiet cluster's system ticket: displaces noisy's heaviest tenant
    sys_t = Ticket(seq, tenant_of_request({"namespace": "kube-system"},
                                          cluster="quiet"),
                   lv_system, 1.0)
    seq += 1
    admitted, victim, reason = q.enqueue(sys_t, 4, 1e9)
    assert admitted and victim is not None
    assert victim.tenant == "noisy:team-a"  # heaviest queued tenant
    assert victim.shed == "displaced"
    # noisy's next user ticket: queue full again, and nothing below it
    # to displace that it outranks — the system ticket is untouchable
    nxt = Ticket(seq, "noisy:team-a", lv_user, 100.0)
    admitted, victim, reason = q.enqueue(nxt, 4, 1e9)
    assert victim is None or victim.tenant != "quiet:kube-system"
    snap = q.snapshot()
    sys_lane = next(l for l in snap["lanes"]
                    if l["priority"] == lv_system.name)
    assert "quiet:kube-system" in sys_lane["tenants"]


def test_fleet_tenant_key_partitions_clusters():
    from gatekeeper_tpu.resilience.qos import tenant_of_request

    req = {"namespace": "team-a"}
    assert tenant_of_request(req) == "team-a"
    assert tenant_of_request(req, cluster="c1") == "c1:team-a"
    assert tenant_of_request(req, cluster="c2") != \
        tenant_of_request(req, cluster="c1")


# --- 5. satellites ---------------------------------------------------------

_ASSIGN = {
    "apiVersion": "mutations.gatekeeper.sh/v1", "kind": "Assign",
    "metadata": {"name": "set-pull-policy"},
    "spec": {
        "applyTo": [{"groups": [""], "versions": ["v1"],
                     "kinds": ["Pod"]}],
        "location": "spec.imagePullPolicy",
        "parameters": {"assign": {"value": "IfNotPresent"}}}}


def _mutation_burst(n=12):
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}", "namespace": "default",
                          "labels": {"i": str(i)}},
             "spec": {"containers": [{"name": "c", "image": "x"}]}}
            for i in range(n)]
    objs[3]["spec"]["imagePullPolicy"] = "Always"  # replace path
    objs[5]["kind"] = "ConfigMap"  # noop lane
    return objs


def test_mutate_ingest_raw_matches_dict():
    """The PR 7 NEXT closed: mutate bursts columnize through the PR 4
    raw-bytes lane; outcomes (patches, lanes, changed flags) are
    identical to the dict path, and the differential ingest lane —
    which asserts raw and dict COLUMNS bit-identical per batch inside
    the flattener — runs clean over the burst."""
    from gatekeeper_tpu.mutation.system import MutationSystem
    from gatekeeper_tpu.mutlane.lane import MutationLane

    system = MutationSystem()
    system.upsert_unstructured(copy.deepcopy(_ASSIGN))
    burst = _mutation_burst()
    ref = MutationLane(system, ingest="dict").mutate_objects(
        [copy.deepcopy(o) for o in burst])
    raw = MutationLane(system, ingest="raw").mutate_objects(
        [copy.deepcopy(o) for o in burst])
    dif = MutationLane(system, ingest="differential").mutate_objects(
        [copy.deepcopy(o) for o in burst])
    for a, b, c in zip(ref, raw, dif):
        assert (a.patch, a.lane, a.changed, a.error) == \
            (b.patch, b.lane, b.changed, b.error)
        assert (a.patch, a.changed, a.error) == \
            (c.patch, c.changed, c.error)
    assert any(o.patch for o in raw)  # the burst actually mutated


def test_mutate_ingest_rejects_unknown_lane():
    from gatekeeper_tpu.mutation.system import MutationSystem
    from gatekeeper_tpu.mutlane.lane import MutationLane

    with pytest.raises(ValueError):
        MutationLane(MutationSystem(), ingest="bogus")


def test_flight_recorder_cluster_axis(tmp_path):
    """Decisions carry the cluster field; /debug/decisions' snapshot
    and the offline `gator decisions` reader both filter on it."""
    from gatekeeper_tpu.gator.decisions_cmd import read_decisions
    from gatekeeper_tpu.observability.flightrec import FlightRecorder

    sink = str(tmp_path / "decisions.jsonl")
    rec = FlightRecorder(capacity=16, sink_path=sink)
    rec.record("validate", "allow", uid="u1", cluster="east",
               tenant="east:team-a")
    rec.record("validate", "deny", uid="u2", cluster="west")
    rec.record("mutate", "allow", uid="u3")  # clusterless (single mode)
    rec.close()
    snap = rec.snapshot(cluster="east")
    assert snap["matched"] == 1
    assert snap["decisions"][0]["uid"] == "u1"
    assert snap["decisions"][0]["cluster"] == "east"
    # compose with a decision-kind filter
    assert rec.snapshot(cluster="west",
                        kinds={"deny"})["matched"] == 1
    assert rec.snapshot(cluster="west",
                        kinds={"allow"})["matched"] == 0
    doc = read_decisions(sink, cluster="west")
    assert doc["matched"] == 1 and doc["decisions"][0]["uid"] == "u2"


def test_costattr_cluster_axis_closes():
    """Packed-pass wall apportioned across clusters sums back exactly
    (the closure contract), and the snapshot exposes the roll-up."""
    from gatekeeper_tpu.observability.costattr import (CostAttribution,
                                                       EP_AUDIT)

    attr = CostAttribution()
    attr.attribute_clusters(2.0, {"a": 30, "b": 10, "c": 0}, EP_AUDIT)
    totals = attr.cluster_totals(EP_AUDIT)
    assert abs(sum(totals.values()) - 2.0) < 1e-9
    assert totals["a"] == pytest.approx(1.5)
    snap = attr.snapshot()
    assert {c["cluster"] for c in snap["clusters"]} == {"a", "b", "c"}


def test_fleet_config_roundtrip(tmp_path):
    from gatekeeper_tpu.fleet import load_fleet_config

    p = tmp_path / "clusters.json"
    p.write_text(json.dumps({
        "clusters": [{"id": "a", "manifests": ["ma"]},
                     {"id": "b", "manifests": ["mb"]}],
        "packChunks": 3}))
    cfg = load_fleet_config(str(p))
    assert [c.cluster_id for c in cfg.clusters] == ["a", "b"]
    assert cfg.pack_chunks == 3
    p.write_text(json.dumps({"clusters": [{"id": "a"}, {"id": "a"}]}))
    with pytest.raises(ValueError):
        load_fleet_config(str(p))


# --- 6. packed against sequential, K=4 same-library clusters ---------------

def _fleet_pass(fleet, pack):
    """One full fleet pass with every snapshot row re-dirtied first, so
    both lanes evaluate identical row sets.  Returns (runs, dispatches)."""
    for fc in fleet.clusters.values():
        for rows in fc.snapshot.all_rows().values():
            fc.snapshot._dirty.update(g for g, _p in rows)
    ev = fleet.runtimes()[0].evaluator
    d0 = ev.dispatch_count
    runs = fleet.sweep(full=True, pack=pack)
    return runs, ev.dispatch_count - d0


def test_bench_fleet_smoke_pins_dispatch_reduction(fleet_ctx):
    """K=4 small clusters over one library, packed vs sequential (shared
    compile cache): dispatch reduction >= 2x, verdicts bit-identical,
    every cluster past the first attaches with zero lowering."""
    k = 4
    skip = tuple(_all_kinds()[_KEEP:])
    fleet = FleetEvaluator(chunk_size=500, exact_totals=False)
    for i in range(k):
        fleet.add_cluster(f"c{i:02d}", _source(40, 11 + i), "lib",
                          _builder(fleet_ctx["cache_dir"], skip))
    try:
        assert fleet.shared_boots == k - 1
        sequential, n_seq = _fleet_pass(fleet, pack=False)
        packed, n_packed = _fleet_pass(fleet, pack=True)
        for cid, run in packed.items():
            _assert_identical(run, sequential[cid])
        assert n_packed < n_seq
        assert n_seq / max(1, n_packed) >= 2.0, (n_seq, n_packed)
    finally:
        fleet.stop()

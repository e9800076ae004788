"""Device-side verdict reduction (ISSUE 9): ``--collect=reduced`` must
be bit-identical to the host-fold masks lane over the library corpus —
violation totals, canonical kept selections (including capped-selection
and the exact-engine fallback merge), snapshot tick/resync results —
while transferring O(kept/violations) device->host bytes instead of the
O(objects x constraints) grid.  The ``differential`` lane asserts the
same per chunk inside the evaluator, and the complete-hits overflow
path must fall back to the masks lane without changing a single
verdict."""

import copy

import numpy as np
import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.parallel.sharded import (HitRows, ShardedEvaluator,
                                             hit_bucket, make_mesh,
                                             violation_rows)
from gatekeeper_tpu.snapshot import ClusterSnapshot, SnapshotConfig
from gatekeeper_tpu.sync.source import FakeCluster
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import (load_library,
                                            make_cluster_objects)


# --- units -----------------------------------------------------------------

def test_hit_bucket_ladder():
    assert hit_bucket(0, 920) == 0
    assert hit_bucket(1, 920) == 16
    assert hit_bucket(17, 920) == 64
    assert hit_bucket(64, 920) == 64
    assert hit_bucket(65, 920) == 256
    assert hit_bucket(257, 920) == 920  # full per-chunk kept capacity
    # a tiny constraint set never allocates past its exhaustive bound
    assert hit_bucket(300, 40) == 40


def test_hitrows_matches_unpackbits():
    rng = np.random.default_rng(3)
    pad_n, n, c = 64, 50, 5
    grid = rng.random((c, pad_n)) < 0.2
    grid[:, n:] = False
    flat = np.nonzero(grid.reshape(-1))[0].astype(np.int64)
    hr = HitRows(flat, pad_n, n, c)
    bits = np.packbits(grid, axis=1)
    for ci in range(c):
        assert np.array_equal(violation_rows(hr, ci, n),
                              violation_rows(bits, ci, n))


# --- library-corpus fixtures ----------------------------------------------

@pytest.fixture(scope="module")
def world():
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    load_library(client)
    objects = make_cluster_objects(150, seed=7)
    return client, tpu, objects


def _mgr(client, tpu, objects, collect, **cfg_kw):
    cfg_kw.setdefault("exact_totals", False)
    cfg_kw.setdefault("chunk_size", 48)
    cfg_kw.setdefault("pipeline", "off")
    limit = cfg_kw.setdefault("violations_limit", 20)
    ev = ShardedEvaluator(tpu, make_mesh(), violations_limit=limit,
                          collect=collect)
    return AuditManager(client, lister=lambda: iter(objects),
                        config=AuditConfig(**cfg_kw), evaluator=ev), ev


def _assert_runs_identical(a, b):
    diff = AuditManager._schedules_differ(
        a.kept, a.total_violations, b.kept, b.total_violations)
    assert diff is None, diff


# --- relist sweep: reduced == masks ---------------------------------------

@pytest.mark.slow  # tier-1 wall budget (PR 16): 24s; the exact-totals
# variant below pins the same reduced==masks equivalence in tier 1.
def test_reduced_matches_masks_nonexact(world):
    client, tpu, objects = world
    mgr_m, ev_m = _mgr(client, tpu, objects, "masks")
    mgr_r, ev_r = _mgr(client, tpu, objects, "reduced")
    run_m = mgr_m.audit()
    run_r = mgr_r.audit()
    assert sum(run_m.total_violations.values()) > 0
    _assert_runs_identical(run_m, run_r)
    # the acceptance signal: the reduced lane moved fewer bytes off the
    # device at equal verdicts
    assert ev_r.perf["d2h_bytes"] < ev_m.perf["d2h_bytes"]
    assert ev_r.perf.get("collect_fallbacks", 0) == 0


def test_reduced_matches_masks_exact_totals(world):
    """Exact-totals parity: totals count RESULTS (a pod with two bad
    containers contributes 2), which renders every hit — the reduced
    lane ships the complete hit-coordinate list instead of the bit
    grid, and the exact-engine fallback kinds (CEL templates, inventory
    -inexact referential kinds) merge through their own drivers on both
    lanes."""
    client, tpu, objects = world
    corpus = objects[:60]
    mgr_m, ev_m = _mgr(client, tpu, corpus, "masks", exact_totals=True,
                       chunk_size=24)
    mgr_r, ev_r = _mgr(client, tpu, corpus, "reduced", exact_totals=True,
                       chunk_size=24)
    run_m = mgr_m.audit()
    run_r = mgr_r.audit()
    assert sum(run_m.total_violations.values()) > 0
    _assert_runs_identical(run_m, run_r)
    assert ev_r.perf["d2h_bytes"] < ev_m.perf["d2h_bytes"]


def test_reduced_capped_selection(world):
    """Capped selection: far more violations than the render cap — the
    device top-k under the budget must keep exactly the first-k
    canonical hits the masks fold keeps, and later chunks (budget
    drained) ship zero kept coordinates."""
    client, tpu, objects = world
    mgr_m, _ = _mgr(client, tpu, objects, "masks", violations_limit=3,
                    chunk_size=32)
    mgr_r, ev_r = _mgr(client, tpu, objects, "reduced",
                       violations_limit=3, chunk_size=32)
    run_m = mgr_m.audit()
    run_r = mgr_r.audit()
    _assert_runs_identical(run_m, run_r)
    capped = [k for k, v in run_m.kept.items() if len(v) == 3]
    assert capped, "corpus must cap at least one constraint"


# --- the differential lane -------------------------------------------------

@pytest.mark.slow  # tier-1 wall budget (PR 16): 37s; the exact-totals
# differential-lane test below keeps the identity pin in tier 1.
def test_differential_lane_proves_identity(world):
    client, tpu, objects = world
    mgr_m, _ = _mgr(client, tpu, objects, "masks")
    mgr_d, ev_d = _mgr(client, tpu, objects, "differential")
    run_m = mgr_m.audit()
    run_d = mgr_d.audit()
    assert not run_d.incomplete
    assert ev_d.perf.get("collect_differential_ok", 0) > 0
    _assert_runs_identical(run_m, run_d)


def test_differential_lane_exact(world):
    client, tpu, objects = world
    corpus = objects[:48]
    mgr_m, _ = _mgr(client, tpu, corpus, "masks", exact_totals=True,
                    chunk_size=24)
    mgr_d, ev_d = _mgr(client, tpu, corpus, "differential",
                       exact_totals=True, chunk_size=24)
    run_m = mgr_m.audit()
    run_d = mgr_d.audit()
    assert not run_d.incomplete
    assert ev_d.perf.get("collect_differential_ok", 0) > 0
    _assert_runs_identical(run_m, run_d)


# --- snapshot lane: tick + resync through reduced collect ------------------

@pytest.mark.slow  # tier-1 wall budget (PR 16): 27s; snapshot tick +
# resync semantics are pinned extensively in tests/test_snapshot.py.
def test_snapshot_reduced_tick_and_resync(world):
    client, tpu, objects = world
    cluster = FakeCluster()
    for o in objects:
        cluster.apply(copy.deepcopy(o))

    def lister():
        return iter(cluster.list())

    def managers(collect):
        ev = ShardedEvaluator(tpu, make_mesh(), violations_limit=20,
                              collect=collect)
        snapshot = ClusterSnapshot(ev, SnapshotConfig())
        snap_mgr = AuditManager(
            client, lister=lister,
            config=AuditConfig(audit_source="snapshot", pipeline="off",
                               exact_totals=False, chunk_size=48),
            evaluator=ev, snapshot=snapshot)
        return ev, snapshot, snap_mgr

    ev_r, snapshot, snap_mgr = managers("reduced")
    _, _, masks_mgr = managers("masks")
    run_r = snap_mgr.audit()  # full pass builds + evaluates the snapshot
    run_m = masks_mgr.audit()
    _assert_runs_identical(run_m, run_r)
    # dirty a few rows through the watch seam and tick: per-row verdict
    # persistence keyed by the returned hit indices, O(churn) evaluated
    changed = copy.deepcopy(objects[3])
    changed["metadata"]["labels"] = {"app": "patched"}
    cluster.apply(changed)
    snapshot.enqueue("MODIFIED", changed)
    tick = snap_mgr.audit_tick()
    assert not tick.incomplete
    # resync differential: fresh relist + host-fold reference sweep must
    # equal the patch-maintained snapshot (columns, vocab, verdicts)
    resync = snap_mgr.audit_resync()
    assert snap_mgr.last_resync_diff is None, snap_mgr.last_resync_diff
    assert not resync.incomplete
    assert snap_mgr.perf.get("resync_ok") == 1.0


# --- complete-hits overflow: masks fallback + adaptive buffer --------------

def test_complete_overflow_falls_back_bit_identically(world):
    client, tpu, objects = world
    corpus = objects[:96]
    mgr_m, _ = _mgr(client, tpu, corpus, "masks", exact_totals=True,
                    chunk_size=48)
    mgr_r, ev_r = _mgr(client, tpu, corpus, "reduced", exact_totals=True,
                       chunk_size=48)
    # force a tiny complete-hits buffer so dense chunks overflow: the
    # collect must re-dispatch those chunks through the masks lane and
    # escalate (or pin) the shape's buffer — verdicts never change
    state = {"cap": 8, "low": 0, "pinned": False, "blast": None}
    ev_r._hit_state_for = lambda kinds, pad_n: state
    run_m = mgr_m.audit()
    run_r = mgr_r.audit()
    _assert_runs_identical(run_m, run_r)
    assert ev_r.perf.get("collect_fallbacks", 0) > 0
    assert state["pinned"] or state["cap"] > 8


# --- budgeted buffer sizing across passes ----------------------------------

def test_budget_sizing_forgets_the_previous_pass():
    """The hit buffer is sized from the previous chunk's selection — but
    a NEW pass starts with its kept budgets reset, and the previous
    pass's drained tail must not size its head chunks (every pass after
    the first re-dispatched them through the masks lane: an overflow, a
    second dispatch and, the first time, a compile inside the pass)."""
    from types import SimpleNamespace

    from gatekeeper_tpu.parallel.sharded import make_mesh

    ev = ShardedEvaluator(None, make_mesh(1), violations_limit=20)
    cons = [object() for _ in range(4)]
    left = {"v": 20}
    flat = SimpleNamespace(kinds=("K",), by_kind={"K": cons}, pad_n=64,
                           budget=lambda _con: left["v"])
    full = 4 * 20
    _b, cap = ev._budget_hit_cap(flat, 4, 20)
    assert cap == full                       # first chunk: no history
    ev._hit_state_for(("K",), 64)["blast"] = 3   # its collect reports 3
    left["v"] = 1
    _b, cap = ev._budget_hit_cap(flat, 4, 20)
    assert cap == 16                         # drained: smallest step
    ev._hit_state_for(("K",), 64)["blast"] = 0   # the pass's tail
    left["v"] = 20                           # next pass: budgets reset
    for _ in range(2):   # pipelined: two dispatches before any collect
        budget, cap = ev._budget_hit_cap(flat, 4, 20)
        assert cap == full and budget.tolist() == [20] * 4

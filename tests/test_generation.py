"""Generations: background compile, executable swap, on-disk compile cache.

The load-bearing claims (ISSUE 12):

- verdicts are bit-identical across ``--generation-swap on|off`` while
  templates churn mid-burst and mid-sweep (compared after quiescence —
  pre-swap batches intentionally serve the OLD generation);
- a killed background compile leaves the serving generation untouched;
- corrupted / version-drifted / vocab-incompatible compile-cache entries
  are rejected and rebuilt, never served;
- a warm-cache cold start performs ZERO lowering (hit counter pinned);
- a snapshot tick spanning a swap re-chunks resident rows against the
  new generation without a relist.
"""

from __future__ import annotations

import copy
import glob
import os
import threading
import time

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.generation import (CompileCache, MISS_COLD,
                                               MISS_CORRUPT, MISS_DIGEST,
                                               MISS_VOCAB)
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
from gatekeeper_tpu.resilience.faults import FaultPlan, inject
from gatekeeper_tpu.target.review import AugmentedUnstructured
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import (library_dir, load_library,
                                            make_cluster_objects)
from gatekeeper_tpu.utils.unstructured import load_yaml_file


def _template_paths():
    return sorted(
        glob.glob(os.path.join(library_dir(), "general", "*",
                               "template.yaml")) +
        glob.glob(os.path.join(library_dir(), "pod-security-policy", "*",
                               "template.yaml")))


def _all_kinds():
    out = []
    for p in _template_paths():
        doc = load_yaml_file(p)[0]
        out.append((doc["spec"]["crd"]["spec"]["names"]["kind"], p))
    return out


# a small template subset keeps per-test compile+trace wall bounded on
# the 1-core tier-1 host (tier-1 runs ~35s under its timeout; every
# fresh client here pays compile + one trace pass); the full-corpus
# differential runs in the slow lane below
_KEEP = 8


def _small_client(generation_swap: bool, cache=None):
    kinds = _all_kinds()
    skip = tuple(k for k, _p in kinds[_KEEP:])
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel, generation_swap=generation_swap,
                    compile_cache=cache)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    load_library(client, skip_kinds=skip)
    if tpu.gen_coord is not None:
        tpu.gen_coord.constraints_fn = client.constraints
    return client, tpu


def _reviews(objects, n=10):
    return [AugmentedUnstructured(object=o, source=SOURCE_ORIGINAL)
            for o in objects[:n]]


def _sig(client, reviews):
    out = []
    for r in client.review_batch(reviews):
        out.append(tuple(sorted(res.msg for res in r.results())))
    return out


def _churn_doc(idx=0):
    """(kind, template doc, constraint docs) of the idx-th KEPT
    template."""
    kind, tpath = _all_kinds()[idx]
    tdoc = load_yaml_file(tpath)[0]
    cons = []
    cpath = os.path.join(os.path.dirname(tpath), "samples",
                         "constraint.yaml")
    if os.path.exists(cpath):
        cons = load_yaml_file(cpath)
    return kind, tdoc, cons


@pytest.fixture(scope="module")
def objects():
    return make_cluster_objects(32, seed=23)


@pytest.fixture(scope="module")
def reference(objects):
    """The swap-off client and its verdict signature — the oracle every
    swap-on quiescent state must match."""
    client, tpu = _small_client(False)
    revs = _reviews(objects)
    return client, _sig(client, revs), revs


# --- swap differential -----------------------------------------------------

def test_swap_on_quiesced_matches_inline(reference, objects):
    """Mid-burst template churn with the background thread running:
    after quiescence the verdicts equal the inline-compile client's,
    and bursts issued DURING the churn never error (they serve the old
    generation)."""
    _ref_client, ref_sig, revs = reference
    client, tpu = _small_client(True)
    coord = tpu.gen_coord
    assert coord is not None
    assert _sig(client, revs) == ref_sig  # pre-churn parity (inline boot)
    coord.start()
    kind, tdoc, cons = _churn_doc(0)
    gen0 = coord.gen_id
    client.remove_template(kind)
    # bursts while the background compile is in flight: old generation
    # answers, no errors, no stalls from lowering on this thread
    for _ in range(3):
        _sig(client, revs)
    client.add_template(tdoc)
    for cdoc in cons:
        client.add_constraint(cdoc)
    for _ in range(2):
        _sig(client, revs)
    assert coord.wait_idle(60.0)
    assert coord.gen_id > gen0
    assert coord.last_error is None
    assert _sig(client, revs) == ref_sig
    coord.stop()


def test_generation_pins_inflight_state(reference, objects):
    """A swap REPLACES the serving dicts; the captured old dict (what an
    in-flight batch holds) is untouched, so the batch finishes on the
    generation it started on."""
    _c, _s, revs = reference
    client, tpu = _small_client(True)
    old_programs = tpu._programs
    old_uids = {k: p.uid for k, p in old_programs.items()}
    kind, tdoc, cons = _churn_doc(1)
    client.remove_template(kind)  # inline (not started): swap happens now
    assert tpu._programs is not old_programs
    assert kind not in tpu._programs
    # the captured generation still holds the removed kind's program
    assert old_uids == {k: p.uid for k, p in old_programs.items()}
    # unchanged kinds' programs carried over by object (executable reuse)
    for k, p in tpu._programs.items():
        assert p is old_programs[k]


def test_killed_background_compile_leaves_serving(reference, objects):
    """compile.generation chaos: the build dies mid-flight — the
    serving generation keeps answering (verdicts = pre-churn), the
    error is recorded, and the next churn event retries cleanly."""
    _ref_client, ref_sig, revs = reference
    client, tpu = _small_client(True)
    coord = tpu.gen_coord
    coord.start()
    sig_before = _sig(client, revs)
    kind, tdoc, cons = _churn_doc(0)
    gen0, swaps0 = coord.gen_id, coord.swap_count
    plan = FaultPlan([{"site": "compile.generation", "mode": "error",
                       "times": 1}])
    with inject(plan):
        client.remove_template(kind)
        deadline = time.monotonic() + 30.0
        while plan.fired() < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert plan.fired() == 1
        deadline = time.monotonic() + 30.0
        while coord.last_error is None and time.monotonic() < deadline:
            time.sleep(0.01)
    assert coord.last_error is not None
    # no swap landed from the killed build
    assert coord.gen_id == gen0 and coord.swap_count == swaps0
    # serving untouched: the removed template still answers
    assert _sig(client, revs) == sig_before == ref_sig
    # the next churn event retries the whole desired set and recovers
    client.add_template(tdoc)  # no-op content-wise; re-triggers a build
    assert coord.wait_idle(60.0)
    assert coord.last_error is None
    # now the earlier removal finally lands with the retried build:
    # desired set == all templates (the re-add restored kind), so the
    # verdicts still match the reference
    assert _sig(client, revs) == ref_sig
    coord.stop()


# --- on-disk compile cache -------------------------------------------------

def test_compile_cache_cold_start_zero_lowering(tmp_path, reference,
                                                objects):
    """THE acceptance pin: a second process start against a warm
    --compile-cache performs zero lowering — every template answers
    from disk (hit counter == template count) with identical
    verdicts."""
    import gatekeeper_tpu.drivers.tpu_driver as TD
    import gatekeeper_tpu.ir.lower_rego as LR

    _ref_client, ref_sig, revs = reference
    cc1 = CompileCache(str(tmp_path))
    client1, tpu1 = _small_client(False, cache=cc1)
    n_templates = len(client1.templates())
    assert cc1.stats()["stores"] == n_templates
    assert _sig(client1, revs) == ref_sig

    calls = [0]
    orig = LR.lower_template

    def counting(*a, **k):
        calls[0] += 1
        return orig(*a, **k)

    TD.lower_template = counting
    try:
        cc2 = CompileCache(str(tmp_path))
        client2, tpu2 = _small_client(False, cache=cc2)
    finally:
        TD.lower_template = orig
    assert calls[0] == 0  # ZERO lowering
    assert cc2.hits == n_templates
    assert cc2.misses == 0
    assert _sig(client2, revs) == ref_sig


def test_compile_cache_corruption_rejected(tmp_path, reference, objects):
    """Tampered payload bytes, stale version fields and digest
    mismatches are rejected (and deleted) on load — never served — and
    the rebuild re-stores a clean entry."""
    import json

    _ref_client, ref_sig, revs = reference
    cc1 = CompileCache(str(tmp_path))
    _small_client(False, cache=cc1)
    pkls = sorted(glob.glob(os.path.join(str(tmp_path), "*.pkl")))
    metas = sorted(glob.glob(os.path.join(str(tmp_path), "*.json")))
    assert pkls and metas
    # corrupt one payload (bit flip)
    raw = bytearray(open(pkls[0], "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(pkls[0], "wb").write(bytes(raw))
    # version-drift another entry's meta (a jax upgrade)
    meta = json.load(open(metas[1]))
    meta["jax"] = "0.0.0-stale"
    json.dump(meta, open(metas[1], "w"))
    cc2 = CompileCache(str(tmp_path))
    client2, _tpu2 = _small_client(False, cache=cc2)
    st = cc2.stats()
    assert st["miss_reasons"].get(MISS_CORRUPT, 0) >= 1
    assert st["miss_reasons"].get(MISS_DIGEST, 0) >= 1
    assert st["hits"] == len(client2.templates()) - st["misses"]
    # rejected entries were rebuilt and re-stored
    assert st["stores"] == st["misses"]
    assert _sig(client2, revs) == ref_sig
    # third start: everything hits again (the rebuilt entries are clean)
    cc3 = CompileCache(str(tmp_path))
    client3, _tpu3 = _small_client(False, cache=cc3)
    assert cc3.stats()["misses"] == 0
    assert _sig(client3, revs) == ref_sig


def test_compile_cache_vocab_drift_is_a_miss(tmp_path, reference,
                                             objects):
    """A process whose vocab already diverged from the entry's snapshot
    must not consume baked sids: the load is a clean miss (reason
    vocab) and the template lowers fresh with correct verdicts."""
    _ref_client, ref_sig, revs = reference
    cc1 = CompileCache(str(tmp_path))
    _small_client(False, cache=cc1)

    cc2 = CompileCache(str(tmp_path))
    kinds = _all_kinds()
    skip = tuple(k for k, _p in kinds[_KEEP:])
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel, compile_cache=cc2)
    # poison the vocab BEFORE loading templates: sid 1 is now a string
    # the snapshot assigned differently
    tpu.vocab.intern("a-string-the-snapshot-never-interned-first")
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    load_library(client, skip_kinds=skip)
    st = cc2.stats()
    assert st["hits"] == 0
    assert st["miss_reasons"].get(MISS_VOCAB, 0) == \
        len(client.templates())
    assert _sig(client, revs) == ref_sig


def test_compile_cache_cold_reason_counted(tmp_path):
    cc = CompileCache(str(tmp_path))
    from gatekeeper_tpu.ops.flatten import Vocab

    assert cc.get("deadbeef", "rego", Vocab()) is None
    assert cc.stats()["miss_reasons"] == {MISS_COLD: 1}


# --- mutlane rides the generation machinery --------------------------------

def test_mutlane_background_recompile(reference):
    from gatekeeper_tpu.mutation.system import MutationSystem
    from gatekeeper_tpu.mutlane import MutationLane

    _c, _s, _r = reference
    client, tpu = _small_client(True)
    coord = tpu.gen_coord
    system = MutationSystem()
    lane = MutationLane(system, coordinator=coord)
    c0 = lane.compiled()
    assert c0.revision == system.revision()
    coord.start()
    # mutator churn: the serving burst keeps the OLD compiled revision
    # until the background install
    system.upsert_unstructured({
        "apiVersion": "mutations.gatekeeper.sh/v1",
        "kind": "AssignMetadata",
        "metadata": {"name": "gen-label"},
        "spec": {"location": "metadata.labels.gen",
                 "parameters": {"assign": {"value": "x"}}},
    })
    assert system.revision() != c0.revision
    stale = lane.compiled()
    assert stale is c0  # served stale, recompile enqueued
    assert coord.wait_idle(30.0)
    fresh = lane.compiled()
    assert fresh is not c0 and fresh.revision == system.revision()
    # and the new mutator actually applies through the batched pass
    out = lane.mutate_objects([{"apiVersion": "v1", "kind": "Pod",
                                "metadata": {"name": "p"}}])
    assert out[0].changed and out[0].patch
    coord.stop()


# --- snapshot re-chunk across a swap ---------------------------------------

def test_snapshot_tick_spans_swap_without_relist(objects):
    """A tick after a template add/remove re-chunks resident rows
    against the new generation: zero relist calls, row ids intact, and
    totals identical to a fresh relist audit of the same state."""
    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.parallel.sharded import (ShardedEvaluator,
                                                 make_mesh)
    from gatekeeper_tpu.snapshot import ClusterSnapshot, SnapshotConfig
    from gatekeeper_tpu.sync.source import FakeCluster

    client, tpu = _small_client(False)
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=20,
                                 collect="reduced")
    cluster = FakeCluster()
    for o in objects:
        cluster.apply(copy.deepcopy(o))
    lists = [0]

    def lister():
        lists[0] += 1
        return iter(cluster.list())

    snapshot = ClusterSnapshot(evaluator, SnapshotConfig())
    cfg = dict(chunk_size=64, pipeline="off", exact_totals=False)
    snap_mgr = AuditManager(client, lister=lister,
                            config=AuditConfig(audit_source="snapshot",
                                               **cfg),
                            evaluator=evaluator, snapshot=snapshot)
    relist_mgr = AuditManager(client, lister=lister,
                              config=AuditConfig(**cfg),
                              evaluator=evaluator)
    snap_mgr.audit()  # initial build (one relist)
    assert lists[0] == 1

    kind, tdoc, cons = _churn_doc(2)
    client.remove_template(kind)
    run = snap_mgr.audit_tick()
    assert lists[0] == 1  # NO relist: the plan change re-chunked
    assert snapshot.rechunk_count == 1
    ref = relist_mgr.audit()
    lists[0] = 1
    assert run.total_objects == ref.total_objects
    diff = AuditManager._verdicts_differ_canonical(
        run.kept, run.total_violations, ref.kept, ref.total_violations,
        20)
    assert diff is None, diff

    # re-add: another plan change, another rechunk, still no relist
    client.add_template(tdoc)
    for cdoc in cons:
        client.add_constraint(cdoc)
    run2 = snap_mgr.audit_tick()
    assert lists[0] == 1
    assert snapshot.rechunk_count == 2
    ref2 = relist_mgr.audit()
    diff = AuditManager._verdicts_differ_canonical(
        run2.kept, run2.total_violations, ref2.kept,
        ref2.total_violations, 20)
    assert diff is None, diff


# --- the full-corpus differential + bench smoke (slow lane) ----------------

@pytest.mark.slow
def test_library_corpus_churn_differential_full():
    """The satellite's full claim: verdicts bit-identical across
    --generation-swap on|off over the WHOLE library corpus while
    templates churn mid-burst, compared after quiescence."""
    objects = make_cluster_objects(60, seed=7)

    def full_client(swap):
        cel = CELDriver()
        tpu = TpuDriver(cel_driver=cel, generation_swap=swap)
        client = Client(target=K8sValidationTarget(),
                        drivers=[tpu, cel],
                        enforcement_points=[WEBHOOK_EP, AUDIT_EP])
        load_library(client)
        if tpu.gen_coord is not None:
            tpu.gen_coord.constraints_fn = client.constraints
        return client, tpu

    ref_client, _ = full_client(False)
    revs = _reviews(objects, 16)
    ref_sig = _sig(ref_client, revs)
    client, tpu = full_client(True)
    tpu.gen_coord.start()
    stop = threading.Event()
    errs: list = []

    def serve():
        while not stop.is_set():
            try:
                _sig(client, revs)
            except Exception as e:  # pragma: no cover — the assertion
                errs.append(e)

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    for idx in (0, 3, 5):
        kind, tdoc, cons = _churn_doc(idx)
        client.remove_template(kind)
        time.sleep(0.05)
        client.add_template(tdoc)
        for cdoc in cons:
            client.add_constraint(cdoc)
    assert tpu.gen_coord.wait_idle(120.0)
    stop.set()
    th.join(30.0)
    assert not errs
    assert _sig(client, revs) == ref_sig
    tpu.gen_coord.stop()


def test_warm_yield_sized_from_core_count():
    """ISSUE 14 satellite: the per-kernel cooperative-yield gap comes
    from the host's core count — 5ms on 1-core (pinned: the 1-core
    behavior must not move), a token 1ms on few-core, zero
    on many-core (a gap there only delays the swap)."""
    from gatekeeper_tpu.drivers.generation import warm_yield_s

    assert warm_yield_s(1) == 0.005  # 1-core behavior pinned unchanged
    assert warm_yield_s(2) == 0.001
    assert warm_yield_s(3) == 0.001
    assert warm_yield_s(4) == 0.0
    assert warm_yield_s(64) == 0.0
    # the default reads the real host
    import os

    assert warm_yield_s() == warm_yield_s(os.cpu_count() or 1)

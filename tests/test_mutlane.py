"""Batched mutation lane (ISSUE 7): the differential harness + /v1/mutate.

The load-bearing pin: batched mutate-then-validate must equal the
per-object reference path BIT-IDENTICALLY — patches, converged objects,
error outcomes, and downstream sweep verdicts — over the library corpus,
with a MIXED registry (lowered Assign/AssignMetadata + host-only
ModifySet/assignIf) so host-fallback batches are inside the covered set.

Also pinned here:
- the compiled-lane cache keys on the registry revision (mutator churn
  recompiles; the revision is initialized, not conjured);
- `mutation.batch` chaos routes the WHOLE batch to the authoritative
  host walk — graceful fallback, never a lost or diverging mutation;
- `/v1/mutate` through the batched handler + microbatcher: patches,
  DELETE passthrough, excluded namespaces, overload shed under both
  failurePolicies (Ignore = admit unmutated + warning, Fail = 429 +
  Retry-After), and the HTTP header emission;
- `gator bench --engine mutate` and the bench script's smoke lane.
"""

import copy
import http.client
import json
import random
import threading

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.metrics import registry as M
from gatekeeper_tpu.metrics.registry import MetricsRegistry
from gatekeeper_tpu.mutation.system import MutationSystem
from gatekeeper_tpu.mutlane import (BatchedMutationHandler, MutationBatcher,
                                    MutationDifferentialError, MutationLane)
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.resilience.faults import FaultPlan, inject
from gatekeeper_tpu.resilience.overload import Shed
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import load_library, make_cluster_objects
from gatekeeper_tpu.webhook.server import WebhookServer


def _assign(name, location, value, extra=None, kinds=("Pod",)):
    params = {"assign": {"value": value}}
    params.update(extra or {})
    return {
        "apiVersion": "mutations.gatekeeper.sh/v1",
        "kind": "Assign", "metadata": {"name": name},
        "spec": {"applyTo": [{"groups": [""], "versions": ["v1"],
                              "kinds": list(kinds)}],
                 "location": location, "parameters": params},
    }


def _assign_meta(name, location, value):
    return {
        "apiVersion": "mutations.gatekeeper.sh/v1beta1",
        "kind": "AssignMetadata", "metadata": {"name": name},
        "spec": {"location": location,
                 "parameters": {"assign": {"value": value}}},
    }


def _mixed_registry():
    """6 lowered + 2 host-only mutators (the bench registry): the
    batched fragment AND the fallback path both live in every burst."""
    return [
        _assign("pull-policy",
                "spec.containers[name: *].imagePullPolicy", "Always"),
        _assign("host-network", "spec.hostNetwork", False),
        _assign("run-as-nonroot",
                "spec.securityContext.runAsNonRoot", True),
        _assign("priority", "spec.priority", 100),
        _assign_meta("owner-label", "metadata.labels.owner",
                     "platform-team"),
        _assign_meta("audit-ann", "metadata.annotations.audited", "true"),
        # host-only: ModifySet and assignIf are outside the fragment
        {
            "apiVersion": "mutations.gatekeeper.sh/v1",
            "kind": "ModifySet", "metadata": {"name": "topo-keys"},
            "spec": {"applyTo": [{"groups": [""], "versions": ["v1"],
                                  "kinds": ["Service"]}],
                     "location": "spec.topologyKeys",
                     "parameters": {"operation": "merge",
                                    "values": {"fromList": ["zone"]}}},
        },
        _assign("dns-policy-cond", "spec.dnsPolicy", "ClusterFirst",
                extra={"assignIf": {"in": ["Default"]}}),
    ]


def _system(mutators=None):
    system = MutationSystem()
    for m in mutators if mutators is not None else _mixed_registry():
        system.upsert_unstructured(m)
    return system


def _weird_obj(rng, i):
    """Objects whose shapes force walk errors and error-parity routing
    (containers that are not lists, securityContext scalars, ...)."""
    obj = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": f"weird-{i}"}}
    spec = {}
    r = rng.random()
    if r < 0.4:
        spec["containers"] = rng.choice(
            ["notalist", {"a": {}}, 5,
             [{"name": "app", "imagePullPolicy": 7}]])
    elif r < 0.7:
        spec["securityContext"] = rng.choice(["bogus", 3, []])
    else:
        spec["priority"] = rng.choice(["100", True])
        obj["metadata"]["labels"] = "notadict"
    obj["spec"] = spec
    return obj


def _corpus(n=200, seed=29, weird=24):
    rng = random.Random(seed)
    objects = make_cluster_objects(n, seed=seed)
    objects += [_weird_obj(rng, i) for i in range(weird)]
    rng.shuffle(objects)
    return objects


def _outcome_sig(o):
    return (o.changed, o.patch, o.error is None, o.obj)


# --- THE differential: batched == reference over the library corpus -------

def test_batched_lane_bit_identical_to_reference():
    """Patches, converged objects, and error outcomes equal the
    per-object reference path over a mixed corpus, and every outcome
    lane (noop/device/solo/multi/host) is actually exercised."""
    metrics = MetricsRegistry()
    lane = MutationLane(_system(), metrics=metrics)
    objects = _corpus()
    # steady-state admissions arrive already converged (the webhook
    # reality): pre-converge a slice so the noop fast path is covered
    objects += [lane.reference_outcome(o).obj
                for o in make_cluster_objects(24, seed=91)]
    outcomes = lane.mutate_objects(objects, want_objects=True)
    lanes_seen = set()
    for obj, got in zip(objects, outcomes):
        want = lane.reference_outcome(obj)
        lanes_seen.add(got.lane)
        assert got.patch == want.patch, (got.lane, obj, got.patch,
                                         want.patch)
        assert got.changed == want.changed, (got.lane, obj)
        assert (got.error is None) == (want.error is None), (
            got.lane, obj, got.error, want.error)
        if got.error is None:
            assert got.obj == want.obj, (got.lane, obj)
        else:
            # the host path reproduced the reference's exact message
            assert got.error == want.error
    # the corpus must exercise the fragment AND the fallbacks
    assert "device" in lanes_seen or "multi" in lanes_seen, lanes_seen
    assert "host" in lanes_seen, lanes_seen
    assert "noop" in lanes_seen, lanes_seen
    assert metrics.get_counter(M.MUTATION_BATCH) >= 1
    fallback = sum(1 for o in outcomes if o.lane == "host")
    total_fb = sum(
        metrics.get_counter(M.MUTATION_FALLBACK, {"reason": r})
        for r in ("host_mutator", "multi", "interacting", "error",
                  "match", "chaos"))
    assert total_fb == fallback
    ops = sum(len(o.patch) for o in outcomes if o.patch)
    assert metrics.get_counter(M.MUTATION_PATCH_OPS) == ops > 0


def test_differential_mode_is_silent_on_agreement():
    lane = MutationLane(_system(), differential=True)
    lane.mutate_objects(_corpus(n=60, seed=5, weird=8),
                        want_objects=True)  # no raise


def test_differential_mode_catches_divergence(monkeypatch):
    """Corrupt the device patch emission: the differential harness must
    flag it (proves the harness can actually fail)."""
    lane = MutationLane(
        _system([_assign("host-network", "spec.hostNetwork", False)]),
        differential=True)
    orig = MutationLane._emit_scalar

    def corrupted(self, m, batch, oi, obj, want_objects):
        out = orig(self, m, batch, oi, obj, want_objects)
        if out.patch:
            out.patch = [dict(out.patch[0], value="WRONG")]
        return out

    monkeypatch.setattr(MutationLane, "_emit_scalar", corrupted)
    with pytest.raises(MutationDifferentialError):
        lane.mutate_objects([{"apiVersion": "v1", "kind": "Pod",
                              "metadata": {"name": "p"}, "spec": {}}])


def test_mutate_then_validate_verdicts_identical():
    """Downstream verdicts: an audit sweep over the batched lane's
    converged corpus equals the sweep over the reference path's
    converged corpus — the full mutate-then-validate composition."""
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[AUDIT_EP])
    load_library(client)
    lane = MutationLane(_system())
    objects = make_cluster_objects(120, seed=37)

    batched = [o.obj for o in lane.mutate_objects(objects,
                                                  want_objects=True)]
    reference = [lane.reference_outcome(o).obj for o in objects]

    def sweep(objs):
        run = AuditManager(
            client, lister=lambda: iter(copy.deepcopy(objs)),
            config=AuditConfig(chunk_size=64, exact_totals=False,
                               pipeline="off"),
            evaluator=ShardedEvaluator(tpu, make_mesh(),
                                       violations_limit=20),
        ).audit()
        return (run.total_violations,
                {k: [(v.message, v.kind, v.name, v.namespace,
                      v.enforcement_action) for v in vs]
                 for k, vs in run.kept.items()})

    sig_batched = sweep(batched)
    sig_reference = sweep(reference)
    assert sum(sig_batched[0].values()) > 0, "corpus produced no verdicts"
    assert sig_batched == sig_reference


# --- compile cache keyed on the registry revision -------------------------

def test_revision_initialized_and_bumped():
    system = MutationSystem()
    assert system.revision() == 0  # initialized in __init__, not conjured
    system.upsert_unstructured(_assign("a", "spec.hostNetwork", False))
    assert system.revision() == 1
    system.remove(next(iter(system.mutators())).id)
    assert system.revision() == 2


def test_mutator_churn_invalidates_compiled_lane():
    system = _system([_assign("host-network", "spec.hostNetwork", False)])
    lane = MutationLane(system)
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "p"}, "spec": {}}
    first = lane.compiled()
    assert lane.compiled() is first  # cached while the registry is quiet
    assert lane.mutate_objects([pod])[0].patch == [
        {"op": "add", "path": "/spec/hostNetwork", "value": False}]
    # in-place churn: same id, different value — MUST recompile
    system.upsert_unstructured(_assign("host-network",
                                       "spec.hostNetwork", True))
    second = lane.compiled()
    assert second is not first
    assert second.revision > first.revision
    assert lane.mutate_objects([pod])[0].patch == [
        {"op": "add", "path": "/spec/hostNetwork", "value": True}]


# --- chaos: the batched program is "down" ---------------------------------

def test_chaos_batch_fault_routes_to_host_identically():
    metrics = MetricsRegistry()
    lane = MutationLane(_system(), metrics=metrics)
    objects = _corpus(n=40, seed=3, weird=6)
    want = [lane.reference_outcome(o) for o in objects]
    plan = FaultPlan([{"site": "mutation.batch", "mode": "error"}])
    with inject(plan):
        outcomes = lane.mutate_objects(objects, want_objects=True)
    assert all(o.lane == "host" for o in outcomes)
    assert metrics.get_counter(M.MUTATION_FALLBACK,
                               {"reason": "chaos"}) == len(objects)
    for got, ref in zip(outcomes, want):
        assert got.patch == ref.patch
        assert (got.error is None) == (ref.error is None)
    # chaos lifted: the lane classifies again (not stuck on host)
    normal = lane.mutate_objects(objects[:8])
    assert any(o.lane != "host" for o in normal)


# --- /v1/mutate serving ---------------------------------------------------

def _review(uid, obj, operation="CREATE", namespace=""):
    req = {"uid": uid, "operation": operation,
           "kind": {"group": "", "version": "v1",
                    "kind": obj.get("kind", "Pod")},
           "userInfo": {"username": "t"}, "object": obj}
    if namespace:
        req["namespace"] = namespace
    return {"apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview", "request": req}


POD = {"apiVersion": "v1", "kind": "Pod",
       "metadata": {"name": "p"}, "spec": {}}


def test_handler_patch_delete_and_exclusion():
    class _Excluder:
        def is_excluded(self, process, namespace):
            return namespace == "kube-system"

    h = BatchedMutationHandler(_system(), process_excluder=_Excluder())
    r = h.handle(_review("u1", copy.deepcopy(POD)))
    assert r.allowed and r.patch, r
    ref = MutationLane(_system()).reference_outcome(copy.deepcopy(POD))
    assert r.patch == ref.patch
    # DELETE passes through unmutated (reference: CREATE/UPDATE only)
    r = h.handle(_review("u2", copy.deepcopy(POD), operation="DELETE"))
    assert r.allowed and r.patch is None
    # excluded namespace passes through
    r = h.handle(_review("u3", copy.deepcopy(POD),
                         namespace="kube-system"))
    assert r.allowed and r.patch is None


def test_handler_error_answers_allowed_with_message():
    h = BatchedMutationHandler(_system())
    bad = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "bad"},
           "spec": {"containers": "notalist"}}
    want = MutationLane(_system()).reference_outcome(copy.deepcopy(bad))
    assert want.error is not None  # the corpus shape really errors
    r = h.handle(_review("u1", bad))
    assert r.allowed and r.patch is None
    assert r.message == want.error


class _ShedGate:
    """OverloadController stand-in whose admit always sheds."""

    def __init__(self, reason="queue_full", retry_after_s=2.0):
        self.reason = reason
        self.retry_after_s = retry_after_s

    def admit(self, cost):
        raise Shed(self.reason, self.retry_after_s)


def test_shed_failure_policy_ignore_admits_unmutated():
    h = BatchedMutationHandler(_system(), overload=_ShedGate(),
                               failure_policy="ignore")
    r = h.handle(_review("u1", copy.deepcopy(POD)))
    assert r.allowed and r.patch is None
    assert r.warnings and "shed" in r.warnings[0]


def test_shed_failure_policy_fail_429_retry_after():
    h = BatchedMutationHandler(_system(), overload=_ShedGate(),
                               failure_policy="fail")
    r = h.handle(_review("u1", copy.deepcopy(POD)))
    assert not r.allowed
    assert r.code == 429
    assert r.retry_after_s == pytest.approx(2.0)


def test_server_mutate_endpoint_emits_retry_after_header():
    h = BatchedMutationHandler(_system(), overload=_ShedGate(),
                               failure_policy="fail")
    srv = WebhookServer(mutation_handler=h, port=0).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        c.request("POST", "/v1/mutate",
                  json.dumps(_review("u1", POD)).encode(),
                  {"Content-Type": "application/json"})
        resp = c.getresponse()
        doc = json.loads(resp.read())
        c.close()
        assert resp.getheader("Retry-After") == "2"
        assert doc["response"]["allowed"] is False
        assert doc["response"]["status"]["code"] == 429
    finally:
        srv.stop(drain_timeout=2)


def test_server_mutate_endpoint_patch_roundtrip():
    """The full wire path: POST /v1/mutate through the microbatcher,
    base64 JSONPatch in the response, bit-identical to the reference."""
    import base64

    system = _system()
    lane = MutationLane(system)
    batcher = MutationBatcher(lane).start()
    h = BatchedMutationHandler(system, lane=lane, batcher=batcher)
    srv = WebhookServer(mutation_handler=h, port=0,
                        mutation_batcher=batcher).start()
    try:
        want = MutationLane(_system()).reference_outcome(
            copy.deepcopy(POD))
        results = {}
        lock = threading.Lock()

        def post(i):
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=10)
            c.request("POST", "/v1/mutate",
                      json.dumps(_review(f"u{i}", POD)).encode(),
                      {"Content-Type": "application/json"})
            doc = json.loads(c.getresponse().read())
            with lock:
                results[f"u{i}"] = doc["response"]
            c.close()

        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert len(results) == 8
        for uid, resp in results.items():
            assert resp["uid"] == uid
            assert resp["allowed"] is True
            assert resp["patchType"] == "JSONPatch"
            patch = json.loads(base64.b64decode(resp["patch"]))
            assert patch == want.patch
    finally:
        srv.stop(drain_timeout=5)
        batcher.stop()


def test_mutation_batcher_stop_drains_queue():
    """Reviews queued in the mutate batcher at stop() time still answer
    (zero-loss drain covers /v1/mutate)."""
    lane = MutationLane(_system())
    b = MutationBatcher(lane, max_batch=2).start()
    plan = FaultPlan([{"site": "mutation.batch", "mode": "sleep",
                       "delay_s": 0.05}])
    results, errors = {}, {}

    def one(i):
        try:
            results[i] = b.mutate({"apiVersion": "v1", "kind": "Pod",
                                   "metadata": {"name": f"p{i}"},
                                   "spec": {}}, None)
        except Exception as e:  # noqa: BLE001
            errors[i] = e

    with inject(plan):
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(10)]
        for t in threads:
            t.start()
        drained = b.stop(timeout=15)
        for t in threads:
            t.join(15)
    assert drained
    assert errors == {}
    assert len(results) == 10
    assert b.queue_depth() == 0
    # chaos error mode routed to host: the verdicts are still correct
    for out in results.values():
        assert out.patch  # every empty pod gets mutated


# --- gator bench + the bench script ---------------------------------------

def test_gator_bench_mutate_engine():
    from gatekeeper_tpu.gator.bench import run_bench

    objs = _mixed_registry() + make_cluster_objects(40, seed=17)
    r = run_bench(objs, "mutate", iterations=2)
    assert r.engine == "mutate"
    assert r.reviews_per_sec > 0
    lo = r.lowering
    assert lo["lowered_mutators"] == 6
    assert lo["host_only_mutators"] == 2
    assert lo["host_objs_per_sec"] > 0
    assert sum(lo["lanes"].values()) == r.objects



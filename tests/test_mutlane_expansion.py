"""Batched expansion stage (ISSUE 7): edge cases the recursive path
never pinned, asserted IDENTICAL between `mutlane.ExpansionStage` /
the audit generator stage and the recursive `expansion/system.py`:

- depth-cap (30) enforcement voids the base with the reference's exact
  error message;
- owner-ref + mock-name stamping and namespace resolution (real ns,
  parent ns, empty-ns pop) byte-for-byte;
- nested generator recursion (Deployment → ReplicaSet → Pod) in the
  reference's depth-first output order;
- `enforcementAction` override + `[Implied by <template>]` prefix on
  generated resultants in the audit sweep;
- the audit generator stage differential: a relist sweep with the
  batched stage equals the same sweep with a recursive-reference stage
  bit-identically over the library corpus, and snapshot-mode generated
  verdicts (O(churn), per parent gid) equal a fresh relist after churn;
- `gator expand --lane differential` (batched CLI lane vs host walk).
"""

import copy
import os

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.rego_driver import RegoDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.expansion.aggregate import CHILD_MSG_PREFIX
from gatekeeper_tpu.expansion.system import (MAX_RECURSION_DEPTH,
                                             ExpansionError,
                                             ExpansionSystem)
from gatekeeper_tpu.mutation.system import MutationSystem
from gatekeeper_tpu.mutlane import ExpansionStage
from gatekeeper_tpu.mutlane.expand_stage import ExpandResult
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.snapshot import (ClusterSnapshot, SnapshotConfig,
                                     WatchIngester, gvks_of)
from gatekeeper_tpu.sync.source import FakeCluster
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import load_library, make_cluster_objects


def _template(name, from_kind, to_kind, source="spec.template",
              group_from="apps", group_to="", enforcement=""):
    return {
        "apiVersion": "expansion.gatekeeper.sh/v1alpha1",
        "kind": "ExpansionTemplate", "metadata": {"name": name},
        "spec": {"applyTo": [{"groups": [group_from], "versions": ["v1"],
                              "kinds": [from_kind]}],
                 "templateSource": source,
                 "generatedGVK": {"group": group_to, "version": "v1",
                                  "kind": to_kind},
                 **({"enforcementAction": enforcement}
                    if enforcement else {})},
    }


def _assign(name, location, value):
    return {
        "apiVersion": "mutations.gatekeeper.sh/v1",
        "kind": "Assign", "metadata": {"name": name},
        "spec": {"applyTo": [{"groups": [""], "versions": ["v1"],
                              "kinds": ["Pod", "ReplicaSet"]}],
                 "location": location,
                 "parameters": {"assign": {"value": value}}},
    }


def _deployment(name, ns="", priv=False):
    spec = {"containers": [{"name": "app"}]}
    if priv:
        spec["containers"][0]["securityContext"] = {"privileged": True}
    d = {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": name},
         "spec": {"template": {"metadata": {"labels": {"app": name}},
                               "spec": spec}}}
    if ns:
        d["metadata"]["namespace"] = ns
    return d


def _ref_expand_batch(es, bases, namespaces=None):
    """The recursive reference wrapped in the stage's result shape."""
    out = []
    for i, base in enumerate(bases):
        ns = namespaces[i] if namespaces else None
        try:
            out.append(ExpandResult(
                es.expand(copy.deepcopy(base), namespace=ns)))
        except ExpansionError as e:
            out.append(ExpandResult([], error=str(e)))
    return out


def _assert_results_identical(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g.error is None) == (w.error is None), (g.error, w.error)
        if g.error is not None:
            assert g.error == w.error
            continue
        assert [r.obj for r in g.resultants] == \
            [r.obj for r in w.resultants]
        assert [(r.template_name, r.enforcement_action)
                for r in g.resultants] == \
            [(r.template_name, r.enforcement_action)
             for r in w.resultants]


# --- stage vs recursive reference: structural edge cases -------------------

def test_mixed_batch_identical_to_reference():
    """Generators, non-generators, error bases, and namespaces in one
    batch: per-base resultants + errors equal the recursive walk."""
    system = MutationSystem()
    system.upsert_unstructured(_assign("nonroot",
                                       "spec.securityContext.runAsNonRoot",
                                       True))
    es = ExpansionSystem(mutation_system=system)
    es.upsert_template(_template("expand-deployments", "Deployment",
                                 "Pod", enforcement="warn"))
    ns_obj = {"apiVersion": "v1", "kind": "Namespace",
              "metadata": {"name": "prod"}}
    bases = [
        _deployment("web", ns="prod"),
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "plain"}, "spec": {}},  # not a generator
        # templateSource missing → the reference errors the base
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "broken"}, "spec": {}},
        _deployment("bare"),  # no namespace anywhere
    ]
    namespaces = [ns_obj, None, ns_obj, None]
    got = ExpansionStage(es).expand_batch(copy.deepcopy(bases),
                                          namespaces)
    want = _ref_expand_batch(es, bases, namespaces)
    _assert_results_identical(got, want)
    assert got[1].resultants == []  # non-generator expands to nothing
    assert got[2].error and "could not find source field" in got[2].error
    # enforcementAction override rides every resultant
    assert got[0].resultants[0].enforcement_action == "warn"


def test_owner_ref_mock_name_and_namespace_stamping():
    """The stamped resultant, pinned literally AND against the
    reference: mock name `<base>-<kind>` lowercased, owner-ref with
    empty uid, namespace from the Namespace object / parent fallback /
    empty-ns pop."""
    es = ExpansionSystem()
    es.upsert_template(_template("expand-deployments", "Deployment",
                                 "Pod"))
    base = _deployment("WEB", ns="shadowed")
    ns_obj = {"apiVersion": "v1", "kind": "Namespace",
              "metadata": {"name": "real-ns"}}
    stage = ExpansionStage(es)

    got = stage.expand_batch([copy.deepcopy(base)], [ns_obj])[0]
    want = _ref_expand_batch(es, [base], [ns_obj])[0]
    _assert_results_identical([got], [want])
    meta = got.resultants[0].obj["metadata"]
    assert meta["name"] == "web-pod"  # lowercased mock name
    assert meta["namespace"] == "real-ns"  # ns object wins
    assert meta["ownerReferences"] == [{
        "apiVersion": "apps/v1", "kind": "Deployment", "name": "WEB",
        "uid": ""}]

    # no Namespace object: the parent's namespace carries over
    got = stage.expand_batch([copy.deepcopy(base)], [None])[0]
    want = _ref_expand_batch(es, [base], [None])[0]
    _assert_results_identical([got], [want])
    assert got.resultants[0].obj["metadata"]["namespace"] == "shadowed"

    # EMPTY Namespace object (gator's cluster-scoped quirk): the
    # namespace key is POPPED off the resultant
    got = stage.expand_batch([copy.deepcopy(base)], [{}])[0]
    want = _ref_expand_batch(es, [base], [{}])[0]
    _assert_results_identical([got], [want])
    assert "namespace" not in got.resultants[0].obj["metadata"]


def _nest(levels):
    """A base whose spec.template nests ``levels`` deep, so a
    self-recursive template expands ``levels`` generations."""
    node = {"spec": {"leaf": True}}
    for _ in range(levels):
        node = {"spec": {"template": node}}
    return {"apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "recur"}, **node}


def test_depth_cap_enforced_identically():
    """A self-recursive template (generated GVK re-enters its own
    applyTo) past the cap voids the base with the reference's exact
    message; below the cap both walks agree — here on the identical
    missing-source error when the nesting bottoms out."""
    es = ExpansionSystem()
    # apps/v1 Deployment → apps/v1 Deployment: every resultant is
    # itself a generator, recursion runs until the cap
    es.upsert_template(_template("self", "Deployment", "Deployment",
                                 group_to="apps"))

    deep = _nest(MAX_RECURSION_DEPTH + 4)
    got = ExpansionStage(es).expand_batch([copy.deepcopy(deep)])[0]
    want = _ref_expand_batch(es, [deep])[0]
    assert want.error == (f"maximum recursion depth of "
                          f"{MAX_RECURSION_DEPTH} reached")
    _assert_results_identical([got], [want])

    # below the cap the chain bottoms out on a generation with no
    # spec.template: BOTH walks void the base with the same
    # missing-source error (recursion error semantics, not just depth)
    shallow = _nest(5)
    got = ExpansionStage(es).expand_batch([copy.deepcopy(shallow)])[0]
    want = _ref_expand_batch(es, [shallow])[0]
    assert want.error and "could not find source field" in want.error
    _assert_results_identical([got], [want])


def test_depth_cap_generated_gvk_needs_matching_group():
    """The chain above only recurses because the generated GVK
    re-enters the template's applyTo — with group "" the resultant is a
    v1 Deployment, does NOT re-match apps/v1, and a 40-deep nest stays
    one generation (no cap, no error)."""
    es = ExpansionSystem()
    es.upsert_template(_template("once", "Deployment", "Deployment"))
    one = ExpansionStage(es).expand_batch([_nest(40)])[0]
    ref = _ref_expand_batch(es, [_nest(40)])[0]
    _assert_results_identical([one], [ref])
    assert one.error is None
    assert len(one.resultants) == 1


def test_nested_generator_recursion_order():
    """Deployment → ReplicaSet → Pod through two templates: resultants
    arrive in the reference's depth-first output order (the child's
    subtree before the children list), with mutation applied per level
    BEFORE the next level expands."""
    system = MutationSystem()
    # this mutator rewrites the subtree the NESTED generator extracts:
    # level ordering is observable, not cosmetic
    system.upsert_unstructured(_assign("stamp", "spec.stamped", True))
    es = ExpansionSystem(mutation_system=system)
    es.upsert_template(_template("deploy-rs", "Deployment", "ReplicaSet"))
    es.upsert_template(_template("rs-pod", "ReplicaSet", "Pod",
                                 group_from="", enforcement="dryrun"))
    base = {"apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "web", "namespace": "d"},
            "spec": {"template": {
                "metadata": {"labels": {"tier": "rs"}},
                "spec": {"template": {
                    "metadata": {"labels": {"tier": "pod"}},
                    "spec": {"containers": [{"name": "c"}]}}}}}}
    got = ExpansionStage(es).expand_batch([copy.deepcopy(base)])[0]
    want = _ref_expand_batch(es, [base])[0]
    _assert_results_identical([got], [want])
    kinds = [r.obj["kind"] for r in got.resultants]
    assert kinds == ["Pod", "ReplicaSet"]  # subtree first, then child
    # the Pod was extracted from the MUTATED ReplicaSet and then
    # mutated itself
    assert got.resultants[0].obj["spec"]["stamped"] is True
    assert got.resultants[1].obj["spec"]["stamped"] is True
    assert got.resultants[0].obj["metadata"]["name"] == "web-replicaset-pod"
    assert [r.enforcement_action for r in got.resultants] == ["dryrun", ""]


# --- the audit generator stage --------------------------------------------

PRIV_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "k8snoprivileged"},
    "spec": {
        "crd": {"spec": {"names": {"kind": "K8sNoPrivileged"}}},
        "targets": [{
            "target": "admission.k8s.io",
            "rego": """
package k8snoprivileged

violation[{"msg": msg}] {
  c := input.review.object.spec.containers[_]
  c.securityContext.privileged
  msg := sprintf("privileged container %v", [c.name])
}
""",
        }],
    },
}

PRIV_CONSTRAINT = {
    "apiVersion": "constraints.gatekeeper.sh/v1beta1",
    "kind": "K8sNoPrivileged", "metadata": {"name": "no-priv"},
    "spec": {"match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]}},
}


def test_audit_expand_generated_prefix_and_override():
    """`--audit-expand`: a Deployment whose pod template is privileged
    produces a violation on the IMPLIED Pod — `[Implied by <template>]`
    prefix, the template's enforcementAction override, counted in
    totals — while the expand-off sweep sees nothing."""
    client = Client(target=K8sValidationTarget(), drivers=[RegoDriver()],
                    enforcement_points=[AUDIT_EP])
    client.add_template(PRIV_TEMPLATE)
    client.add_constraint(PRIV_CONSTRAINT)
    es = ExpansionSystem(mutation_system=MutationSystem())
    es.upsert_template(_template("expand-deployments", "Deployment",
                                 "Pod", enforcement="warn"))
    objects = [_deployment("web", ns="prod", priv=True),
               {"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "prod"}}]

    def run(expand):
        return AuditManager(
            client, lister=lambda: iter(copy.deepcopy(objects)),
            config=AuditConfig(chunk_size=16, pipeline="off",
                               expand_generated=expand),
            expansion_system=es,
        ).audit()

    off = run(False)
    assert sum(off.total_violations.values()) == 0

    on = run(True)
    key = ("K8sNoPrivileged", "no-priv")
    assert on.total_violations[key] == 1
    v = on.kept[key][0]
    assert v.message.startswith(CHILD_MSG_PREFIX % "expand-deployments")
    assert "privileged container app" in v.message
    assert v.enforcement_action == "warn"  # the template's override
    assert v.kind == "Pod" and v.name == "web-pod"
    assert v.namespace == "prod"


class _RefStage:
    """Recursive-reference drop-in for the batched ExpansionStage."""

    def __init__(self, es):
        self.es = es

    def expand_batch(self, bases, namespaces=None, source=""):
        return _ref_expand_batch(self.es, bases, namespaces)


@pytest.fixture(scope="module")
def library_corpus():
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[AUDIT_EP])
    load_library(client)
    objects = make_cluster_objects(140, seed=43)
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=20)
    return client, objects, evaluator


def _expansion_system():
    system = MutationSystem()
    system.upsert_unstructured(_assign(
        "nonroot", "spec.securityContext.runAsNonRoot", True))
    es = ExpansionSystem(mutation_system=system)
    es.upsert_template(_template("expand-deployments", "Deployment",
                                 "Pod", enforcement="warn"))
    return es


def _mgr(client, evaluator, objects, es, **cfg_kw):
    cfg_kw.setdefault("chunk_size", 64)
    cfg_kw.setdefault("exact_totals", False)
    cfg_kw.setdefault("pipeline", "off")
    cfg_kw.setdefault("expand_generated", True)
    lister = (objects if callable(objects)
              else (lambda: iter(copy.deepcopy(objects))))
    return AuditManager(client, lister=lister,
                        config=AuditConfig(**cfg_kw),
                        evaluator=evaluator, expansion_system=es)


def test_audit_generator_stage_differential_library(library_corpus):
    """THE audit-stage differential: the relist sweep with the batched
    expansion stage equals the same sweep with the recursive-reference
    stage bit-identically over the library corpus (device grid for
    lowered kinds, driver lane for the rest, Generated mutation
    applied) — and the generated rows really contribute verdicts."""
    client, objects, evaluator = library_corpus
    es = _expansion_system()

    batched = _mgr(client, evaluator, objects, es).audit()

    ref_mgr = _mgr(client, evaluator, objects, es)
    ref_mgr._expansion_stage = _RefStage(es)
    reference = ref_mgr.audit()

    diff = AuditManager._verdicts_differ_canonical(
        batched.kept, batched.total_violations,
        reference.kept, reference.total_violations, 20)
    assert diff is None, diff

    plain = _mgr(client, evaluator, objects, es,
                 expand_generated=False).audit()
    assert sum(batched.total_violations.values()) > \
        sum(plain.total_violations.values()), \
        "the generator stage added no verdicts — vacuous differential"
    # implied-Pod violations carry the prefix + override
    gen = [v for vs in batched.kept.values() for v in vs
           if v.message.startswith("[Implied by")]
    assert gen and all(v.enforcement_action == "warn" for v in gen)


def test_snapshot_generated_verdicts_track_churn(library_corpus):
    """Snapshot mode: generated verdicts live per parent gid and ride
    the dirty set — full pass, post-churn tick (modified/deleted/added
    generators), and the built-in resync differential all equal a fresh
    relist with the same expansion stage."""
    client, objects, evaluator = library_corpus
    es = _expansion_system()
    cluster = FakeCluster()
    for o in objects:
        cluster.apply(copy.deepcopy(o))

    def lister():
        return iter(cluster.list())

    snapshot = ClusterSnapshot(evaluator, SnapshotConfig())
    snap_mgr = AuditManager(
        client, lister=lister,
        config=AuditConfig(audit_source="snapshot", chunk_size=64,
                           exact_totals=False, pipeline="off",
                           expand_generated=True, resync_every=0),
        evaluator=evaluator, snapshot=snapshot, expansion_system=es)
    relist_mgr = _mgr(client, evaluator, lister, es)

    def assert_identical(snap_run):
        relist_run = relist_mgr.audit()
        diff = AuditManager._verdicts_differ_canonical(
            snap_run.kept, snap_run.total_violations,
            relist_run.kept, relist_run.total_violations, 20)
        assert diff is None, diff

    ingester = WatchIngester(snapshot, cluster,
                             gvks_of(cluster.list())).start()
    try:
        first = snap_mgr.audit()  # full pass builds generated verdicts
        assert_identical(first)
        assert any(v.message.startswith("[Implied by")
                   for vs in first.kept.values() for v in vs)

        # churn: a generator's pod template changes (its generated
        # verdicts must recompute), one generator disappears, a fresh
        # one appears
        deps = [o for o in cluster.list()
                if o.get("kind") == "Deployment"]
        assert len(deps) >= 2, "corpus must contain generators"
        mod = copy.deepcopy(deps[0])
        tmpl = mod["spec"].setdefault("template", {})
        tmpl.setdefault("spec", {})["hostPID"] = True
        tmpl.setdefault("metadata", {}).setdefault(
            "labels", {})["churn"] = "1"
        cluster.apply(mod)
        cluster.delete(deps[1])
        cluster.apply(_deployment("fresh-gen", ns="default", priv=True))
        ingester.pump()
        assert snapshot.dirty_count() > 0
        assert_identical(snap_mgr.audit_tick())  # O(churn) tick

        # the built-in resync differential (reference sweep expands too)
        resync_run = snap_mgr.audit_resync()
        assert snap_mgr.last_resync_diff is None, snap_mgr.last_resync_diff
        assert not resync_run.incomplete
    finally:
        ingester.stop()


# --- gator expand CLI lanes -----------------------------------------------

def test_gator_expand_differential_lane(tmp_path, capsys):
    import json

    import yaml

    from gatekeeper_tpu.gator.expand_cmd import run_cli

    docs = [
        _template("expand-deployments", "Deployment", "Pod",
                  enforcement="warn"),
        _assign("nonroot", "spec.securityContext.runAsNonRoot", True),
        {"apiVersion": "v1", "kind": "Namespace",
         "metadata": {"name": "prod"}},
        _deployment("web", ns="prod", priv=True),
        _deployment("bare"),
    ]
    path = tmp_path / "input.yaml"
    path.write_text(yaml.safe_dump_all(docs))
    assert run_cli(["-f", str(path), "--lane", "differential",
                    "--format", "json"]) == 0
    out = capsys.readouterr()
    assert "differential: batched lane identical" in out.err
    got = json.loads(out.out)
    # the host walk, run independently, produced the same documents
    assert run_cli(["-f", str(path), "--lane", "host",
                    "--format", "json"]) == 0
    want = json.loads(capsys.readouterr().out)
    assert got == want
    assert any(o.get("metadata", {}).get("name") == "web-pod"
               for o in got)


# --- the scoped working copy: the documented expansion, and json_patch ---------

EXPANSION_LIBRARY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "libraries", "expansion")


def test_expand_deployments_with_the_documented_mutators_is_the_reference():
    """``expand-deployments`` with ``always-pull-image`` (lowered, the
    ``solo`` lane) and ``k8spspdefaultallowprivilegeescalation``
    (host-only, pathTests MustNotExist: the probe, then the host where it
    writes) through the stage equals the recursive reference, the bases
    are left as they were, and no two resultants share a subtree."""
    from gatekeeper_tpu.utils.unstructured import load_yaml_file

    system = MutationSystem()
    for f in ("always-pull-image.yaml",
              "k8spspdefaultallowprivilegeescalation.yaml"):
        for doc in load_yaml_file(os.path.join(EXPANSION_LIBRARY, f)):
            system.upsert_unstructured(doc)
    es = ExpansionSystem(mutation_system=system)
    for doc in load_yaml_file(os.path.join(EXPANSION_LIBRARY,
                                           "expand-deployments.yaml")):
        es.upsert_template(doc)

    def workload(kind, name, containers):
        return {"apiVersion": "apps/v1", "kind": kind,
                "metadata": {"name": name, "namespace": "prod"},
                "spec": {"template": {"metadata": {"labels": {"app": name}},
                                      "spec": {"containers": containers}}}}

    closed = {"allowPrivilegeEscalation": False}
    bases = [
        workload("Deployment", "set", [{"name": "a", "image": "nginx",
                                        "securityContext": dict(closed)}]),
        workload("Deployment", "unset", [{"name": "a"}, {"name": "b"}]),
        workload("ReplicaSet", "mixed",
                 [{"name": "a", "securityContext": dict(closed)},
                  {"name": "b", "securityContext": {"runAsUser": 1}}]),
        workload("StatefulSet", "bogus",
                 [{"name": "a"}, {"name": "b", "securityContext": "x"}]),
        workload("Deployment", "pulled",
                 [{"name": "a", "imagePullPolicy": "Always",
                   "securityContext": dict(closed)}]),
    ]
    ns = {"apiVersion": "v1", "kind": "Namespace",
          "metadata": {"name": "prod"}}
    namespaces = [ns] * len(bases)
    snapshot = copy.deepcopy(bases)
    got = ExpansionStage(es).expand_batch(bases, namespaces)
    assert bases == snapshot
    want = []
    for base in bases:
        # the reference raises the walk's MutateError out of expand
        try:
            want.append(ExpandResult(es.expand(copy.deepcopy(base),
                                               namespace=ns)))
        except Exception as e:  # noqa: BLE001
            want.append(ExpandResult([], error=str(e)))
    _assert_results_identical(got, want)
    assert got[3].error is not None and not got[0].error
    pods = [r.obj for g in got for r in g.resultants]
    assert {p["metadata"]["name"] for p in pods} == {
        "set-pod", "unset-pod", "mixed-pod", "pulled-pod"}
    assert all(c["imagePullPolicy"] == "Always"
               and c["securityContext"]["allowPrivilegeEscalation"] is False
               for p in pods for c in p["spec"]["containers"])
    seen: dict = {}
    for i, p in enumerate(pods):
        stack = [p]
        while stack:
            node = stack.pop()
            if isinstance(node, (dict, list)):
                assert seen.setdefault(id(node), i) == i
                stack.extend(node.values() if isinstance(node, dict)
                             else node)


SHARED = {"keep": {"deep": [1, {"x": True}]}}


@pytest.mark.parametrize("before,after,ops", [
    # an object is its own diff's identity, whatever it holds
    (SHARED, SHARED, []),
    ([1, float("nan")], None, []),
    ({"a": {"b": [1, 2]}, "c": "d"}, None, []),
    # the cases the lane's patches are made of, unchanged
    ({"a": 1}, {"a": 2}, [{"op": "replace", "path": "/a", "value": 2}]),
    ({"a": 1}, {"a": True},
     [{"op": "replace", "path": "/a", "value": True}]),
    ({"a": 1, "b": 2}, {"a": 1},
     [{"op": "remove", "path": "/b"}]),
    ({"spec": {}}, {"spec": {"securityContext": {"runAsNonRoot": True}}},
     [{"op": "add", "path": "/spec/securityContext",
       "value": {"runAsNonRoot": True}}]),
    ({"a/b": {"c~d": 1}}, {"a/b": {"c~d": 2}},
     [{"op": "replace", "path": "/a~1b/c~0d", "value": 2}]),
    ({"l": [{"n": "a"}]}, {"l": [{"n": "a", "p": "Always"}]},
     [{"op": "replace", "path": "/l",
       "value": [{"n": "a", "p": "Always"}]}]),
    ({"l": [1]}, {"l": [1]}, []),
    (1, "1", [{"op": "replace", "path": "/", "value": "1"}]),
    # a scoped copy's new spine over shared subtrees diffs as a deep
    # copy's does
    ({"keep": SHARED["keep"], "spec": {"a": 1}},
     {"keep": SHARED["keep"], "spec": {"a": 1, "b": 2}},
     [{"op": "add", "path": "/spec/b", "value": 2}]),
])
def test_json_patch_is_unchanged_and_skips_what_both_share(before, after,
                                                            ops):
    from gatekeeper_tpu.webhook.mutation import json_patch

    after = before if after is None else after
    assert json_patch(before, after) == ops
    # the same pair with no subtree shared reads the same ops
    if before is not after:
        assert json_patch(copy.deepcopy(before), copy.deepcopy(after)) == ops

"""The mutation lane copies only what a mutator can write.

The lane's working copy of an object (the probe of matching host-only
mutators, ``solo``, ``multi``, and ``device`` under ``want_objects``) is a
new root copied along the location paths of the mutators about to be
applied (``mutation/core.py:copy_along``); every other subtree is shared
with the input.  Each case here runs one burst through the lane and holds
every object of it to three things:

- the input object is deep-equal to a snapshot taken before the call;
- ``outcome.obj``, ``outcome.patch`` and the error equal
  ``reference_outcome`` (a deep copy and the full fixed point);
- a write into every container of ``outcome.obj`` the input does not hold
  leaves the input as it was.

The ``mutlane.apply`` span counts the copies: one whole copy for each
``host`` outcome and none elsewhere.
"""

import copy
import os
import sys

import pytest

from gatekeeper_tpu.expansion.system import ExpansionSystem
from gatekeeper_tpu.match.match import SOURCE_GENERATED
from gatekeeper_tpu.mutation import core
from gatekeeper_tpu.mutation.mutators import (WRITES_ALONG_PATH,
                                              _AssignImageSetter,
                                              _AssignMetaSetter,
                                              _AssignSetter,
                                              _ModifySetSetter)
from gatekeeper_tpu.mutation.path_parser import parse
from gatekeeper_tpu.mutlane import MutationLane
from gatekeeper_tpu.observability import tracing
from gatekeeper_tpu.utils.rawjson import RawJSON
from gatekeeper_tpu.utils.synthetic import make_cluster_objects
from gatekeeper_tpu.utils.unstructured import load_yaml_file
from tests.test_mutlane import _assign, _assign_meta, _corpus, \
    _mixed_registry, _system

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
EXPANSION = os.path.join(ROOT, "benchmark", "libraries", "expansion")


def _pod(name, containers, ns="ns-0", spec=None):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": ns,
                         "labels": {"app": name}},
            "spec": {"containers": containers, **(spec or {})}}


def _modify_set(name, location, values, operation="merge"):
    return {"apiVersion": "mutations.gatekeeper.sh/v1",
            "kind": "ModifySet", "metadata": {"name": name},
            "spec": {"applyTo": [{"groups": [""], "versions": ["v1"],
                                  "kinds": ["Pod"]}],
                     "location": location,
                     "parameters": {"operation": operation,
                                    "values": {"fromList": values}}}}


def _assign_image(name, location, **params):
    return {"apiVersion": "mutations.gatekeeper.sh/v1alpha1",
            "kind": "AssignImage", "metadata": {"name": name},
            "spec": {"applyTo": [{"groups": [""], "versions": ["v1"],
                                  "kinds": ["Pod"]}],
                     "location": location, "parameters": params}}


# --- the cases: a registry, a burst, the source, what it must exercise --------

def _mixed():
    """tests/test_mutlane.py's corpus and mixed registry (six lowered
    mutators, a host-only ModifySet and an assignIf), with a converged
    slice for the noop lane."""
    lane = MutationLane(_system())
    objects = _corpus(n=120, seed=29, weird=24)
    objects += [lane.reference_outcome(o).obj
                for o in make_cluster_objects(16, seed=91)]
    return _mixed_registry(), objects, "", {"noop", "device", "host"}


def _implied_pods(n=1500, seed=2147442001):
    """The implied Pods of the library-full-expand cluster's workloads,
    as the generator stage hands them to the lane (not yet mutated)."""
    from benchmark import cluster, manifest

    cfg = manifest.read_json(os.path.join(
        ROOT, "benchmark", "configs", "library-full-expand.json"))
    es = ExpansionSystem()
    for doc in load_yaml_file(os.path.join(EXPANSION,
                                           "expand-deployments.yaml")):
        es.upsert_template(doc)
    (template,) = es.templates()
    return [ExpansionSystem._expand_resource(o, None, template)
            for o in cluster.Cluster(cfg["cluster"], n, seed).objects(0)
            if template.applies_to(o)]


def _expand_mutators():
    """library-full-expand's two Assigns over its implied Pods: the
    lowered glob ``always-pull-image`` (``solo``) and the host-only
    ``k8spspdefaultallowprivilegeescalation`` with its pathTests
    MustNotExist (the probe), plus a Pod whose walk raises midway: its
    first container takes the write, its second holds a scalar
    securityContext."""
    mutators = [doc for f in ("always-pull-image.yaml",
                              "k8spspdefaultallowprivilegeescalation.yaml")
                for doc in load_yaml_file(os.path.join(EXPANSION, f))]
    objects = _implied_pods()
    objects += [
        _pod("raises-midway", [{"name": "a", "image": "nginx"},
                               {"name": "b", "image": "nginx",
                                "securityContext": "bogus"}]),
        _pod("all-set", [{"name": "a", "image": "nginx",
                          "securityContext": {
                              "allowPrivilegeEscalation": False}}]),
        _pod("none-set", [{"name": "a"}, {"name": "b"}]),
    ]
    return mutators, objects, SOURCE_GENERATED, {"solo", "host"}


def _keyed_and_glob():
    """Keyed list paths (a match, a keyed item created, a whole keyed item
    assigned), a glob ModifySet and an AssignImage on the probe."""
    mutators = [
        _assign("app-pull", "spec.containers[name: app].imagePullPolicy",
                "IfNotPresent"),
        _assign("sidecar-image", "spec.initContainers[name: init].image",
                "busybox"),
        _assign("gpu-toleration", "spec.tolerations[key: gpu].effect",
                "NoSchedule"),
        _assign("cache-volume", "spec.volumes[name: cache]",
                {"name": "cache", "emptyDir": {}}),
        _modify_set("app-args", "spec.containers[name: *].args",
                    ["--verbose"]),
        _assign_image("registry", "spec.ephemeralContainers[name: *].image",
                      assignDomain="registry.local"),
    ]
    cache = [{"name": "cache", "emptyDir": {}}]
    done = {"name": "app", "imagePullPolicy": "IfNotPresent",
            "args": ["--verbose"]}
    gpu = [{"key": "gpu", "effect": "NoSchedule"}]
    objects = [
        # the probe changes nothing: sidecar-image alone, a keyed match
        _pod("init-only", [dict(done)],
             spec={"volumes": cache, "tolerations": gpu,
                   "initContainers": [{"name": "init",
                                       "image": "alpine"}]}),
        # sidecar-image and gpu-toleration: two disjoint keyed paths, the
        # keyed items init and gpu created
        _pod("init-and-gpu", [dict(done), {"name": "web",
                                           "args": ["--verbose"]}],
             spec={"initContainers": [{"name": "other"}],
                   "tolerations": [{"key": "spot"}],
                   "volumes": cache + [{"name": "data", "emptyDir": {}}]}),
        # app-pull would create the item app that app-args' glob then
        # walks: not independent, so the host's fixed point owns it
        _pod("app-missing", [{"name": "web", "args": ["--verbose"]}],
             spec={"volumes": cache, "tolerations": gpu,
                   "initContainers": [{"name": "init",
                                       "image": "busybox"}]}),
        # the probe writes, so the host owns these: args created by the
        # glob ModifySet, a keyed item added, a keyed item rewritten in
        # place, an image's domain
        _pod("args-missing", [{"name": "app"}], spec={"volumes": cache}),
        _pod("no-cache", [{"name": "web", "args": ["--verbose"]}],
             spec={"volumes": [{"name": "data", "emptyDir": {}}]}),
        _pod("cache-on-host", [{"name": "web", "args": ["--verbose"]}],
             spec={"volumes": [{"name": "cache", "hostPath": {}}]}),
        _pod("ephemeral", [{"name": "app", "args": ["--verbose"]}],
             spec={"volumes": cache,
                   "ephemeralContainers": [{"name": "dbg",
                                            "image": "busybox:1"}]}),
    ]
    return mutators, objects, "", {"solo", "multi", "host"}


def _created_and_undone():
    """A missing intermediate the walk creates and then undoes (an
    assignIf that fails at the leaf), and pathTests MustNotExist on an
    intermediate node."""
    mutators = [
        _assign("root-only", "spec.securityContext.runAsUser", 1000,
                extra={"assignIf": {"in": [0]}}),
        _assign("fresh-dns", "spec.dnsConfig.ndots", 2,
                extra={"pathTests": [{"subPath": "spec.dnsConfig",
                                      "condition": "MustNotExist"}]}),
        _assign_meta("owner", "metadata.labels.owner", "platform"),
    ]
    objects = [
        # the probe creates securityContext, fails the gate and undoes it
        _pod("bare", [{"name": "a"}], spec={"dnsConfig": {"ndots": 5}}),
        _pod("owned", [{"name": "a"}], spec={"dnsConfig": {"ndots": 5}}),
        _pod("as-root", [{"name": "a"}],
             spec={"securityContext": {"runAsUser": 0},
                   "dnsConfig": {}}),
        _pod("no-dns", [{"name": "a"}]),
    ]
    objects[1]["metadata"]["labels"]["owner"] = "someone"
    return mutators, objects, "", {"noop", "device", "host"}


CASES = {
    "mixed-corpus": _mixed,
    "library-full-expand": _expand_mutators,
    "keyed-and-glob": _keyed_and_glob,
    "created-and-undone": _created_and_undone,
}


def _containers(tree, out=None) -> list:
    out = [] if out is None else out
    if isinstance(tree, (dict, list)):
        out.append(tree)
        for v in (tree.values() if isinstance(tree, dict) else tree):
            _containers(v, out)
    return out


def _unshared(tree, shared: set) -> list:
    """The containers of ``tree`` whose ids are not in ``shared``, and
    not below one that is."""
    if not isinstance(tree, (dict, list)) or id(tree) in shared:
        return []
    out = [tree]
    for v in (tree.values() if isinstance(tree, dict) else tree):
        out.extend(_unshared(v, shared))
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_working_copies_leave_the_input_as_it_was(case):
    mutators, objects, source, lanes_wanted = CASES[case]()
    lane = MutationLane(_system(mutators))
    snapshot = copy.deepcopy(objects)
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        outcomes = lane.mutate_objects(objects, source=source,
                                       want_objects=True)
    assert objects == snapshot
    lanes = [o.lane for o in outcomes]
    assert lanes_wanted <= set(lanes), lanes

    scoped_lanes = sharing = 0
    for obj, got in zip(objects, outcomes):
        want = lane.reference_outcome(obj, source=source)
        assert (got.error is None) == (want.error is None), \
            (got.lane, obj, got.error, want.error)
        if got.error is not None:
            assert got.error == want.error
            continue
        assert got.patch == want.patch, (got.lane, obj)
        assert got.changed == want.changed and got.obj == want.obj, \
            (got.lane, obj)
        if got.lane in ("device", "solo", "multi"):
            scoped_lanes += 1
        shared = {id(c) for c in _containers(obj)}
        spine = _unshared(got.obj, shared)
        if got.changed and got.lane != "host":
            # a scoped copy: a new root that shares what no mutator wrote
            assert spine and got.obj is not obj
            sharing += len(spine) < len(_containers(got.obj))
        for c in spine:
            if isinstance(c, dict):
                c["__written_after__"] = True
            else:
                c.append("__written_after__")
        assert obj == snapshot[objects.index(obj)], (got.lane, obj)

    assert sharing > 0
    (apply_span,) = [s for t in tracer.traces() for s in t["spans"]
                     if s["name"] == "mutlane.apply"]
    attrs = apply_span["attributes"]
    assert attrs["copies_full"] == lanes.count("host")
    # every solo/multi/device outcome copied along its paths, and each
    # probe of a matching host-only mutator did too
    assert attrs["copies_scoped"] >= scoped_lanes


def test_the_probe_copies_along_the_host_only_paths():
    """library-full-expand's host-only Assign matches every implied Pod:
    each one is probed on a scoped copy, so the scoped copies outnumber
    the solo outcomes by the probes, and only the host outcomes copy
    whole."""
    mutators, objects, source, _ = _expand_mutators()
    lane = MutationLane(_system(mutators))
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        outcomes = lane.mutate_objects(objects, source=source,
                                       want_objects=True)
    (sp,) = [s for t in tracer.traces() for s in t["spans"]
             if s["name"] == "mutlane.apply"]
    lanes = [o.lane for o in outcomes]
    attrs = sp["attributes"]
    assert attrs["copies_scoped"] == len(objects) + lanes.count("solo")
    assert attrs["copies_full"] == lanes.count("host") > 0
    # the walk that raised midway left its Pod to the host, which
    # reproduces the reference's message
    raised = outcomes[objects.index(next(
        o for o in objects if o["metadata"]["name"] == "raises-midway"))]
    assert raised.lane == "host" and raised.error == \
        lane.reference_outcome(objects[-3], source=source).error
    assert "expected object" in raised.error


def test_a_kind_that_may_write_off_its_path_is_copied_whole():
    """A mutator class outside the four kinds (here a subclass, matched by
    exact type) gets a deep copy, and the span counts it as whole."""
    from gatekeeper_tpu.mutation.mutators import AssignMutator

    class Elsewhere(AssignMutator):
        def mutate_obj(self, obj):
            changed = super().mutate_obj(obj)
            if changed:
                obj["metadata"]["touched"] = "yes"
            return changed

    assert Elsewhere not in WRITES_ALONG_PATH
    m = Elsewhere(_assign("dns", "spec.dnsPolicy", "Default",
                          extra={"assignIf": {"in": ["ClusterFirst"]}}))
    system = _system([])
    system.upsert(m)
    lane = MutationLane(system)
    obj = _pod("p", [{"name": "a"}], spec={"dnsPolicy": "ClusterFirst"})
    snapshot = copy.deepcopy(obj)
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        (out,) = lane.mutate_objects([obj], want_objects=True)
    assert obj == snapshot and out.obj == lane.reference_outcome(obj).obj
    (sp,) = [s for t in tracer.traces() for s in t["spans"]
             if s["name"] == "mutlane.apply"]
    # the probe's copy is whole, and so is the host's that follows it
    assert sp["attributes"]["copies_scoped"] == 0
    assert sp["attributes"]["copies_full"] == 2


# --- copy_along itself ------------------------------------------------------------

POD = {"metadata": {"name": "p", "labels": {"a": "b"}},
       "spec": {"containers": [{"name": "c", "securityContext": {"x": 1},
                                "ports": [{"containerPort": 80}]},
                               "not-a-dict"],
                "volumes": [{"name": "v"}]}}


@pytest.mark.parametrize("paths,copied,shared", [
    # an object path: root, spec and securityContext's parent are copied;
    # the leaf's own value and every side branch are shared
    (["spec.hostNetwork"], [(), ("spec",)],
     [("metadata",), ("spec", "containers"), ("spec", "volumes")]),
    # a glob crossing a list: the list and every dict item in it
    (["spec.containers[name: *].securityContext.privileged"],
     [("spec", "containers"), ("spec", "containers", 0),
      ("spec", "containers", 0, "securityContext")],
     [("spec", "containers", 0, "ports"), ("spec", "volumes")]),
    # a last list node rewrites the matched item in place: items copied
    (["spec.volumes[name: v]"],
     [("spec", "volumes"), ("spec", "volumes", 0)],
     [("spec", "containers")]),
    # two paths share their prefix once
    (["metadata.labels.owner", "spec.containers[name: c].image"],
     [("metadata",), ("metadata", "labels"), ("spec", "containers", 0)],
     [("spec", "containers", 0, "securityContext"),
      ("spec", "volumes")]),
])
def test_copy_along_copies_the_spine_and_shares_the_rest(paths, copied,
                                                         shared):
    def at(tree, where):
        for k in where:
            tree = tree[k]
        return tree

    out = core.copy_along(POD, [parse(p) for p in paths])
    assert out == POD
    for where in copied:
        assert at(out, where) is not at(POD, where), where
    for where in shared:
        assert at(out, where) is at(POD, where), where
    # a non-dict list item is never a container of a walk
    assert out["spec"]["containers"][1] == "not-a-dict"


def test_copy_along_keeps_a_loaded_rawjson_root_a_rawjson():
    raw = RawJSON(b'{"spec": {"dnsPolicy": "Default"}, "metadata": {}}')
    raw.get("spec")  # loaded
    out = core.copy_along(raw, [parse("spec.dnsPolicy")])
    assert type(out) is RawJSON and out == raw
    assert out["spec"] is not raw["spec"]
    assert out["metadata"] is raw["metadata"]


# --- the setters return new values and never edit the one they are given ---------

@pytest.mark.parametrize("setter,current", [
    (_AssignSetter({"runAsNonRoot": True, "caps": ["ALL"]}, {}),
     {"runAsNonRoot": False, "caps": []}),
    (_AssignSetter(["a", {"b": 1}], {"in": [["x"]]}), ["x"]),
    (_AssignSetter({"name": "v", "emptyDir": {}}, {}),
     {"name": "v", "hostPath": {"path": "/"}}),
    (_ModifySetSetter(["b", {"k": 1}], "merge"), ["a", {"k": 2}]),
    (_ModifySetSetter(["a"], "prune"), ["a", {"k": 2}, "c"]),
    (_AssignMetaSetter("platform"), None),
    (_AssignImageSetter("registry.local", "", ":v2"), "nginx:1"),
])
def test_a_setter_never_edits_the_current_value(setter, current):
    before = copy.deepcopy(current)
    new, do_set = setter.set_value({"k": current}, "k", current,
                                   current is not None)
    assert current == before
    if isinstance(new, (dict, list)):
        assert new is not current
        # nothing the setter made is the value it holds either, so a
        # later write into the converged object cannot reach the mutator
        held = getattr(setter, "value", None)
        assert held is None or new is not held


# --- independence: a key creates an item a glob on the same list walks ------------

@pytest.mark.parametrize("a,b,alias", [
    ("spec.containers[name: app].imagePullPolicy",
     "spec.containers[name: *].args", True),
    ("spec.containers[name: *].imagePullPolicy",
     "spec.containers[name: *].securityContext.privileged", False),
    ("spec.containers[name: app].image",
     "spec.containers[name: web].image", False),
    ("spec.containers[name: app].image",
     "spec.containers[name: app].args", False),
    ("spec.containers[name: app].image",
     "spec.containers[name: app].image", True),
    ("spec.volumes[name: cache]", "spec.volumes[name: *].emptyDir", True),
    ("spec.hostNetwork", "spec.hostPID", False),
])
def test_paths_may_alias(a, b, alias):
    from gatekeeper_tpu.mutlane.lane import _paths_may_alias

    assert _paths_may_alias(parse(a), parse(b)) is alias
    assert _paths_may_alias(parse(b), parse(a)) is alias

"""Result counts on the device (``Program.count``): for every kind of the
``library-full`` and ``library-cel`` configurations, the count the device
gives a violating (object, constraint) pair is ``len(results)`` of the
template's own engine (``RegoDriver`` / ``CELDriver``), wherever the device
certifies it, on generated objects and on adversarial ones: containers and
init containers that share a name, names and images that hold ``<``, ``>``,
``%v`` or a message's own text, clauses that hold on one item together,
empty and missing item lists.  The kinds whose count the lowerer proves are
pinned, so a proof that quietly declines fails here.  And an exact-lane fold
that renders no hit of a counted row keeps the totals and the kept
violations, in order, of the fold that renders every hit."""

from __future__ import annotations

import copy
import os
import random
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_cel_library import OTHER, _adversarial, _generated  # noqa: E402

from benchmark.libraries import make_cel  # noqa: E402
from gatekeeper_tpu.apis.constraints import AUDIT_EP  # noqa: E402
from gatekeeper_tpu.audit.manager import AuditManager  # noqa: E402
from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.drivers.base import ReviewCfg  # noqa: E402
from gatekeeper_tpu.drivers.cel_driver import CELDriver  # noqa: E402
from gatekeeper_tpu.drivers.rego_driver import RegoDriver  # noqa: E402
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.ir import masks as masks_mod  # noqa: E402
from gatekeeper_tpu.ir.program import (  # noqa: E402
    as_grid, build_param_table, needed_fields, pack_batch_cols, slim_cols,
    vocab_tables, walk_join_values)
from gatekeeper_tpu.ops.flatten import Flattener  # noqa: E402
from gatekeeper_tpu.parallel import sharded  # noqa: E402
from gatekeeper_tpu.target.review import AugmentedUnstructured  # noqa: E402
from gatekeeper_tpu.target.target import K8sValidationTarget  # noqa: E402
from gatekeeper_tpu.utils.unstructured import load_yaml_file  # noqa: E402

TARGET = "admission.k8s.gatekeeper.sh"
LIBRARY = os.path.join(ROOT, "library")
CEL_LIB = os.path.join(ROOT, "benchmark", "libraries", "cel")
PER_KIND = 96  # generated objects of each kind a constraint matches

# library-full: every policy directory; library-cel: the 36 CEL blocks (its
# other ten templates are library-full's own)
FULL = sorted((area, name) for area in ("general", "pod-security-policy")
              for name in os.listdir(os.path.join(LIBRARY, area)))
CEL = sorted(make_cel.POLICIES)
CASES = [("full", area, name) for area, name in FULL] + \
    [("cel", "cel", name) for name in CEL]

# the kinds whose result count the lowerer proves, per library; every
# other kind renders its hits for their count (PERF.md section 7 says why)
COUNTED_FULL = {
    "K8sAllowedRepos", "K8sBlockEndpointEditDefaultRole",
    "K8sBlockLoadBalancer", "K8sBlockNodePort", "K8sBlockWildcardIngress",
    "K8sContainerEphemeralStorageLimit", "K8sContainerLimits",
    "K8sContainerLimitsCEL", "K8sContainerRequests",
    "K8sContainerResources", "K8sDisallowAnonymous",
    "K8sDisallowInteractive", "K8sDisallowedTags", "K8sExternalIPs",
    "K8sHorizontalPodAutoscaler", "K8sHostNamespace",
    "K8sHostNetworkingPorts", "K8sHttpsOnly", "K8sImageDigests",
    "K8sNoPrivileged", "K8sNoUpdateServiceAccount",
    "K8sPSPAllowPrivilegeEscalationContainer", "K8sPSPAllowedUsers",
    "K8sPSPAppArmor", "K8sPSPAutomountServiceAccountTokenPod",
    "K8sPSPFSGroup", "K8sPSPFlexVolumes", "K8sPSPForbiddenSysctls",
    "K8sPSPHostFilesystem", "K8sPSPProcMount",
    "K8sPSPReadOnlyRootFilesystem", "K8sPSPSELinuxV2", "K8sPSPSeccomp",
    "K8sPSPVolumeTypes", "K8sPodDisruptionBudget", "K8sReplicaLimits",
    "K8sStorageClass", "K8sUniqueIngressHost",
}
UNCOUNTED_FULL = {
    # a message that names a parameter element beside the item
    "K8sDisallowedRepos", "K8sPSPCapabilities", "K8sRequiredProbes",
    "K8sVerifyDeprecatedAPI",
    # two clauses print one format over different parameters
    "K8sContainerRatios",
    # details beside the message; a message through get_message
    "K8sRequiredAnnotations", "K8sRequiredLabels",
    # a message that names the inventory entry
    "K8sUniqueServiceSelector",
}


def _docs(lib: str, area: str, name: str) -> tuple:
    d = os.path.join(CEL_LIB if lib == "cel" else
                     os.path.join(LIBRARY, area), name)
    return (load_yaml_file(os.path.join(d, "template.yaml"))[0],
            load_yaml_file(os.path.join(d, "samples", "constraint.yaml"))[0])


def _constraint_docs(cdoc: dict, name: str) -> list:
    """The sample constraint and, where the CEL suite has one, a second
    parameter set."""
    docs = [cdoc]
    if name in OTHER:
        other = copy.deepcopy(cdoc)
        other["metadata"]["name"] += "-other"
        other.setdefault("spec", {}).pop("parameters", None)
        if OTHER[name] is not None:
            other["spec"]["parameters"] = copy.deepcopy(OTHER[name])
        docs.append(other)
    return docs


def _kinds(con) -> list:
    kinds = sorted({k for block in (con.match or {}).get("kinds") or []
                    for k in block.get("kinds") or []})
    return kinds or ["Pod", "Service", "Ingress"]


# --- adversarial objects of this module ------------------------------------

# names that collide, that hold a message's delimiters, its verbs or its text
NAMES = ["app", "app", "side", "a>b", "<c", "d%v", "e>", "",
         "x> has no memory limit", "y> memory limit <1Gi",
         "z> is running with stdin attached, which is not allowed", 5, None]
IMAGES = ["nginx", "nginx:latest", "nginx:1.2", "evil.io/x:v1",
          "openpolicyagent/app1:latest", "img> uses a disallowed tag <x",
          "pause@sha256:" + "a" * 64, "q<r>s:v2", 7]


def _tricky_container(rng, j: int) -> dict:
    c: dict = {"name": rng.choice(NAMES + [f"c{j}"] * 4)}
    if rng.random() < 0.9:
        c["image"] = rng.choice(IMAGES)
    res: dict = {}
    for where in ("limits", "requests"):
        if rng.random() < 0.6:
            res[where] = {k: rng.choice(["100m", "2", "512Mi", "4Gi", "1",
                                         "banana", 2])
                          for k in ("cpu", "memory", "ephemeral-storage")
                          if rng.random() < 0.6}
    if res or rng.random() < 0.5:
        c["resources"] = res
    sc: dict = {}
    for f, vals in (("privileged", [True, False]),
                    ("allowPrivilegeEscalation", [True, False]),
                    ("readOnlyRootFilesystem", [True, False]),
                    ("procMount", ["Unmasked", "Default"]),
                    ("runAsUser", [0, 1000, 150]),
                    ("seccompProfile", [{"type": "Unconfined"},
                                        {"type": "RuntimeDefault"},
                                        {"type": "a>b"}]),
                    ("seLinuxOptions", [{"level": "s0"}])):
        if rng.random() < 0.4:
            sc[f] = rng.choice(vals)
    if sc:
        c["securityContext"] = sc
    for f in ("tty", "stdin"):
        if rng.random() < 0.3:
            c[f] = True
    return c


def _tricky_pod(rng, i: int) -> dict:
    spec: dict = {}
    for axis, p in (("containers", 0.95), ("initContainers", 0.5),
                    ("ephemeralContainers", 0.2)):
        if rng.random() < p:
            spec[axis] = [_tricky_container(rng, j)
                          for j in range(rng.choice([0, 1, 2, 3, 4]))]
    if rng.random() < 0.5:
        spec["securityContext"] = {
            "sysctls": [{"name": rng.choice(
                ["kernel.msgmax", "net.core.x", "net.core.x", "a>b"])}
                for _ in range(rng.randint(0, 3))],
            "runAsUser": rng.choice([0, 1000]),
            "seccompProfile": {"type": rng.choice(["Unconfined",
                                                   "RuntimeDefault"])},
        }
    if rng.random() < 0.5:
        spec["volumes"] = [
            rng.choice([{"name": "v", "hostPath": {"path": rng.choice(
                ["/var/x", "/etc", "/etc", "/a>b"])}},
                {"name": "f", "flexVolume": {"driver": rng.choice(
                    ["ex/lvm", "ex/lvm", "a>b"])}},
                {"name": "e", "emptyDir": {}}])
            for _ in range(rng.randint(0, 3))]
    for f in ("hostNetwork", "hostPID", "hostIPC"):
        if rng.random() < 0.2:
            spec[f] = True
    if rng.random() < 0.5:
        spec["serviceAccountName"] = rng.choice(["sa-a", "sa-old"])
    meta: dict = {"name": rng.choice([f"p{i}", f"p{i}", "q>r"]),
                  "namespace": "ns-1"}
    if rng.random() < 0.4:
        pre = "container.apparmor.security.beta.kubernetes.io/"
        meta["annotations"] = {
            pre + rng.choice(["c0", "c1", "a>b"]): rng.choice(
                ["unconfined", "unconfined", "x> (annotation <y"])
            for _ in range(rng.randint(1, 3))}
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
            "spec": spec}


def _tricky_other(rng, i: int, kind: str) -> dict:
    meta = {"name": f"o{i}", "namespace": "ns-1"}
    if kind == "Service":
        return {"apiVersion": "v1", "kind": kind, "metadata": meta,
                "spec": {"type": rng.choice(["NodePort", "LoadBalancer"]),
                         "externalIPs": [rng.choice(
                             ["10.0.0.1", "10.0.0.1", "a>b", "203.0.113.0"])
                             for _ in range(rng.randint(0, 3))]}}
    if kind == "Ingress":
        return {"apiVersion": "networking.k8s.io/v1", "kind": kind,
                "metadata": meta,
                "spec": {"rules": [rng.choice(
                    [{"host": "a.example.com"}, {"host": "a.example.com"},
                     {"host": "*.x"}, {"host": "*.x"}, {}])
                    for _ in range(rng.randint(0, 3))]}}
    if kind in ("RoleBinding", "ClusterRoleBinding"):
        return {"apiVersion": "rbac.authorization.k8s.io/v1", "kind": kind,
                "metadata": meta,
                "subjects": [{"kind": "User", "name": rng.choice(
                    ["system:anonymous", "system:anonymous",
                     "system:unauthenticated", "alice"])}
                    for _ in range(rng.randint(0, 3))]}
    if kind == "PodDisruptionBudget":
        return {"apiVersion": "policy/v1", "kind": kind, "metadata": meta,
                "spec": rng.choice([{"maxUnavailable": 0},
                                    {"maxUnavailable": "0"},
                                    {"minAvailable": "100%"},
                                    {"minAvailable": "100%",
                                     "maxUnavailable": 0}])}
    if kind == "HorizontalPodAutoscaler":
        return {"apiVersion": "autoscaling/v2", "kind": kind,
                "metadata": meta,
                "spec": {"minReplicas": rng.randint(0, 20),
                         "maxReplicas": rng.randint(0, 60)}}
    if kind == "PersistentVolumeClaim":
        spec = {} if rng.random() < 0.4 else {
            "storageClassName": rng.choice(["standard", "missing"])}
        return {"apiVersion": "v1", "kind": kind, "metadata": meta,
                "spec": spec}
    return {"apiVersion": "v1", "kind": kind, "metadata": meta}


def _colliding_pods() -> list:
    """Pods whose two items print one message where a value holds the
    text between two of its message's arguments: the interpreter keeps
    one result, so a count of the distinct tuples that did not leave such
    a pod uncertified would say two."""
    ann = "container.apparmor.security.beta.kubernetes.io/"
    pairs = []
    for between in ("> didn't specify an image tag <",
                    "> uses an image without a digest <",
                    "> has an invalid image repo <"):
        pairs.append([{"name": "p", "image": "q" + between + "r"},
                      {"name": "p" + between + "q", "image": "r"}])
    between = "> is not allowed for container <"
    pairs.append([
        {"name": "B" + between + "C", "image": "x",
         "securityContext": {"seccompProfile": {"type": "A"}}},
        {"name": "C", "image": "x", "securityContext": {
            "seccompProfile": {"type": "A" + between + "B"}}}])
    between = "> (annotation <"
    pods = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"collide-{i}", "namespace": "ns-1"},
             "spec": {"containers": cs}} for i, cs in enumerate(pairs)]
    pods[0]["metadata"]["annotations"] = {
        ann + "w" + between + ann + "z": "v", ann + "z": "v" + between
        + ann + "w"}
    return pods


def _objects(kinds: list, seed: int) -> list:
    rng = random.Random(seed)
    out = _colliding_pods() if "Pod" in kinds else []
    for kind in kinds:
        out.extend(_generated().get(kind, [])[:PER_KIND])
    out.extend(_adversarial(set(kinds), 96, seed))
    for i in range(96):
        kind = kinds[i % len(kinds)]
        out.append(_tricky_pod(rng, i) if kind == "Pod"
                   else _tricky_other(rng, i, kind))
    return out


def _syncable(obj) -> bool:
    """An Ingress the device's inventory tables hold exactly: every join
    value and its owner's name and namespace strings (elsewhere the sweep
    routes the referential kind to the interpreter)."""
    meta = obj.get("metadata")
    return (obj.get("kind") == "Ingress" and isinstance(meta, dict)
            and all(isinstance(meta.get(f, ""), str)
                    for f in ("name", "namespace"))
            and all(isinstance(v, str) for v in walk_join_values(
                obj, ("spec", "rules", "*", "host"))))


def _reviews(objects: list) -> list:
    """Audit reviews, every third an UPDATE from a copy of the object
    under another service account (``K8sNoUpdateServiceAccount``)."""
    target = K8sValidationTarget()
    out = []
    for i, obj in enumerate(objects):
        review = target.handle_review(AugmentedUnstructured(object=obj))
        if i % 3 == 0 and isinstance(obj.get("spec"), dict):
            old = copy.deepcopy(obj)
            old["spec"]["serviceAccountName"] = "sa-old"
            review.request.operation = "UPDATE"
            review.request.old_object = old
        out.append(review)
    return out


# --- the two engines --------------------------------------------------------

def _clients(lib, area, name):
    tdoc, cdoc = _docs(lib, area, name)
    cel = CELDriver()
    tpu = TpuDriver(batch_bucket=16, cel_driver=cel)
    device = Client(target=K8sValidationTarget(), drivers=[tpu, cel])
    plain = Client(target=K8sValidationTarget(),
                   drivers=[RegoDriver(), CELDriver()])
    cons = []
    for client in (device, plain):
        client.add_template(copy.deepcopy(tdoc))
        cons = [client.add_constraint(copy.deepcopy(d))
                for d in _constraint_docs(cdoc, name)]
    return tpu, device, plain, cons


def _device_counts(tpu, cons, objects, reviews) -> tuple:
    """(verdicts, results, certified, mask), each [C, n], as the exact
    lane's sweep computes them for one kind."""
    kind = cons[0].kind
    prog = tpu._programs[kind]
    n = len(objects)
    pad_n = 16
    while pad_n < n:
        pad_n *= 2
    docs = [{"kind": r.request.kind, "operation": r.request.operation,
             "name": r.request.name, "namespace": r.request.namespace,
             "userInfo": r.request.user_info,
             "oldObject": r.request.old_object} for r in reviews]
    batch = Flattener(prog.program.schema, tpu.vocab).flatten(
        objects, pad_n=pad_n, reviews=docs)
    table = build_param_table(prog.program, cons, tpu.vocab)
    extra = tpu.inventory_cols(kind)[0]
    grid = prog.run(batch, table, vocab=tpu.vocab, extra_cols=extra)
    cols = slim_cols(pack_batch_cols(batch),
                     needed_fields(prog.program, counting=True))
    cols.update(vocab_tables(prog.program, tpu.vocab, counting=True))
    cols.update(extra)
    cnt, ok = jax.jit(prog._build_count())(table, cols)
    shape = (len(cons), batch.n)
    cnt, ok = np.asarray(as_grid(cnt, shape)), np.asarray(as_grid(ok, shape))
    mask = masks_mod.constraint_masks(
        cons, batch, tpu.vocab, objects, [r.namespace for r in reviews],
        [r.source for r in reviews])
    return (grid[:, :n] & mask[:, :n], cnt[:, :n], ok[:, :n], mask[:, :n])


@pytest.mark.parametrize("lib,area,name", CASES,
                         ids=[f"{c[0]}-{c[2]}" for c in CASES])
def test_device_count_is_the_engines_result_count(lib, area, name):
    tpu, device, plain, cons = _clients(lib, area, name)
    kind = cons[0].kind
    prog = tpu._programs.get(kind)
    if lib == "cel" or kind in COUNTED_FULL:
        assert prog is not None and prog.program.count is not None, kind
    else:
        assert kind in UNCOUNTED_FULL, kind
        assert prog is None or prog.program.count is None, kind
        return
    objects = _objects(_kinds(cons[0]), seed=len(name))
    for obj in filter(_syncable, objects):  # K8sUniqueIngressHost's
        for client in (device, plain):
            client.add_data(obj)
    assert tpu.inventory_exact(kind)
    reviews = _reviews(objects)
    hits, cnt, ok, mask = _device_counts(tpu, cons, objects, reviews)
    engine = plain._template_driver[kind]
    cfg = ReviewCfg(enforcement_point=AUDIT_EP)
    checked = 0
    for ci, con in enumerate(cons):
        for oi in np.nonzero(mask[ci])[0].tolist():
            want = len(engine.query(TARGET, [con], reviews[oi], cfg).results)
            assert bool(hits[ci, oi]) == (want > 0), (kind, objects[oi])
            if hits[ci, oi] and ok[ci, oi]:
                assert cnt[ci, oi] == want, (
                    f"{kind}/{con.name} counts {cnt[ci, oi]}, the engine "
                    f"returns {want} on {objects[oi]}")
                checked += 1
    assert checked, "no certified violating pair to hold the count to"


# --- the fold --------------------------------------------------------------

@pytest.fixture(scope="module")
def full_world():
    """library-full on the device, and a chunk of tricky Pods with many
    hits per counted row."""
    from benchmark import manifest, wiring

    conf = manifest.read_json(os.path.join(
        ROOT, "benchmark", "configs", "library-full.json"))
    cel = CELDriver()
    tpu = TpuDriver(batch_bucket=16, cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel])
    wiring.load_library(client, conf)
    rng = random.Random(41)
    objects = [_tricky_pod(rng, i) for i in range(160)]
    return tpu, client, objects


def _fold(tpu, swept, objects, limit, counted: bool) -> tuple:
    """(totals, kept, renders) of one chunk's exact fold; ``counted``
    False strips the rows' result marks, which is the render-every-hit
    fold."""
    target = K8sValidationTarget()
    renders = [0]

    def render(con, oi):
        renders[0] += 1
        review = target.handle_review(AugmentedUnstructured(
            object=objects[oi]))
        return tpu.render_query(TARGET, con, review).results

    if not counted:
        swept = {kind: tuple(entry) for kind, entry in swept.items()}
    totals, kept = {}, {}
    for con, total, kept_list in AuditManager.fold_swept(
            swept, len(objects), render, limit, True):
        totals[con.key()] = total
        kept[con.key()] = kept_list
    return totals, kept, renders[0]


@pytest.mark.parametrize("collect", ["reduced", "masks"])
def test_a_fold_that_renders_no_counted_hit_keeps_what_rendering_kept(
        full_world, collect):
    tpu, client, objects = full_world
    cons = [c for c in client.constraints()
            if "Pod" in _kinds(c) or not (c.match or {}).get("kinds")]
    ev = sharded.ShardedEvaluator(tpu, sharded.make_mesh(1),
                                  violations_limit=3, collect=collect)
    swept = ev.sweep(cons, objects, return_bits=True)
    marked = {kind for kind, entry in swept.items() if entry.results}
    assert marked and marked <= COUNTED_FULL
    assert {k for k in swept if tpu._programs[k].program.count is None}
    # a row a delimiter-holding name leaves uncertified reads -1 and
    # renders every hit; the others carry their result totals
    counted_rows = [int(c) for kind in marked for c in swept[kind][3]]
    assert -1 in counted_rows and any(c > 0 for c in counted_rows)
    got = _fold(tpu, swept, objects, 3, counted=True)
    want = _fold(tpu, swept, objects, 3, counted=False)
    assert got[0] == want[0]
    assert got[1] == want[1]
    assert sum(want[0].values()) > len(want[0]) * 3
    assert got[2] < want[2]

"""The ``library-cel`` templates (``benchmark/libraries/cel``, PR 34): each of
the 36 K8sNativeValidation blocks lowers onto the device IR, the device's
verdicts and messages are the CEL evaluator's on generated and on adversarial
objects, and on generated objects the violating objects are the Rego
sibling's (the engines may differ in message and in violations per object,
not in which objects violate)."""

from __future__ import annotations

import copy
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cluster, manifest  # noqa: E402
from benchmark.libraries import make_cel  # noqa: E402
from gatekeeper_tpu.apis.constraints import Constraint  # noqa: E402
from gatekeeper_tpu.apis.templates import ConstraintTemplate  # noqa: E402
from gatekeeper_tpu.drivers.cel_driver import CELDriver  # noqa: E402
from gatekeeper_tpu.drivers.rego_driver import RegoDriver  # noqa: E402
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.target.review import AugmentedUnstructured  # noqa: E402
from gatekeeper_tpu.target.target import K8sValidationTarget  # noqa: E402
from gatekeeper_tpu.utils.unstructured import load_yaml_file  # noqa: E402

TARGET = "admission.k8s.gatekeeper.sh"
CEL_LIB = os.path.join(ROOT, "benchmark", "libraries", "cel")
NAMES = sorted(make_cel.POLICIES)
PER_KIND = 320  # generated objects a constraint is held to, of its kinds

# one other parameter set per kind (None: the kind takes no parameters, so
# the other set is the sample's with nothing under ``parameters``)
OTHER = {
    "allowedrepos": {"repos": ["openpolicyagent/app1", "docker.io/"]},
    "allowprivilegeescalation": {"exemptImages": ["openpolicyagent/app1"]},
    "apparmor": {"allowedProfiles": ["runtime/default", "unconfined"]},
    "capabilities": {"allowedCapabilities": ["*"],
                     "requiredDropCapabilities": ["NET_RAW", "ALL"]},
    "containerlimits": {"cpu": "4", "memory": "512Mi"},
    "containerrequests": {"cpu": "100m", "memory": "64Mi"},
    "containerresources": {"exemptImages": ["openpolicyagent/app1*",
                                            "exempt.io/tool:v1"]},
    "disallowedrepos": {"repos": ["openpolicyagent/app2", "quay.io/"]},
    "disallowedtags": {"tags": ["latest", "v1", "v2"]},
    "disallowinteractivetty": {"exemptImages": ["openpolicyagent/app3*"]},
    "ephemeralstoragelimit": {"ephemeral-storage": "3Gi"},
    "externalip": {"allowedIPs": ["203.0.113.0", "198.51.100.7"]},
    "forbiddensysctls": {"forbiddenSysctls": ["net.*", "kernel.shm_rmid_forced"]},
    "hostfilesystem": {"allowedHostPaths": [{"pathPrefix": "/var"},
                                            {"pathPrefix": "/etc"}]},
    "hostnetworkingports": {"hostNetwork": True, "min": 1024, "max": 30000},
    "httpsonly": {"tlsOptional": True},
    "replicalimits": {"ranges": [{"min_replicas": 1, "max_replicas": 4},
                                 {"min_replicas": 10, "max_replicas": 100}]},
    "requiredannotations": {"annotations": [
        {"key": "a8r.io/owner", "allowedRegex": "^team-[0-3]$"},
        {"key": "a8r.io/runbook"}]},
    "requiredlabels": {"labels": [{"key": "gatekeeper"},
                                  {"key": "owner", "allowedRegex": ""}]},
    "requiredprobes": {"probes": ["livenessProbe"]},
    "flexvolumes": {"allowedFlexVolumes": [{"driver": "example/other"}]},
    "fsgroup": {"rule": "MustRunAs", "ranges": [{"min": 500, "max": 2000}]},
    "procmount": {"procMount": "Unmasked"},
    "seccomp": {"allowedProfiles": ["Unconfined", "RuntimeDefault"]},
    "selinux": {"allowedSELinuxOptions": [
        {"level": "s0:c123,c456", "role": "object_r", "type": "spc_t",
         "user": "system_u"}]},
    "users": {"runAsUser": {"rule": "MustRunAsNonRoot"}},
    "volumes": {"volumes": ["*"]},
}


def _config() -> dict:
    return manifest.read_json(os.path.join(
        ROOT, "benchmark", "configs", "library-full.json"))


_GENERATED: dict = {}


def _generated() -> dict:
    """{kind: objects} of the configuration's own generator at rehearsal
    size (4096 objects, one seed)."""
    if not _GENERATED:
        conf = manifest.apply_rehearsal(_config())
        n = int(conf["objects"])
        for obj in cluster.Cluster(conf["cluster"], n, seed=34).objects(0):
            _GENERATED.setdefault(obj["kind"], []).append(obj)
    return _GENERATED


def _docs(name: str) -> tuple:
    d = os.path.join(CEL_LIB, name)
    return (load_yaml_file(os.path.join(d, "template.yaml"))[0],
            load_yaml_file(os.path.join(d, "samples", "constraint.yaml"))[0])


def _constraints(name: str, sets: list) -> list:
    _tdoc, cdoc = _docs(name)
    out = []
    for i, params in enumerate(sets):
        doc = copy.deepcopy(cdoc)
        if params is not _SAMPLE:
            doc["metadata"]["name"] += f"-{i}"
            doc.setdefault("spec", {}).pop("parameters", None)
            if params is not None:
                doc["spec"]["parameters"] = copy.deepcopy(params)
        out.append(Constraint.from_unstructured(doc))
    return out


_SAMPLE = object()


def _tpu(name: str, cons: list) -> TpuDriver:
    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    tpu.add_template(ConstraintTemplate.from_unstructured(_docs(name)[0]))
    for con in cons:
        tpu.add_constraint(con)
    return tpu


def _rego(name: str, cons: list) -> RegoDriver:
    area = make_cel.POLICIES[name][0]
    rego = RegoDriver()
    rego.add_template(ConstraintTemplate.from_unstructured(load_yaml_file(
        os.path.join(ROOT, "library", area, name, "template.yaml"))[0]))
    for con in cons:
        rego.add_constraint(con)
    return rego


def _kinds(con) -> set:
    return {k for block in (con.match or {}).get("kinds") or []
            for k in block.get("kinds") or []}


def _reviews(objects: list) -> list:
    target = K8sValidationTarget()
    return [target.handle_review(AugmentedUnstructured(object=o))
            for o in objects]


def _key(r) -> tuple:
    return r.constraint["metadata"]["name"], r.msg


def _assert_device_is_evaluator(tpu, cons, objects):
    target = K8sValidationTarget()
    reviews = _reviews(objects)
    got = tpu.query_batch(TARGET, cons, reviews)
    for oi, review in enumerate(reviews):
        want = []
        for con in cons:
            if target.to_matcher(con.match).match(review):
                want.extend(tpu._cel.query(TARGET, [con], review).results)
        assert sorted(map(_key, got[oi].results)) == \
            sorted(map(_key, want)), (
                f"divergence on object {oi}: {objects[oi]}\n"
                f"got={sorted(map(_key, got[oi].results))}\n"
                f"want={sorted(map(_key, want))}")


def _sample_of(cons) -> list:
    kinds = set().union(*(_kinds(c) for c in cons))
    out = []
    for kind in sorted(kinds):
        out.extend(_generated().get(kind, [])[:PER_KIND])
    return out


# --- adversarial objects ----------------------------------------------------

def _maybe(rng, p: float) -> bool:
    return rng.random() < p


def _adv_container(rng, j: int) -> object:
    if _maybe(rng, 0.04):
        return rng.choice(["c", 5, None, ["x"]])
    c: dict = {}
    if _maybe(rng, 0.9):
        c["name"] = rng.choice([f"c{j}", f"c{j}", 3, None])
    if _maybe(rng, 0.88):
        c["image"] = rng.choice([
            "openpolicyagent/opa:0.9", "openpolicyagent/app1:latest",
            "openpolicyagent/app3", "exempt.io/tool:v1", "nginx",
            "k8s.gcr.io/pause@sha256:" + "a" * 64, "evilcorp.io/x:v1",
            "openpolicyagent/app2@sha256:" + "0" * 64, 7, True, None])
    if _maybe(rng, 0.75):
        r = rng.random()
        if r < 0.6:
            res: dict = {}
            for where in ("limits", "requests"):
                if _maybe(rng, 0.8):
                    q: dict = {}
                    for what in ("cpu", "memory", "ephemeral-storage"):
                        if _maybe(rng, 0.75):
                            q[what] = rng.choice([
                                "100m", "2", "512Mi", "2Gi", "4Gi", "1e3",
                                "banana", 512, None, "", "300m", "1Gi"])
                    res[where] = rng.choice([q, q, q, "x", None, [], ["cpu"]])
            c["resources"] = res
        else:
            c["resources"] = rng.choice([{}, "notadict", 5, None, []])
    if _maybe(rng, 0.7):
        sc: dict = {}
        for f in ("privileged", "allowPrivilegeEscalation",
                  "readOnlyRootFilesystem"):
            if _maybe(rng, 0.5):
                sc[f] = rng.choice([True, False, "yes", 1, None])
        if _maybe(rng, 0.4):
            caps: dict = {}
            for f in ("add", "drop"):
                if _maybe(rng, 0.7):
                    caps[f] = rng.choice([
                        ["NET_RAW"], ["ALL"], ["NET_BIND_SERVICE"],
                        ["SYS_ADMIN", "NET_RAW"], [], "NET_RAW", None,
                        [5, "NET_RAW"], {"NET_RAW": 1, "ALL": 2},
                        {"NET_BIND_SERVICE": True}, {}])
            sc["capabilities"] = rng.choice([caps, caps, caps, "x", None])
        if _maybe(rng, 0.3):
            sc["procMount"] = rng.choice(["Unmasked", "Default", 3, None])
        if _maybe(rng, 0.4):
            sc["runAsUser"] = rng.choice([0, 150, 1000, "150", None, 1.5,
                                          True, 100, 200])
        if _maybe(rng, 0.4):
            sc["seccompProfile"] = rng.choice([
                {"type": "RuntimeDefault"}, {"type": "Unconfined"}, {},
                {"type": 5}, "x", None, {"type": None}])
        if _maybe(rng, 0.3):
            opts = {"level": "s0:c123,c456", "role": "object_r",
                    "type": "svirt_sandbox_file_t", "user": "system_u"}
            if _maybe(rng, 0.5):
                opts.pop(rng.choice(sorted(opts)))
            if _maybe(rng, 0.3):
                opts["type"] = rng.choice(["spc_t", 5, None])
            sc["seLinuxOptions"] = rng.choice([opts, opts, {}, "x", None])
        c["securityContext"] = rng.choice([sc, sc, sc, sc, "bad", None, []])
    for probe in ("livenessProbe", "readinessProbe"):
        if _maybe(rng, 0.7):
            c[probe] = rng.choice([{"tcpSocket": {"port": 1}}, None, False])
    for f in ("tty", "stdin"):
        if _maybe(rng, 0.3):
            c[f] = rng.choice([True, False, "true", 1, None])
    if _maybe(rng, 0.5):
        ports = []
        for _ in range(rng.randint(0, 3)):
            p: object = {"containerPort": 80}
            if _maybe(rng, 0.7):
                p["hostPort"] = rng.choice([80, 8080, 9001, 20, "80", None,
                                            1.5, True, 30000, 1024])
            ports.append(rng.choice([p, p, p, "p", None, 8080]))
        c["ports"] = rng.choice([ports, ports, ports, "x", None,
                                 {"a": {"hostPort": 99999}}, {}])
    return c


def _adv_containers(rng) -> object:
    cs = [_adv_container(rng, j) for j in range(rng.randint(0, 3))]
    return rng.choice([cs, cs, cs, cs, cs, "x", None, {"a": {"name": "m"}},
                       {}])


def _adv_pod(rng, i: int) -> dict:
    spec: dict = {}
    if _maybe(rng, 0.92):
        spec["containers"] = _adv_containers(rng)
    if _maybe(rng, 0.3):
        spec["initContainers"] = _adv_containers(rng)
    if _maybe(rng, 0.2):
        spec["ephemeralContainers"] = _adv_containers(rng)
    if _maybe(rng, 0.6):
        psc: dict = {}
        if _maybe(rng, 0.5):
            psc["runAsUser"] = rng.choice([0, 150, 1000, "x", None, 100])
        if _maybe(rng, 0.5):
            psc["seccompProfile"] = rng.choice([
                {"type": "RuntimeDefault"}, {"type": "Unconfined"}, {},
                "x", None])
        if _maybe(rng, 0.5):
            psc["fsGroup"] = rng.choice([0, 1, 600, 1000, 2000, 5000, "1",
                                         None, True, 1.5])
        if _maybe(rng, 0.4):
            psc["seLinuxOptions"] = rng.choice([
                {"level": "s0:c123,c456", "role": "object_r",
                 "type": "svirt_sandbox_file_t", "user": "system_u"},
                {"level": "s0:c123,c456", "role": "object_r",
                 "type": "spc_t", "user": "system_u"},
                {"level": "s0"}, {}, "x", None])
        if _maybe(rng, 0.5):
            ctls = [rng.choice([
                {"name": "kernel.msgmax", "value": "1"},
                {"name": "net.core.somaxconn"}, {"name": "net.ipv4.x"},
                {"name": "kernel.shm_rmid_forced"}, {"name": 5}, {}, "x",
                None, {"name": "vm.swappiness"}])
                for _ in range(rng.randint(0, 3))]
            psc["sysctls"] = rng.choice([ctls, ctls, ctls, "x", None,
                                         {"a": {"name": "kernel.x"}}])
        spec["securityContext"] = rng.choice([psc, psc, psc, psc, "x", None])
    for f in ("hostNetwork", "hostPID", "hostIPC",
              "automountServiceAccountToken"):
        if _maybe(rng, 0.35):
            spec[f] = rng.choice([True, False, "true", 0, None])
    if _maybe(rng, 0.5):
        vols = []
        for k in range(rng.randint(0, 3)):
            v: object = {"name": f"v{k}"}
            kind = rng.choice(["emptyDir", "hostPath", "flexVolume",
                               "secret", "configMap", "nfs"])
            if kind == "hostPath":
                v[kind] = rng.choice([
                    {"path": "/var/log/x"}, {"path": "/etc"}, {"path": 5},
                    {}, "x", None, {"path": "/var"}])
            elif kind == "flexVolume":
                v[kind] = rng.choice([
                    {"driver": "example/lvm"}, {"driver": "example/other"},
                    {"driver": None}, {}, "x", None])
            else:
                v[kind] = rng.choice([{}, None, False])
            if _maybe(rng, 0.1):
                v.pop("name")
            vols.append(rng.choice([v, v, v, v, "vol", None, ["name"]]))
        spec["volumes"] = rng.choice([vols, vols, vols, vols, "x", None,
                                      {"a": {"name": "m", "nfs": {}}}, {}])
    meta: dict = {"name": rng.choice([f"p{i}", f"p{i}", f"p{i}", 5, None])}
    if _maybe(rng, 0.05):
        meta.pop("name")
    if _maybe(rng, 0.5):
        pre = "container.apparmor.security.beta.kubernetes.io/"
        ann = {}
        for _ in range(rng.randint(0, 3)):
            ann[rng.choice([pre + "c0", pre + "c1", "other/x", pre])] = \
                rng.choice(["runtime/default", "unconfined", "localhost/x",
                            5, None, True])
        meta["annotations"] = rng.choice([
            ann, ann, ann, ann, "x", None, ["runtime/default"],
            ["unconfined", 3], []])
    obj = {"apiVersion": "v1", "kind": "Pod", "metadata": meta}
    if _maybe(rng, 0.95):
        obj["spec"] = rng.choice([spec] * 12 + ["x", None])
    return obj


def _adv_meta(rng, name: str) -> dict:
    meta: dict = {"name": rng.choice([name, name, name, 5, None]),
                  "namespace": "ns-1"}
    for what, keys in (("labels", ["owner", "gatekeeper", "app"]),
                       ("annotations", ["a8r.io/owner", "a8r.io/runbook",
                                        "kubernetes.io/ingress.allow-http"])):
        if _maybe(rng, 0.75):
            m = {}
            for k in keys:
                if _maybe(rng, 0.6):
                    m[k] = rng.choice([
                        "team-1", "team-7", "usera.agilebank.demo", "false",
                        "true", "", 5, None, True, ["x"]])
            meta[what] = rng.choice([m, m, m, m, m, "x", None, [],
                                     ["owner", "a8r.io/owner"], {}])
    return meta


def _adv_other(rng, i: int, kind: str) -> dict:
    obj: dict = {"kind": kind, "metadata": _adv_meta(rng, f"o{i}")}
    if kind == "Service":
        obj["apiVersion"] = "v1"
        spec: dict = {}
        if _maybe(rng, 0.8):
            spec["type"] = rng.choice(["ClusterIP", "NodePort",
                                       "LoadBalancer", 5, None])
        if _maybe(rng, 0.6):
            ips = [rng.choice(["203.0.113.0", "198.51.100.7", "10.0.0.1", 5,
                               None]) for _ in range(rng.randint(0, 3))]
            spec["externalIPs"] = rng.choice([
                ips, ips, ips, "x", None, {"203.0.113.0": 1},
                {"10.0.0.1": 1, "203.0.113.0": 2}, {}])
        obj["spec"] = rng.choice([spec] * 10 + ["x", None])
    elif kind == "Ingress":
        obj["apiVersion"] = "networking.k8s.io/v1"
        spec = {}
        if _maybe(rng, 0.85):
            rules = [rng.choice([
                {"host": "a.example.com"}, {"host": "*.example.com"}, {},
                {"host": 5}, {"host": None}, "x", None])
                for _ in range(rng.randint(0, 3))]
            spec["rules"] = rng.choice([rules, rules, rules, rules, "x", None,
                                        {"a": {"host": "m.example.com"}}, {}])
        if _maybe(rng, 0.7):
            spec["tls"] = rng.choice([[{"hosts": ["a"]}], [], "x", None,
                                      {"a": 1}, {}, [None]])
        obj["spec"] = rng.choice([spec] * 10 + ["x", None])
    elif kind == "Deployment":
        obj["apiVersion"] = "apps/v1"
        spec = {}
        if _maybe(rng, 0.85):
            spec["replicas"] = rng.choice([0, 1, 3, 4, 5, 10, 50, 60, 100,
                                           101, "3", None, 2.5, True])
        obj["spec"] = rng.choice([spec] * 10 + ["x", None])
    elif kind == "Namespace":
        obj["apiVersion"] = "v1"
    elif kind in ("RoleBinding", "ClusterRoleBinding"):
        obj["apiVersion"] = "rbac.authorization.k8s.io/v1"
        if _maybe(rng, 0.9):
            subs = [rng.choice([
                {"kind": "User", "name": "system:anonymous"},
                {"kind": "Group", "name": "system:unauthenticated"},
                {"kind": "User", "name": "alice"}, {"kind": "User"},
                {"name": 5}, {"name": None}, "x", None])
                for _ in range(rng.randint(0, 3))]
            obj["subjects"] = rng.choice([
                subs, subs, subs, subs, "x", None,
                {"a": {"name": "system:anonymous"}}, {}])
    elif kind == "ClusterRole":
        obj["apiVersion"] = "rbac.authorization.k8s.io/v1"
        obj["metadata"] = rng.choice([
            {"name": "system:aggregate-to-edit"},
            {"name": "system:aggregate-to-edit"}, {"name": "view"}, {},
            {"name": 5}])
        if _maybe(rng, 0.9):
            rules = []
            for _ in range(rng.randint(0, 3)):
                r: object = {}
                if _maybe(rng, 0.85):
                    r["resources"] = rng.choice([
                        ["endpoints"], ["pods", "endpoints"], ["pods"], [],
                        "endpoints", None, {"endpoints": 1}, [5]])
                if _maybe(rng, 0.85):
                    r["verbs"] = rng.choice([
                        ["get"], ["create"], ["get", "patch"], ["update"], [],
                        "create", None, {"create": 1}, {"get": 1}, [7]])
                rules.append(rng.choice([r, r, r, r, "x", None]))
            obj["rules"] = rng.choice([rules, rules, rules, rules, "x", None,
                                       {}])
    return obj


class _Calm(random.Random):
    """Draws from the front of every list of options, where the well-formed
    values are: objects that sit near a policy's boundary and not past it."""

    def choice(self, seq):
        return super().choice(seq[:max(1, (len(seq) * 3 + 4) // 5)])


def _adversarial(kinds: set, n: int, seed: int) -> list:
    wild, calm = random.Random(seed), _Calm(seed)
    kinds = sorted(kinds)
    out = []
    for i in range(n):
        kind = kinds[i % len(kinds)]
        rng = calm if (i // len(kinds)) % 2 else wild
        out.append(_adv_pod(rng, i) if kind == "Pod"
                   else _adv_other(rng, i, kind))
    return out


# --- the cases --------------------------------------------------------------

def _sets(name: str) -> list:
    return [_SAMPLE, OTHER.get(name)]


@pytest.mark.parametrize("name", NAMES)
def test_the_template_lowers(name):
    tpu = _tpu(name, _constraints(name, [_SAMPLE]))
    assert tpu.fallback_kinds() == {}, tpu.fallback_kinds()
    assert len(tpu.lowered_kinds()) == 1


@pytest.mark.parametrize("name", NAMES)
def test_device_equals_evaluator_on_generated_objects(name):
    cons = _constraints(name, _sets(name))
    _assert_device_is_evaluator(_tpu(name, cons), cons, _sample_of(cons))


@pytest.mark.parametrize("name", NAMES)
def test_device_equals_evaluator_on_adversarial_objects(name):
    sets = _sets(name) + ([None] if OTHER.get(name) is not None else [])
    cons = _constraints(name, sets)
    kinds = set().union(*(_kinds(c) for c in cons))
    _assert_device_is_evaluator(
        _tpu(name, cons), cons,
        _adversarial(kinds, 360, seed=34 + NAMES.index(name)))


@pytest.mark.parametrize("name", NAMES)
def test_violating_objects_equal_the_rego_sibling(name):
    """On the generator's objects the CEL block and this repository's Rego
    template of the same kind name the same violating objects, for the
    sample constraint and for the other parameter set."""
    cons = _constraints(name, _sets(name))
    objects = _sample_of(cons)
    reviews = _reviews(objects)
    cel = CELDriver()
    cel.add_template(ConstraintTemplate.from_unstructured(_docs(name)[0]))
    rego = _rego(name, cons)
    target = K8sValidationTarget()
    hits = 0
    for con in cons:
        matcher = target.to_matcher(con.match)
        for oi, review in enumerate(reviews):
            if not matcher.match(review):
                continue
            by_cel = bool(cel.query(TARGET, [con], review).results)
            by_rego = bool(rego.query(TARGET, [con], review).results)
            assert by_cel == by_rego, (
                f"{con.name}: CEL {by_cel}, Rego {by_rego} on "
                f"{objects[oi]}")
            hits += by_cel
    # the comparison is of something: where the cluster draws the kind's
    # deviation, some object violates one of the two parameter sets
    if name not in _NO_GENERATED_VIOLATION:
        assert hits > 0


# kinds whose objects the cluster does not list, or whose deviation it does
# not draw: both engines agree that nothing violates
_NO_GENERATED_VIOLATION = {"blockendpointeditdefaultrole",
                           "blockloadbalancer"}


# --- what the PR touches beside the templates -------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_render_token_hits_and_misses(name):
    """The render memo's key for a CEL kind: the same template and
    Constraint hit; a changed parameter or a changed template miss."""
    from gatekeeper_tpu.audit.render_memo import RenderMemo

    tdoc, cdoc = _docs(name)
    con = Constraint.from_unstructured(cdoc)
    tpu = _tpu(name, [con])
    memo = RenderMemo()
    memo.begin_pass(1, 20)
    raw = b'{"kind": "Pod"}'
    token = tpu.render_token(con)
    assert token is not None
    memo.put((token, "", raw), ["kept"])
    again = tpu.render_token(con)
    assert again == token and hash(again) == hash(token)
    assert memo.get((again, "", raw)) == ["kept"]
    # a changed parameter arrives as a new Constraint of the same name
    changed = copy.deepcopy(cdoc)
    changed.setdefault("spec", {})["parameters"] = copy.deepcopy(
        OTHER.get(name) or {"unread": True})
    con2 = Constraint.from_unstructured(changed)
    tpu.add_constraint(con2)
    assert memo.get((tpu.render_token(con2), "", raw)) is None
    # a changed template compiles to another object
    edited = copy.deepcopy(tdoc)
    source = edited["spec"]["targets"][0]["code"][0]["source"]
    source["validations"][0]["message"] = "edited"
    tpu.add_template(ConstraintTemplate.from_unstructured(edited))
    assert tpu.fallback_kinds() == {}
    assert memo.get((tpu.render_token(con), "", raw)) is None
    assert memo.get((tpu.render_token(con2), "", raw)) is None


def _service_world():
    """Two CEL kinds of the library and one Rego kind over Services."""
    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    cons = []
    for name in ("blockloadbalancer", "externalip"):
        tdoc, cdoc = _docs(name)
        tpu.add_template(ConstraintTemplate.from_unstructured(tdoc))
        cons.append(Constraint.from_unstructured(cdoc))
    d = os.path.join(ROOT, "library", "general", "blocknodeport")
    tpu.add_template(ConstraintTemplate.from_unstructured(
        load_yaml_file(os.path.join(d, "template.yaml"))[0]))
    cons.append(Constraint.from_unstructured(load_yaml_file(
        os.path.join(d, "samples", "constraint.yaml"))[0]))
    for con in cons:
        tpu.add_constraint(con)
    assert tpu.fallback_kinds() == {}
    return tpu, cons


def test_sweep_rows_are_written_on_every_dispatch():
    from gatekeeper_tpu.observability import tracing
    from gatekeeper_tpu.parallel import sharded

    tpu, cons = _service_world()
    services = _generated()["Service"][:64]
    ev = sharded.ShardedEvaluator(tpu, sharded.make_mesh(1),
                                  violations_limit=20, collect="reduced")
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        ev.sweep(cons, services, return_bits=True)
    n = ev.dispatch_count
    assert n >= 1
    assert ev.perf["sweep_rows"] == 3 * n
    assert ev.perf["sweep_rows_cel"] == 2 * n
    spans = [s for t in tracer.traces() for s in t["spans"]
             if "sweep_rows" in s["attributes"]]
    assert len(spans) == n
    assert all(s["attributes"]["sweep_rows"] == 3
               and s["attributes"]["sweep_rows_cel"] == 2 for s in spans)
    # a dispatch with no CEL row writes its 0
    ev.perf_reset()
    ev.sweep([c for c in cons if c.kind == "K8sBlockNodePort"], services,
             return_bits=True)
    assert ev.perf["sweep_rows"] >= 1
    assert ev.perf["sweep_rows_cel"] == 0


_FALLS_BACK = {
    "apiVersion": "templates.gatekeeper.sh/v1", "kind": "ConstraintTemplate",
    "metadata": {"name": "k8sceloldobject"},
    "spec": {"crd": {"spec": {"names": {"kind": "K8sCelOldObject"}}},
             "targets": [{"target": TARGET, "code": [{
                 "engine": "K8sNativeValidation",
                 "source": {"validations": [{
                     "expression": "oldObject == null",
                     "message": "no"}]}}]}]},
}


def test_the_driver_lower_span():
    from gatekeeper_tpu.observability import tracing

    tracer = tracing.Tracer(seed=0)
    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    with tracing.activate(tracer):
        tpu.add_template(ConstraintTemplate.from_unstructured(
            _docs("capabilities")[0]))
        tpu.add_template(ConstraintTemplate.from_unstructured(_FALLS_BACK))
        tpu.add_template(ConstraintTemplate.from_unstructured(
            load_yaml_file(os.path.join(ROOT, "library", "general",
                                        "blocknodeport",
                                        "template.yaml"))[0]))
    spans = {s["attributes"]["kind"]: s["attributes"]
             for t in tracer.traces() for s in t["spans"]
             if s["name"] == "driver.lower"}
    assert spans["K8sPSPCapabilities"] == {
        "kind": "K8sPSPCapabilities", "engine": "cel", "lowered": True,
        "cached": False}
    assert spans["K8sBlockNodePort"]["engine"] == "rego"
    assert spans["K8sBlockNodePort"]["lowered"] is True
    fell = spans["K8sCelOldObject"]
    assert fell["engine"] == "cel" and fell["lowered"] is False
    assert fell["error"] == tpu.fallback_kinds()["K8sCelOldObject"]
    assert "oldObject" in fell["error"]


def test_request_operation_lowers_and_reads_the_review():
    """``(has(request.operation) && request.operation == "UPDATE") || ...``,
    the validation of a block whose Rego skips updates: "" in an audit
    review, the request's own at admission."""
    from gatekeeper_tpu.target.review import AdmissionRequest, GkReview

    tdoc = copy.deepcopy(_docs("readonlyrootfilesystem")[0])
    tdoc["metadata"]["name"] = "k8scelskipsupdates"
    tdoc["spec"]["crd"]["spec"]["names"]["kind"] = "K8sCelSkipsUpdates"
    source = tdoc["spec"]["targets"][0]["code"][0]["source"]
    source["validations"][0]["expression"] = (
        '(has(request.operation) && request.operation == "UPDATE") || '
        "size(variables.badContainers) == 0")
    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    tpu.add_template(ConstraintTemplate.from_unstructured(tdoc))
    assert tpu.fallback_kinds() == {}
    con = Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sCelSkipsUpdates", "metadata": {"name": "skips"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Pod"]}]}}})
    tpu.add_constraint(con)
    pods = _generated()["Pod"][:48]
    _assert_device_is_evaluator(tpu, [con], pods)
    reviews = []
    for i, pod in enumerate(pods):
        op = ("CREATE", "UPDATE")[i % 2]
        reviews.append(GkReview(request=AdmissionRequest(
            uid=str(i), kind={"group": "", "version": "v1", "kind": "Pod"},
            name=pod["metadata"]["name"],
            namespace=pod["metadata"]["namespace"], operation=op,
            object=pod, old_object=pod if op == "UPDATE" else None)))
    got = tpu.query_batch(TARGET, [con], reviews)
    for i, review in enumerate(reviews):
        want = tpu._cel.query(TARGET, [con], review).results
        assert sorted(map(_key, got[i].results)) == sorted(map(_key, want))
        if i % 2:
            assert not got[i].results  # an update is skipped
    assert any(got[i].results for i in range(0, len(pods), 2))

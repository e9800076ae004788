"""The ``library-c500`` library: the 46 templates of ``library/general`` and
``library/pod-security-policy``, each a directory under
``benchmark/libraries/c500/`` with a copy of its ``template.yaml`` and a
``samples/constraint.yaml`` whose every document is a constraint.

    python3 benchmark/libraries/make_c500.py [--out DIR] [--seed N]

writes the committed files anew (``tests/benchmark`` holds them to this
script, byte for byte).  The set is 500 constraints:

- 46 *baseline* ones: each template's own sample constraint, cluster-wide,
  with ``excludedNamespaces`` added;
- 454 *tenant* ones over the templates whose kind the cluster mix holds
  (42 of the 46), spread evenly: 50 tenants, tenant ``t`` owning ``ns-t``,
  ``ns-(t+50)``, ``ns-(t+100)`` and ``ns-(t+150)``.  Three in four list the
  tenant's namespaces under ``match.namespaces``; every fourth names the
  tenant's prefix as one glob (``ns-<t>*``).  Each keeps the sample's
  ``kinds`` and ``enforcementAction`` and draws its parameters from the
  template's ``VARIANTS`` below: the sample's own, or values near them that
  the compliant objects of ``benchmark/cluster.py`` satisfy too.

``website/docs/howto.md`` ("The match field") is where ``namespaces`` with
prefix globs and ``excludedNamespaces`` come from; the tenants, the globs
and the variants are this file's, and the configuration lists them under
``assumed``.
"""

from __future__ import annotations

import argparse
import copy
import os
import random
import shutil

import yaml

HERE = os.path.dirname(os.path.abspath(__file__))
LIBRARY = os.path.join(os.path.dirname(os.path.dirname(HERE)), "library")
AREAS = ("general", "pod-security-policy")
SEED = 500
CONSTRAINTS = 500
TENANTS = 50
NAMESPACES = 200
EXCLUDED = ["kube-system", "gatekeeper-system", "ns-19*"]
# kinds benchmark/cluster.py makes; a sample constraint whose kinds name
# none of them (ClusterRole, HorizontalPodAutoscaler, PodDisruptionBudget,
# PersistentVolumeClaim) stays a baseline constraint only
CLUSTER_KINDS = {"Pod", "Service", "Ingress", "Deployment", "Namespace",
                 "RoleBinding", "ClusterRoleBinding"}

_SELINUX = {"level": "s0:c123,c456", "role": "object_r", "user": "system_u"}
_VOLUMES = ["configMap", "emptyDir", "projected", "secret", "downwardAPI",
            "persistentVolumeClaim"]
_KVS = [{"kind": "Ingress", "apiVersion": "extensions/v1beta1",
         "targetVersion": "networking.k8s.io/v1"},
        {"kind": "PodSecurityPolicy", "apiVersion": "policy/v1beta1",
         "targetVersion": "(removed)"}]
_OWNER = {"key": "owner", "allowedRegex": "^[a-zA-Z]+.agilebank.demo$"}

# template -> the ``parameters`` a tenant constraint draws from, beside the
# sample's own (which is always the first choice).  Every value keeps the
# objects the generator means to be compliant compliant: limits at or above
# its (request, limit) pairs, ranges around its ranges, lists that hold the
# sample's entries.
VARIANTS = {
    "allowedrepos": [
        {"repos": ["openpolicyagent/", "registry.internal.example/"]},
        {"repos": ["openpolicyagent/", "docker.io/rando/"]}],
    "capabilities": [
        {"allowedCapabilities": ["NET_BIND_SERVICE", "CHOWN"],
         "requiredDropCapabilities": ["NET_RAW"]},
        {"allowedCapabilities": ["NET_BIND_SERVICE", "KILL", "AUDIT_WRITE"],
         "requiredDropCapabilities": ["NET_RAW"]}],
    "containerlimits": [{"cpu": "500m", "memory": "1Gi"},
                        {"cpu": "200m", "memory": "2Gi"},
                        {"cpu": "1", "memory": "2Gi"}],
    "containerlimitscel": [{"memory": "2Gi"}, {"memory": "4Gi"}],
    "containerrequests": [{"cpu": "500m", "memory": "1Gi"},
                          {"cpu": "200m", "memory": "2Gi"},
                          {"cpu": "1", "memory": "2Gi"}],
    "containerresourceratios": [{"ratio": 3}, {"ratio": 4}],
    "containerresources": [
        {"exemptImages": ["exempt.io/*", "debug.example/*"]}],
    "disallowedrepos": [{"repos": ["k8s.gcr.io/"]},
                        {"repos": ["evilcorp.io/", "quay.io/other/"]}],
    "disallowedtags": [{"tags": ["latest", "dev"]},
                       {"tags": ["latest", "v1"]}],
    "disallowinteractivetty": [{"exemptImages": ["debug.example/*"]}],
    "ephemeralstoragelimit": [{"ephemeral-storage": "1Gi"},
                              {"ephemeral-storage": "2Gi"}],
    "externalip": [
        {"allowedIPs": ["203.0.113.0", "203.0.113.1", "203.0.113.2"]},
        {"allowedIPs": ["203.0.113.0", "198.51.100.7"]}],
    "forbiddensysctls": [{"forbiddenSysctls": ["kernel.*"]},
                         {"forbiddenSysctls": ["net.core.somaxconn"]}],
    "hostfilesystem": [
        {"allowedHostPaths": [{"pathPrefix": "/var/log"},
                              {"pathPrefix": "/etc"}]},
        {"allowedHostPaths": [{"pathPrefix": "/var"}]}],
    "hostnetworkingports": [{"hostNetwork": False, "min": 80, "max": 20000},
                            {"hostNetwork": True, "min": 80, "max": 9000},
                            {"hostNetwork": False, "min": 1, "max": 9000}],
    "httpsonly": [{"tlsOptional": False}],
    "noprivileged": [{"exemptImages": ["exempt.io/*"]}],
    "noupdateserviceaccount": [{"allowedUsers": [
        "system:serviceaccount:kube-system:replicaset-controller",
        "system:serviceaccount:kube-system:deployment-controller"]}],
    "replicalimits": [{"ranges": [{"min_replicas": 3, "max_replicas": 100}]},
                      {"ranges": [{"min_replicas": 1, "max_replicas": 50}]},
                      {"ranges": [{"min_replicas": 2, "max_replicas": 64}]}],
    "requiredannotations": [
        {"annotations": [{"key": "a8r.io/owner",
                          "allowedRegex": "^team-[0-9]+$"}]},
        {"message": "Services of this tenant name their owning team",
         "annotations": [{"key": "a8r.io/owner", "allowedRegex": ".+"}]}],
    "requiredlabels": [
        {"message": "Namespaces of this tenant carry an `owner` label",
         "labels": [_OWNER]},
        {"message": "All namespaces must have an `owner` label that points "
                    "to your company username",
         "labels": [{"key": "owner",
                     "allowedRegex": "^[a-z]+.agilebank.demo$"}]}],
    "requiredprobes": [{"probes": ["readinessProbe"]},
                       {"probes": ["livenessProbe"]}],
    "verifydeprecatedapi": [{"kvs": _KVS + [
        {"kind": "Deployment", "apiVersion": "extensions/v1beta1",
         "targetVersion": "apps/v1"}]}],
    "allowprivilegeescalation": [{"exemptImages": ["exempt.io/*"]}],
    "apparmor": [{"allowedProfiles": ["runtime/default",
                                      "localhost/tenant"]}],
    "flexvolumes": [
        {"allowedFlexVolumes": [{"driver": "example/lvm"}]},
        {"allowedFlexVolumes": [{"driver": "example/lvm"},
                                {"driver": "example/cifs"},
                                {"driver": "example/other"}]}],
    "fsgroup": [{"rule": "MayRunAs", "ranges": [{"min": 1, "max": 2000}]},
                {"rule": "MayRunAs", "ranges": [{"min": 1, "max": 65535}]}],
    "procmount": [{"procMount": "Unmasked"}],
    "seccomp": [{"allowedProfiles": ["RuntimeDefault", "Localhost"]}],
    "selinux": [{"allowedSELinuxOptions": [
        dict(_SELINUX, type="svirt_sandbox_file_t"),
        dict(_SELINUX, type="spc_t")]}],
    "users": [
        {"runAsUser": {"rule": "MustRunAs",
                       "ranges": [{"min": 100, "max": 1000}]}},
        {"runAsUser": {"rule": "MustRunAsNonRoot"}}],
    "volumes": [{"volumes": _VOLUMES + ["hostPath"]},
                {"volumes": _VOLUMES + ["hostPath", "flexVolume"]}],
}


def templates() -> list:
    """[(directory name, path)] of the 46, in the order library-full loads
    them."""
    return [(name, os.path.join(LIBRARY, area, name))
            for area in AREAS
            for name in sorted(os.listdir(os.path.join(LIBRARY, area)))
            if os.path.exists(os.path.join(LIBRARY, area, name,
                                           "template.yaml"))]


def sample_of(path: str) -> dict:
    with open(os.path.join(path, "samples", "constraint.yaml")) as f:
        docs = [d for d in yaml.safe_load_all(f) if d]
    if len(docs) != 1:
        raise ValueError(f"{path}: {len(docs)} sample constraints")
    return docs[0]


def in_cluster(sample: dict) -> bool:
    blocks = ((sample.get("spec") or {}).get("match") or {}).get("kinds")
    if not blocks:
        return True  # no kinds: every object
    return any("*" in (b.get("kinds") or ["*"])
               or CLUSTER_KINDS & set(b["kinds"]) for b in blocks)


def tenant_namespaces(t: int) -> list:
    return [f"ns-{t + TENANTS * j}" for j in range(NAMESPACES // TENANTS)]


def baseline(sample: dict) -> dict:
    doc = copy.deepcopy(sample)
    match = doc.setdefault("spec", {}).setdefault("match", {})
    match["excludedNamespaces"] = list(EXCLUDED)
    return doc


def tenant(sample: dict, name: str, t: int, glob: bool, rng) -> dict:
    doc = copy.deepcopy(sample)
    doc["metadata"] = {"name": f"t{t:02d}-{sample['metadata']['name']}"}
    spec = doc.setdefault("spec", {})
    spec.setdefault("match", {})["namespaces"] = (
        [f"ns-{t}*"] if glob else tenant_namespaces(t))
    choice = rng.randrange(1 + len(VARIANTS.get(name, ())))
    if choice:
        spec["parameters"] = copy.deepcopy(VARIANTS[name][choice - 1])
    return doc


def constraint_set(seed: int = SEED) -> dict:
    """{template directory name: [constraint documents]}."""
    rng = random.Random(f"c500:{seed}")
    all_templates = templates()
    samples = {name: sample_of(path) for name, path in all_templates}
    out = {name: [baseline(samples[name])] for name, _ in all_templates}
    scoped = [name for name, _ in all_templates if in_cluster(samples[name])]
    n_tenant = CONSTRAINTS - len(all_templates)
    each, more = divmod(n_tenant, len(scoped))
    one_more = set(rng.sample(scoped, more))
    j = 0
    for name in scoped:
        for _ in range(each + (name in one_more)):
            # tenants in turn; every fourth constraint is the glob form
            out[name].append(tenant(samples[name], name, j % TENANTS,
                                    j % 4 == 3, rng))
            j += 1
    return out


def write(out_dir: str, seed: int = SEED) -> int:
    docs = constraint_set(seed)
    n = 0
    for name, path in templates():
        d = os.path.join(out_dir, name)
        os.makedirs(os.path.join(d, "samples"), exist_ok=True)
        shutil.copyfile(os.path.join(path, "template.yaml"),
                        os.path.join(d, "template.yaml"))
        with open(os.path.join(d, "samples", "constraint.yaml"), "w") as f:
            yaml.safe_dump_all(docs[name], f, sort_keys=False,
                               default_flow_style=False)
        n += len(docs[name])
    return n


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "c500"))
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args()
    print(f"{write(args.out, args.seed)} constraints under {args.out}")

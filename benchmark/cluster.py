"""The cluster generator: Kubernetes objects from a configuration's
distributions and a seed.  Imports nothing but the standard library, so the
generation pool and the reference children stay JAX-free.

A copy of ``gatekeeper_tpu/utils/synthetic.py`` in shape (the same six
kinds, the same fields the shipped library's sample constraints read) with
its distributions moved into the configuration file: the kind mix, the
namespace skew (Zipf), the containers-per-Pod distribution with its tail,
the share of Pods with init containers and volumes, and every deviation
rate.  It also writes the fields the original never set (requests,
ephemeral-storage, runAsUser, seccomp, allowPrivilegeEscalation), so that a
Pod is mostly compliant instead of violating six constraints every time.

Objects are drawn per shard: shard ``s`` of a corpus uses its own
``random.Random(f"{seed}:{s}")``, so any shard can be made alone, in any
process, and a seed always gives the same corpus.

What the seed does NOT change is the cluster's vocabulary: which position
holds which kind (so object names, which carry the position, are the same
set), the image pool, and the whole first shard, in which every string of
low cardinality (namespaces, images, label values, container names) makes
its first appearance.  The program numbers strings in the order it first
sees them and bakes those numbers into its compiled sweep programs (column
dictionaries, elided constants: ``parallel/sharded.py:pack_transfer_cols``),
so a corpus with another vocabulary order is another set of executables and
finds nothing in the XLA cache: ~180 s of compile on the v5e for each new
seed (my chip run, PR 22).  With the vocabulary held, the seed still draws
every field of seven shards in eight, and set-up finds its programs.
"""

from __future__ import annotations

import bisect
import itertools
import json
import random

SHARD = 32_768  # objects per shard; one audit chunk
_LABELLED_KINDS = ("Pod", "Service", "Ingress", "Deployment")
# Kubernetes' rand.SafeEncodeString alphabet, in which pod-template-hash
# is written (apimachinery pkg/util/rand)
_SAFE_ALPHANUMS = "bcdfghjklmnpqrstvwxz2456789"

_REPO_OK = "openpolicyagent/"
_REPOS_BAD = ("docker.io/rando/", "quay.io/other/")
_REPOS_DISALLOWED = ("k8s.gcr.io/", "evilcorp.io/")
_SHARED_HOSTS = tuple(f"svc-{i}.example.com" for i in range(40))
_EXTERNAL_IPS_BAD = tuple(f"203.0.113.{i}" for i in range(1, 9))
_BAD_CAPS = ("NET_ADMIN", "SYS_TIME", "CHOWN", "KILL", "AUDIT_WRITE")
_SYSCTLS_OK = ("net.ipv4.tcp_syncookies", "net.ipv4.ip_local_port_range")
_SYSCTLS_BAD = ("kernel.shm_rmid_forced", "net.core.somaxconn")
# (request, limit) pairs inside the sample constraints' caps (cpu 200m,
# memory 1Gi) and inside containerresourceratios' ratio of 2
_CPU_OK = (("50m", "100m"), ("100m", "200m"), ("100m", "100m"))
_MEM_OK = (("128Mi", "256Mi"), ("256Mi", "512Mi"), ("512Mi", "1Gi"),
           ("256Mi", "256Mi"))
_EPHEMERAL_OK = ("100Mi", "250Mi", "500Mi")
_VOLUMES_OK = ({"emptyDir": {}}, {"configMap": {"name": "cfg"}},
               {"secret": {"secretName": "tls"}},
               {"persistentVolumeClaim": {"claimName": "data"}})


class _Extreme:
    """A stand-in for ``random.Random`` whose every draw is the lowest (or
    the highest): the Pod it yields has every optional field (or none), so
    the widest ragged columns of the corpus are in it whatever the seed."""

    def __init__(self, high: bool):
        self.high = high

    def random(self) -> float:
        return 1.0 - 2.0 ** -53 if self.high else 0.0

    def choice(self, seq):
        return seq[-1 if self.high else 0]

    def randrange(self, lo, hi=None):
        lo, hi = (0, lo) if hi is None else (lo, hi)
        return hi - 1 if self.high else lo


class Cluster:
    """One configuration's ``cluster`` section, ready to draw from."""

    def __init__(self, spec: dict, n: int, seed: int):
        self.spec = spec
        self.seed = seed
        self.n = int(n)
        self.dev = spec["deviations"]
        kinds = spec["kinds"]
        self._kinds = list(kinds)
        self._kind_cum = list(itertools.accumulate(kinds.values()))
        ns = spec["namespaces"]
        self.namespaces = [f"ns-{i}" for i in range(int(ns["count"]))]
        self._ns_cum = list(itertools.accumulate(
            1.0 / (r + 1) ** float(ns["zipf_s"])
            for r in range(len(self.namespaces))))
        self._listed = bool(ns.get("listed", False))
        self._ns_labels = {key: self._rule(f"namespaces.labels.{key}", rule)
                           for key, rule in ns.get("labels", {}).items()}
        if self._ns_labels and not self._listed:
            raise ValueError("cluster.namespaces.labels belong to the "
                             "cluster's own Namespace objects: it needs "
                             "cluster.namespaces.listed")
        self._labels = {
            kind: {key: self._rule(f"labels.{kind}.{key}", rule, drawn=True)
                   for key, rule in rules.items()}
            for kind, rules in spec.get("labels", {}).items()}
        unlabelled = set(self._labels) - set(_LABELLED_KINDS)
        if unlabelled:
            raise ValueError(f"cluster.labels names {sorted(unlabelled)}; "
                             f"it labels {_LABELLED_KINDS} only")
        pod = spec["pod"]
        self._cont_values = pod["containers"]["values"]
        self._cont_cum = list(itertools.accumulate(
            pod["containers"]["weights"]))
        self._init_share = float(pod["init_container_share"])
        self._vol_share = float(pod["volumes_share"])
        self._images = self._image_pool(random.Random("images"))
        templates = spec.get("pod_templates")
        if templates not in (None, "pod"):
            raise ValueError(f"cluster.pod_templates: {templates!r}; the "
                             "one value is \"pod\"")
        self._pod_templates = templates == "pod"
        self._makers = {"Pod": self._pod, "Service": self._service,
                        "Ingress": self._ingress,
                        "Deployment": self._deployment,
                        "ReplicaSet": self._replica_set,
                        "StatefulSet": self._stateful_set,
                        "Namespace": self._namespace,
                        "RoleBinding": self._binding,
                        "ClusterRoleBinding": self._cluster_binding}
        unknown = set(self._kinds) - set(self._makers)
        if unknown:
            raise ValueError(f"cluster.kinds names no generator: {unknown}")

    # --- draws -----------------------------------------------------------
    @staticmethod
    def _pick(rng, values, cum):
        return values[bisect.bisect_left(cum, rng.random() * cum[-1])]

    def namespace(self, rng) -> str:
        return self._pick(rng, self.namespaces, self._ns_cum)

    @classmethod
    def _rule(cls, where: str, rule: dict, drawn: bool = False):
        """A label rule of the configuration, checked, as a function of
        (stream, namespace index) that gives the value or None for no
        label.  ``{"cycle": c, "format": f}`` gives the namespace of index
        k the value ``f.format(k % c)``; ``{"values", "weights", "absent"}``
        is a weighted draw that leaves the label out with probability
        ``absent`` (0 if not given).  ``drawn``: only the second will do,
        an object has no index."""
        keys = set(rule)
        if keys == {"cycle", "format"} and not drawn \
                and int(rule["cycle"]) > 0:
            cycle, fmt = int(rule["cycle"]), str(rule["format"])
            return lambda rng, k: fmt.format(k % cycle)
        if {"values", "weights"} <= keys <= {"values", "weights", "absent"} \
                and len(rule["values"]) == len(rule["weights"]) > 0 \
                and min(rule["weights"]) > 0:
            absent, values = float(rule.get("absent", 0.0)), rule["values"]
            cum = list(itertools.accumulate(rule["weights"]))
            return lambda rng, k: (None if rng.random() < absent
                                   else cls._pick(rng, values, cum))
        raise ValueError(f"cluster.{where}: no label rule in {rule}")

    def _label(self, rng, kind: str, meta: dict) -> None:
        """The configuration's labels of ``kind``, drawn from the object's
        own stream into ``meta``; no draw where it names none."""
        for key, rule in self._labels.get(kind, {}).items():
            value = rule(rng, None)
            if value is not None:
                meta.setdefault("labels", {})[key] = value

    def _image_pool(self, rng) -> list:
        # a cluster runs a bounded set of images which Pods share, not one
        # digest per Pod; the pool also bounds the vocabulary
        d, pool = self.dev, []
        for i in range(480):
            r = rng.random()
            if r < d["image_repo_not_allowed"]:
                repo = rng.choice(_REPOS_BAD)
            elif r < d["image_repo_not_allowed"] + d["image_repo_disallowed"]:
                repo = rng.choice(_REPOS_DISALLOWED)
            else:
                repo = _REPO_OK
            name, r = f"{repo}app{i % 60}", rng.random()
            if r < d["image_tag_latest"]:
                pool.append(f"{name}:latest")
            elif r < d["image_tag_latest"] + d["image_no_digest"]:
                pool.append(f"{name}:v{rng.randrange(1, 9)}")
            else:
                digest = "".join(rng.choice("0123456789abcdef")
                                 for _ in range(64))
                pool.append(f"{name}@sha256:{digest}")
        return pool

    # --- kinds -------------------------------------------------------------
    def _container(self, rng, name: str) -> dict:
        d, rnd = self.dev, rng.random
        c: dict = {"name": name, "image": rng.choice(self._images)}
        limits: dict = {}
        requests: dict = {}
        if rnd() >= d["no_limits"]:
            cpu_r, cpu_l = rng.choice(_CPU_OK)
            mem_r, mem_l = rng.choice(_MEM_OK)
            limits = {"cpu": "2" if rnd() < d["cpu_limit_over"] else cpu_l,
                      "memory": ("4Gi" if rnd() < d["memory_limit_over"]
                                 else mem_l)}
            if rnd() >= d["no_requests"]:
                requests = {"cpu": cpu_r, "memory": mem_r}
            if rnd() >= d["no_ephemeral_limit"]:
                limits["ephemeral-storage"] = (
                    "2Gi" if rnd() < d["ephemeral_limit_over"]
                    else rng.choice(_EPHEMERAL_OK))
        if limits or requests:
            c["resources"] = {k: v for k, v in (("limits", limits),
                                                ("requests", requests)) if v}
        sc: dict = {}
        if rnd() >= d["privilege_escalation_open"]:
            sc["allowPrivilegeEscalation"] = False
        if rnd() < d["privileged"]:
            sc["privileged"] = True
        if rnd() >= d["root_fs_writable"]:
            sc["readOnlyRootFilesystem"] = True
        caps: dict = {}
        if rnd() >= d["caps_not_dropped"]:
            caps["drop"] = ["NET_RAW"]
        if rnd() < d["caps_added"]:
            caps["add"] = ([rng.choice(_BAD_CAPS)]
                           if rnd() < d["caps_added_bad"]
                           else ["NET_BIND_SERVICE"])
        if caps:
            sc["capabilities"] = caps
        if rnd() < d["proc_mount_unmasked"]:
            sc["procMount"] = "Unmasked"
        if sc:
            c["securityContext"] = sc
        if rnd() >= d["no_liveness_probe"]:
            c["livenessProbe"] = {"tcpSocket": {"port": 8080}}
        if rnd() >= d["no_readiness_probe"]:
            c["readinessProbe"] = {"httpGet": {"path": "/", "port": 8080}}
        if rnd() < d["tty"]:
            c["tty"] = True
        if rnd() < 0.3:
            port: dict = {"containerPort": 8080}
            if rnd() < d["host_port"]:
                # the sample allows hostPorts in [80, 9000]
                port["hostPort"] = (rng.randrange(9001, 65535)
                                    if rnd() < d["host_port_bad"]
                                    else rng.randrange(80, 9000))
            c["ports"] = [port]
        return c

    def _pod_spec(self, rng) -> dict:
        d, rnd = self.dev, rng.random
        n = (self._cont_values[-1] if isinstance(rng, _Extreme)
             else self._pick(rng, self._cont_values, self._cont_cum))
        spec: dict = {"containers": [self._container(rng, f"c{j}")
                                     for j in range(n)]}
        if rnd() < self._init_share:
            spec["initContainers"] = [self._container(rng, "init0")]
        psc: dict = {}
        if rnd() >= d["no_run_as_user"]:
            # the sample allows uids in [100, 200]
            psc["runAsUser"] = (rng.choice((0, 1000))
                                if rnd() < d["run_as_user_bad"]
                                else rng.randrange(100, 201))
        if rnd() >= d["no_seccomp"]:
            psc["seccompProfile"] = {
                "type": "Unconfined" if rnd() < d["seccomp_bad"]
                else "RuntimeDefault"}
        if rnd() < d["fs_group_set"]:
            # the sample allows fsGroups in [1, 1000]
            psc["fsGroup"] = (2000 if rnd() < d["fs_group_bad"]
                              else rng.randrange(1, 1001))
        if rnd() < d["selinux_set"]:
            psc["seLinuxOptions"] = {"level": "s0:c123,c456",
                                     "role": "object_r", "user": "system_u",
                                     "type": ("spc_t"
                                              if rnd() < d["selinux_bad"]
                                              else "svirt_sandbox_file_t")}
        if rnd() < d["sysctl_set"]:
            psc["sysctls"] = [{"name": rng.choice(
                _SYSCTLS_BAD if rnd() < d["sysctl_bad"] else _SYSCTLS_OK),
                "value": "1"}]
        if psc:
            spec["securityContext"] = psc
        if rnd() < d["host_network"]:
            spec["hostNetwork"] = True
        if rnd() < d["host_pid"]:
            spec["hostPID"] = True
        if rnd() < d["host_ipc"]:
            spec["hostIPC"] = True
        if rnd() >= d["token_automounted"]:
            spec["automountServiceAccountToken"] = False
        if rnd() < self._vol_share:
            vols = [{"name": "data", **rng.choice(_VOLUMES_OK)}]
            if rnd() < d["host_path_volume"]:
                # the sample allows the /var/log prefix only
                vols.append({"name": "host", "hostPath": {
                    "path": rng.choice(("/etc", "/dev"))
                    if rnd() < d["host_path_bad"] else "/var/log/app"}})
            if rnd() < d["flex_volume"]:
                vols.append({"name": "flex", "flexVolume": {
                    "driver": "example/other"
                    if rnd() < d["flex_volume_bad"] else "example/lvm"}})
            spec["volumes"] = vols
        return spec

    def _pod_meta(self, rng, meta: dict) -> dict:
        """A Pod's labels and annotations, drawn into ``meta`` (which
        already names the Pod, or is a pod template's)."""
        meta["labels"] = {"app": f"app{rng.randrange(50)}"}
        self._label(rng, "Pod", meta)
        if rng.random() < self.dev["apparmor_set"]:
            meta["annotations"] = {
                "container.apparmor.security.beta.kubernetes.io/c0":
                "unconfined" if rng.random() < self.dev["apparmor_bad"]
                else "runtime/default"}
        return meta

    def _pod(self, rng, i: int, ns: str) -> dict:
        meta = self._pod_meta(rng, {"name": f"pod-{i}", "namespace": ns})
        return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
                "spec": self._pod_spec(rng)}

    def _pod_template(self, rng, template_hash: str | None = None) -> dict:
        """``spec.template`` of a workload.  Under ``pod_templates: "pod"``
        a Pod's draw (its labels, annotations and spec), the labels with
        ``pod-template-hash`` where the workload is a ReplicaSet; otherwise
        the one container every Deployment had before the key existed."""
        if not self._pod_templates:
            return {"spec": {"containers": [self._container(rng, "c0")]}}
        meta = self._pod_meta(rng, {})
        if template_hash is not None:
            meta["labels"]["pod-template-hash"] = template_hash
        return {"metadata": meta, "spec": self._pod_spec(rng)}

    def _service(self, rng, i: int, ns: str) -> dict:
        d = self.dev
        spec: dict = {"ports": [{"port": 80}],
                      "type": ("NodePort" if rng.random() < d["node_port"]
                               else "ClusterIP")}
        if rng.random() < d["external_ip"]:
            # the sample allows 203.0.113.0 only
            spec["externalIPs"] = [
                rng.choice(_EXTERNAL_IPS_BAD)
                if rng.random() < d["external_ip_bad"] else "203.0.113.0"]
        meta: dict = {"name": f"svc-{i}", "namespace": ns}
        self._label(rng, "Service", meta)
        if rng.random() >= d["no_owner_annotation"]:
            meta["annotations"] = {"a8r.io/owner":
                                   f"team-{rng.randrange(8)}"}
        return {"apiVersion": "v1", "kind": "Service", "metadata": meta,
                "spec": spec}

    def _ingress(self, rng, i: int, ns: str) -> dict:
        d = self.dev
        # every Ingress has a host of its own; a few also route a host from
        # a shared pool (duplicates violate the referential
        # uniqueingresshost policy) or a wildcard
        hosts = [f"ing-{i}.example.com"]
        if rng.random() < d["ingress_shared_host"]:
            hosts.append(rng.choice(_SHARED_HOSTS))
        if rng.random() < d["ingress_wildcard"]:
            hosts.append("*.example.com")
        spec: dict = {"rules": [{"host": h} for h in hosts]}
        meta: dict = {"name": f"ing-{i}", "namespace": ns}
        self._label(rng, "Ingress", meta)
        if rng.random() >= d["ingress_http"]:
            spec["tls"] = [{"hosts": hosts}]
            meta["annotations"] = {
                "kubernetes.io/ingress.allow-http": "false"}
        return {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
                "metadata": meta, "spec": spec}

    def _replicas(self, rng) -> int:
        # the sample allows 3..50 replicas
        return (rng.choice((1, 60))
                if rng.random() < self.dev["replicas_out_of_range"]
                else rng.choice((3, 3, 5, 8, 12, 20)))

    def _deployment(self, rng, i: int, ns: str) -> dict:
        replicas = self._replicas(rng)
        meta: dict = {"name": f"dep-{i}", "namespace": ns}
        self._label(rng, "Deployment", meta)
        template = self._pod_template(rng)
        spec: dict = {"replicas": replicas}
        if self._pod_templates:
            spec["selector"] = {"matchLabels": {
                "app": template["metadata"]["labels"]["app"]}}
        spec["template"] = template
        return {"apiVersion": "apps/v1", "kind": "Deployment",
                "metadata": meta, "spec": spec}

    def _replica_set(self, rng, i: int, ns: str) -> dict:
        """The ReplicaSet a Deployment's controller runs, at the
        Deployment's replicas."""
        name = f"rs-{i}"
        replicas = self._replicas(rng)
        template = self._pod_template(rng, template_hash(name))
        meta: dict = {"name": name, "namespace": ns}
        spec: dict = {"replicas": replicas}
        if self._pod_templates:
            meta["labels"] = dict(template["metadata"]["labels"])
            spec["selector"] = {"matchLabels": {
                key: meta["labels"][key]
                for key in ("app", "pod-template-hash")}}
        spec["template"] = template
        return {"apiVersion": "apps/v1", "kind": "ReplicaSet",
                "metadata": meta, "spec": spec}

    def _stateful_set(self, rng, i: int, ns: str) -> dict:
        name = f"sts-{i}"
        replicas = self._replicas(rng)
        template = self._pod_template(rng)
        spec: dict = {"replicas": replicas, "serviceName": name}
        if self._pod_templates:
            spec["selector"] = {"matchLabels": {
                "app": template["metadata"]["labels"]["app"]}}
        spec["template"] = template
        return {"apiVersion": "apps/v1", "kind": "StatefulSet",
                "metadata": {"name": name, "namespace": ns}, "spec": spec}

    def _namespace(self, rng, i: int, _ns: str) -> dict:
        labels = {}
        if rng.random() >= self.dev["namespace_no_owner"]:
            # the sample wants owner to match ^[a-zA-Z]+.agilebank.demo$
            labels["owner"] = (f"user{chr(97 + rng.randrange(26))}"
                               ".agilebank.demo")
        if rng.random() < 0.8:
            labels["gatekeeper"] = "true"
        return {"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": f"ns-x{i}", "labels": labels}}

    def _own_namespace(self, k: int) -> dict:
        """The Namespace object of ``ns-k`` under ``namespaces.listed``:
        ``owner`` and ``gatekeeper`` as a filler draws them, then the
        configured labels, all from a stream keyed on the name, so that it
        is one object for every seed, in the corpus and in the lookup."""
        name = self.namespaces[k]
        rng = random.Random(f"namespace:{name}")
        obj = self._namespace(rng, 0, "")
        obj["metadata"]["name"] = name
        for key, rule in self._ns_labels.items():
            value = rule(rng, k)
            if value is not None:
                obj["metadata"]["labels"][key] = value
        return obj

    def _binding(self, rng, i: int, ns: str, kind="RoleBinding") -> dict:
        subject = {"kind": "User", "apiGroup": "rbac.authorization.k8s.io",
                   "name": ("system:anonymous"
                            if rng.random() < self.dev["anonymous_subject"]
                            else f"user-{rng.randrange(30)}")}
        obj = {"apiVersion": "rbac.authorization.k8s.io/v1", "kind": kind,
               "metadata": {"name": f"rb-{i}"}, "subjects": [subject],
               "roleRef": {"kind": "ClusterRole", "name": "view",
                           "apiGroup": "rbac.authorization.k8s.io"}}
        if kind == "RoleBinding":
            obj["metadata"]["namespace"] = ns
        return obj

    def _cluster_binding(self, rng, i: int, ns: str) -> dict:
        return self._binding(rng, i, ns, "ClusterRoleBinding")

    # --- corpora -----------------------------------------------------------
    def shards(self) -> int:
        return -(-self.n // SHARD)

    def objects(self, shard: int):
        """The objects of one shard of the cluster, in order.  Kinds come
        from a stream the seed does not touch; shard 0 is the same for
        every seed (see the module's docstring)."""
        kind_rng = random.Random(f"kinds:{shard}")
        rng = random.Random(f"{self.seed}:{shard}" if shard
                            else "vocabulary")
        # the first two Pods of the cluster are the widest there can be, and
        # so are the first two pod templates of each workload kind
        extremes = {kind: [] if shard else [_Extreme(False), _Extreme(True)]
                    for kind in (("Pod", "Deployment", "ReplicaSet",
                                  "StatefulSet")
                                 if self._pod_templates else ("Pod",))}
        # under namespaces.listed the shard's first Namespace objects are
        # the cluster's own; the draws after them stay the fillers ns-x<i>
        own = len(self.namespaces) if self._listed and not shard else 0
        listed = 0
        lo = shard * SHARD
        for i in range(lo, min(self.n, lo + SHARD)):
            kind = self._pick(kind_rng, self._kinds, self._kind_cum)
            widest = extremes.get(kind)
            draw = widest.pop() if widest else rng
            ns = self.namespace(rng)
            if kind == "Namespace" and listed < own:
                yield self._own_namespace(listed)
                listed += 1
            else:
                yield self._makers[kind](draw, i, ns)
        if listed < own:
            raise ValueError(
                f"cluster.namespaces.listed: shard 0 draws {listed} objects "
                f"of kind Namespace, the cluster has {own} namespaces")

    def stream(self):
        """Objects of the cluster's kind mix without end, every field drawn
        from the seed: what an admission stream creates and updates."""
        rng = random.Random(f"{self.seed}:stream")
        for i in itertools.count():
            ns = self.namespace(rng)
            kind = self._pick(rng, self._kinds, self._kind_cum)
            yield self._makers[kind](rng, i, ns)

    def namespace_objects(self) -> dict:
        """The Namespace object of every namespace the others live in, by
        name (what the webhook's namespace lookup serves): under
        ``namespaces.listed`` the very objects the corpus lists."""
        if self._listed:
            return {name: self._own_namespace(k)
                    for k, name in enumerate(self.namespaces)}
        rng = random.Random(f"{self.seed}:namespaces")
        out = {}
        for name in self.namespaces:
            obj = self._namespace(rng, 0, "")
            obj["metadata"]["name"] = name
            out[name] = obj
        return out

    def inventory(self, kind: str):
        """As many objects of ``kind`` as the cluster holds, from a stream
        of their own: the inventory an admission cell syncs without making
        the whole cluster."""
        share = self.spec["kinds"].get(kind, 0.0) / self._kind_cum[-1]
        rng = random.Random(f"{self.seed}:inventory:{kind}")
        for i in range(round(self.n * share)):
            yield self._makers[kind](rng, i, self.namespace(rng))


def template_hash(name: str) -> str:
    """A ``pod-template-hash`` as Kubernetes writes one (the safe encoding
    of a 32-bit FNV-1a's decimal digits), of the workload's name: the same
    for every seed, as the names are."""
    h = 0x811C9DC5
    for b in name.encode():
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return "".join(_SAFE_ALPHANUMS[int(d) % len(_SAFE_ALPHANUMS)]
                   for d in str(h).encode())


def dumps(obj) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode()


def write_shard(spec: dict, objects: int, seed: int, shard: int, path: str,
                referential: list, per_kind: dict) -> dict:
    """One shard as JSONL at ``path``, its ``referential`` kinds once more
    at ``path + '.inv'``, a stratified sample (the first ``per_kind``
    objects of each kind, with their corpus index) at ``path + '.sample'``
    and the counts by kind, which it returns, at ``path + '.counts'``."""
    cluster = Cluster(spec, objects, seed)
    counts: dict = {}
    lo = shard * SHARD
    with open(path, "wb") as f, open(path + ".inv", "wb") as inv, \
            open(path + ".sample", "wb") as sample:
        for j, obj in enumerate(cluster.objects(shard)):
            line = dumps(obj) + b"\n"
            f.write(line)
            kind = obj["kind"]
            if kind in referential:
                inv.write(line)
            seen = counts.get(kind, 0)
            if seen < per_kind.get(kind, 0):
                sample.write(b"%d\t" % (lo + j) + line)
            counts[kind] = seen + 1
    with open(path + ".counts", "w") as f:
        json.dump(counts, f)
    return counts


def admission_review(obj: dict, uid: str, operation: str) -> dict:
    """The AdmissionReview an apiserver would send for ``obj``.  An UPDATE
    carries the object with one label changed as ``oldObject``."""
    api = obj["apiVersion"]
    group, _, version = api.rpartition("/")
    meta = obj["metadata"]
    req = {"uid": uid, "operation": operation,
           "kind": {"group": group, "version": version,
                    "kind": obj["kind"]},
           "name": meta.get("name", ""),
           "namespace": meta.get("namespace", ""),
           "userInfo": {"username": "benchmark"}, "object": obj}
    if operation == "UPDATE":
        old = json.loads(json.dumps(obj))
        old["metadata"].setdefault("labels", {})["revision"] = "previous"
        req["oldObject"] = old
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": req}


if __name__ == "__main__":
    # python benchmark/cluster.py <job.json>: one shard, in a process of its
    # own (the parent makes all shards of a corpus at once)
    import sys

    with open(sys.argv[1]) as _f:
        write_shard(**json.load(_f))

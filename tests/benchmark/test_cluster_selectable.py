"""The generator's optional keys (PR 31): a cluster that lists its own
Namespace objects (``cluster.namespaces.listed``), labels them by rule
(``cluster.namespaces.labels``) and draws labels onto the namespaced kinds
(``cluster.labels``), so that a deployment whose constraints select by
``namespaceSelector`` and ``labelSelector`` can be added as files.

Three things are held here.  Without the keys the generator writes byte for
byte what it wrote before they existed (digests taken on the parent tree,
commit 76fc3a5).  With them, on the fixture configuration beside this file
(in no manifest), the cluster is whole and the labels follow their rules.
And the route from the corpus to the reference needs no further code: the
``.inv`` lines of ``referential_kinds`` put every Namespace into the
interpreter client's ``NamespaceCache``, and ``reference.audit_results``
then answers selector constraints as ``match.matches`` does when handed the
Namespace.  Nothing here times the system under test."""

from __future__ import annotations

import collections
import copy
import hashlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cluster, manifest, reference, wiring  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs", "c500-selectors-fixture.json")
ACCEPTED = ["library-full", "psp-pods", "library-c500"]
SEED = 2147483999


def config(name: str) -> dict:
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           name + ".json"))


def fixture_config() -> dict:
    return manifest.read_json(FIXTURE)


# --- without the keys: byte for byte the parent's ----------------------------

# sha256 of a shard's four files (``corpus_digest``) and of
# ``namespace_objects()``, from benchmark/cluster.py at commit 76fc3a5, the
# parent of PR 31, seed 2147483999: the rehearse size whole, and shard 0
# and the seeded shard 3 of the real size
PARENT = {
    "library-full": {
        "rehearse": "c1a708bac6e29cdfc836ed95552609a2"
                    "519911601c1e0a4bad02906be32ddc2c",
        "shard0": "c2148b168c6f0c02cabee538c9997d68"
                  "8b1cb678303b254d19d4b731528e985c",
        "shard3": "0e0bc864e23d1e728ed76a4048d8c0ca"
                  "c0822e1714919c725dfebb20ee597f07",
        "namespaces": "3ba3eb5393104733f605601d4ebd9f7e"
                      "1996a7abb33d7bec83f7d7b8964a8689"},
    "psp-pods": {
        "rehearse": "d6598f7ff4044b4fabdfdc3286c792c7"
                    "4c3442c2cd88b3f14199a9669003e72d",
        "shard0": "195dabfb7b7a8a30f75edb085cbd48db"
                  "df730faa6ade589fb0aa8357c4ef82a7",
        "shard3": "54e684c4e84f1c29f41066c87a927e30"
                  "4ab37b81d9cfb311f122763016dfa539",
        "namespaces": "3ba3eb5393104733f605601d4ebd9f7e"
                      "1996a7abb33d7bec83f7d7b8964a8689"},
    "library-c500": {
        "rehearse": "1a5394ca2853fa95443e3c43eb5b93f8"
                    "73ecebfb38283206b21568866251e462",
        "shard0": "f02eed73487c386a61dd0daedc100592"
                  "9eebbc407d8c8ec3f41c4be3990521b5",
        "shard3": "0e6d637a1804832227ee7ac3a9c3e435"
                  "5095da5f08227f9720bd9124d752d9f7",
        "namespaces": "350ebca36f084b33478eaa03662a8417"
                      "3af08598eb2d619d07a30ab3634e3b0b"},
}


def corpus_digest(cfg: dict, shard: int, per_kind: dict, tmp) -> str:
    """One shard as ``audit.make_corpus`` has it written: the JSONL, its
    ``.inv``, ``.sample`` and ``.counts``, hashed together."""
    path = os.path.join(str(tmp), "shard.jsonl")
    cluster.write_shard(cfg["cluster"], cfg["objects"], SEED, shard, path,
                        cfg["referential_kinds"], per_kind)
    h = hashlib.sha256()
    for ext in ("", ".inv", ".sample", ".counts"):
        with open(path + ext, "rb") as f:
            h.update(ext.encode() + b"\0" + f.read())
    return h.hexdigest()


@pytest.mark.parametrize("what", ["rehearse", "shard0", "shard3",
                                  "namespaces"])
@pytest.mark.parametrize("name", ACCEPTED)
def test_an_accepted_configuration_generates_what_the_parent_did(
        name, what, tmp_path):
    cfg = config(name)
    for key in ("listed", "labels"):
        assert key not in cfg["cluster"]["namespaces"]
    assert "labels" not in cfg["cluster"]
    if what == "namespaces":
        got = hashlib.sha256(json.dumps(
            cluster.Cluster(cfg["cluster"], cfg["objects"],
                            SEED).namespace_objects(),
            sort_keys=True).encode()).hexdigest()
    elif what == "rehearse":
        toy = manifest.apply_rehearsal(copy.deepcopy(cfg))
        got = corpus_digest(toy, 0, toy["reference_sample"], tmp_path)
    else:
        n_shards = -(-cfg["objects"] // cluster.SHARD)
        per_kind = {k: -(-v // n_shards)
                    for k, v in cfg["reference_sample"].items()}
        got = corpus_digest(cfg, int(what[-1]), per_kind, tmp_path)
    assert got == PARENT[name][what]


# --- with the keys: the fixture's corpus -----------------------------------

class Corpus:
    """The fixture's corpus (one shard at its size) as ``write_shard``
    leaves it."""

    def __init__(self, tmp, seed: int):
        self.cfg = fixture_config()
        self.path = os.path.join(str(tmp), f"corpus.{seed}.jsonl")
        self.cluster = cluster.Cluster(self.cfg["cluster"],
                                       self.cfg["objects"], seed)
        self.counts = cluster.write_shard(
            self.cfg["cluster"], self.cfg["objects"], seed, 0, self.path,
            self.cfg["referential_kinds"], self.cfg["reference_sample"])
        self.objects = self.lines("")

    def lines(self, ext: str) -> list:
        with open(self.path + ext, "rb") as f:
            return [json.loads(ln.partition(b"\t")[2] if ext == ".sample"
                               else ln) for ln in f]

    def of_kind(self, kind: str, ext: str = "") -> list:
        objs = self.lines(ext) if ext else self.objects
        return [o for o in objs if o["kind"] == kind]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    return Corpus(tmp_path_factory.mktemp("selectable"), 1)


def test_the_fixture_is_library_c500s_cluster_with_the_three_keys():
    fix, c500 = fixture_config()["cluster"], config("library-c500")["cluster"]
    for key in ("kinds", "pod", "deviations"):
        assert fix[key] == c500[key], key
    assert fix["namespaces"]["zipf_s"] == c500["namespaces"]["zipf_s"]
    assert fix["namespaces"]["listed"] is True
    assert set(fix["namespaces"]["labels"]) == {
        "tenant", "env", "policy.example.com/exempt"}
    assert set(fix["labels"]) == {"Pod", "Service", "Ingress", "Deployment"}
    assert fixture_config()["referential_kinds"] == ["Ingress", "Namespace"]
    # and it is in no manifest
    assert "c500-selectors-fixture" not in json.dumps(
        manifest.read_json(manifest.MANIFEST))


def test_every_namespace_in_use_has_exactly_one_namespace_object(corpus):
    count = corpus.cfg["cluster"]["namespaces"]["count"]
    names = [f"ns-{k}" for k in range(count)]
    in_use = {o["metadata"]["namespace"] for o in corpus.objects
              if "namespace" in o["metadata"]}
    assert in_use == set(names)
    listed = corpus.of_kind("Namespace")
    # the first `count` objects of the kind, in order, each once; every
    # later draw of the kind is a filler, the namespace of nothing
    assert [o["metadata"]["name"] for o in listed[:count]] == names
    assert len(listed) == corpus.counts["Namespace"] > count
    assert all(re.fullmatch(r"ns-x\d+", o["metadata"]["name"])
               for o in listed[count:])
    own = collections.Counter(o["metadata"]["name"] for o in listed)
    assert all(own[name] == 1 for name in in_use)


def test_the_lookup_serves_the_very_objects_the_corpus_lists(corpus):
    count = corpus.cfg["cluster"]["namespaces"]["count"]
    lookup = corpus.cluster.namespace_objects()
    assert list(lookup) == [f"ns-{k}" for k in range(count)]
    assert corpus.of_kind("Namespace")[:count] == list(lookup.values())


def test_the_namespaces_are_the_same_for_every_seed(corpus,
                                                    tmp_path_factory):
    other = Corpus(tmp_path_factory.mktemp("selectable2"), 2)
    # shard 0 is the vocabulary: every byte of it
    with open(corpus.path, "rb") as a, open(other.path, "rb") as b:
        assert a.read() == b.read()
    assert corpus.cluster.namespace_objects() == \
        other.cluster.namespace_objects()
    # a seeded shard lives in the listed namespaces and lists fillers only
    cfg = corpus.cfg
    seeded = [list(cluster.Cluster(cfg["cluster"], cluster.SHARD + 2048,
                                   seed).objects(1)) for seed in (1, 2)]
    assert seeded[0] != seeded[1]
    lookup = corpus.cluster.namespace_objects()
    for obj in seeded[0]:
        if obj["kind"] == "Namespace":
            assert obj["metadata"]["name"].startswith("ns-x")
        elif "namespace" in obj["metadata"]:
            assert obj["metadata"]["namespace"] in lookup


def test_with_namespace_referential_they_are_in_the_inventory_and_the_sample(
        corpus):
    count = corpus.cfg["cluster"]["namespaces"]["count"]
    listed = corpus.of_kind("Namespace")
    assert corpus.of_kind("Namespace", ".inv") == listed
    assert len(corpus.of_kind("Ingress", ".inv")) == corpus.counts["Ingress"]
    # the stratified sample's Namespace share begins with the cluster's own
    share = corpus.cfg["reference_sample"]["Namespace"]
    assert share > count
    assert corpus.of_kind("Namespace", ".sample") == listed[:share]


def test_namespace_labels_follow_their_rules(corpus):
    rules = corpus.cfg["cluster"]["namespaces"]["labels"]
    cycle = rules["tenant"]["cycle"]
    lookup = corpus.cluster.namespace_objects()
    exempt = 0
    for k, obj in enumerate(lookup.values()):
        labels = obj["metadata"]["labels"]
        assert labels["tenant"] == f"t{k % cycle}"
        assert labels["env"] in rules["env"]["values"]  # no absent share
        exempt += labels.get("policy.example.com/exempt") == "true"
        # what the library's own samples read is drawn as a filler's is
        assert set(labels) <= {"owner", "gatekeeper", *rules}
        if "owner" in labels:
            assert re.fullmatch(r"user[a-z]\.agilebank\.demo",
                                labels["owner"])
    # absent 0.9 over 40 names, from streams keyed on the names alone
    assert 0 < exempt < len(lookup) // 3
    assert len({o["metadata"]["labels"]["env"]
                for o in lookup.values()}) == 3
    # the tenancy a constraint can be written on: tenant t7 owns ns-7,
    # ns-17, ns-27, ns-37
    assert [n for n, o in lookup.items()
            if o["metadata"]["labels"]["tenant"] == "t7"] == [
                "ns-7", "ns-17", "ns-27", "ns-37"]


@pytest.mark.parametrize("kind", ["Pod", "Service", "Ingress",
                                  "Deployment"])
def test_object_labels_follow_their_rules(corpus, kind):
    rules = corpus.cfg["cluster"]["labels"][kind]
    objs = corpus.of_kind(kind)
    own = {"app"} if kind == "Pod" else set()
    for obj in objs:
        labels = obj["metadata"].get("labels", {})
        assert set(labels) <= own | set(rules)
        if kind == "Pod":
            assert re.fullmatch(r"app\d+", labels["app"])
    for key, rule in rules.items():
        seen = collections.Counter(
            o["metadata"].get("labels", {}).get(key) for o in objs)
        absent = seen.pop(None, 0) / len(objs)
        assert absent == pytest.approx(rule.get("absent", 0.0), abs=0.08)
        assert set(seen) == set(rule["values"])
        drawn, weight = sum(seen.values()), sum(rule["weights"])
        for value, w in zip(rule["values"], rule["weights"]):
            assert seen[value] / drawn == pytest.approx(w / weight,
                                                        abs=0.08)


def test_the_unlabelled_kinds_and_the_fillers_stay_as_they_were(corpus):
    for kind in ("RoleBinding", "ClusterRoleBinding"):
        assert all("labels" not in o["metadata"]
                   for o in corpus.of_kind(kind))
    count = corpus.cfg["cluster"]["namespaces"]["count"]
    for obj in corpus.of_kind("Namespace")[count:]:
        assert set(obj["metadata"]["labels"]) <= {"owner", "gatekeeper"}


def test_an_admission_stream_draws_the_labels_too(corpus):
    pods = [o for o, _ in zip(corpus.cluster.stream(), range(400))
            if o["kind"] == "Pod"]
    tiers = {o["metadata"]["labels"].get("tier") for o in pods}
    assert tiers == {None, "frontend", "backend", "batch"}


def test_a_first_shard_with_too_few_namespace_draws_is_an_error():
    spec = fixture_config()["cluster"]
    with pytest.raises(ValueError, match="shard 0 draws"):
        list(cluster.Cluster(spec, 100, 1).objects(0))
    # library-c500's 200 names do not fit its rehearse size either: about
    # 205 draws, which is why the fixture takes 40
    wide = copy.deepcopy(spec)
    wide["namespaces"]["count"] = 400
    with pytest.raises(ValueError, match="400 namespaces"):
        list(cluster.Cluster(wide, 4096, 1).objects(0))


@pytest.mark.parametrize("change,message", [
    (lambda s: s["namespaces"].pop("listed"), "needs cluster.namespaces"),
    (lambda s: s["namespaces"]["labels"].update(tenant={"cycle": 10}),
     "namespaces.labels.tenant: no label rule"),
    (lambda s: s["namespaces"]["labels"].update(
        tenant={"cycle": 0, "format": "t{}"}), "namespaces.labels.tenant"),
    (lambda s: s["labels"]["Pod"].update(
        tier={"values": ["a", "b"], "weights": [1]}),
     "labels.Pod.tier: no label rule"),
    (lambda s: s["labels"]["Pod"].update(
        tier={"values": ["a", "b"], "weights": [1, 0]}), "labels.Pod.tier"),
    # an object has no index: only the drawn rule labels one
    (lambda s: s["labels"]["Pod"].update(
        tier={"cycle": 3, "format": "t{}"}), "labels.Pod.tier"),
    (lambda s: s["labels"].update(RoleBinding={}), "RoleBinding"),
])
def test_a_section_the_generator_cannot_follow_is_refused(change, message):
    spec = copy.deepcopy(fixture_config()["cluster"])
    change(spec)
    with pytest.raises(ValueError, match=message):
        cluster.Cluster(spec, 4096, 1)


# --- the route to the reference ----------------------------------------------

SELECTORS = {
    "tenant-t7": {"namespaceSelector": {"matchLabels": {"tenant": "t7"}}},
    "not-exempt": {"namespaceSelector": {"matchExpressions": [
        {"key": "policy.example.com/exempt", "operator": "DoesNotExist"}]}},
    "backend": {"labelSelector": {"matchLabels": {"tier": "backend"}}},
}


def constraint(name: str, match: dict) -> dict:
    """K8sRequiredLabels on Pods, asking for a label no Pod has: a Pod
    violates it exactly where the constraint matches it."""
    return {"apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sRequiredLabels", "metadata": {"name": name},
            "spec": {"match": {"kinds": [{"apiGroups": [""],
                                          "kinds": ["Pod"]}], **match},
                     "parameters": {"labels": [{"key": "never-there"}]}}}


@pytest.fixture(scope="module")
def interpreter(corpus):
    client = wiring.interpreter_client(corpus.cfg)
    for name, match in SELECTORS.items():
        client.add_constraint(constraint(name, match))
    return client


def pod_lines(corpus, n: int = 600) -> list:
    return [b"%d\t" % i + cluster.dumps(pod)
            for i, pod in enumerate(corpus.of_kind("Pod")[:n])]


def test_the_reference_answers_selectors_once_the_inventory_is_synced(
        corpus, interpreter):
    from gatekeeper_tpu.match.match import (SOURCE_ORIGINAL, Matchable,
                                            MatchError, matches)

    lines = pod_lines(corpus)
    # before the sync the audit's review has no Namespace to select on
    with pytest.raises(MatchError, match="missing Namespace"):
        reference.audit_results(interpreter, lines[:1])
    # as reference.main feeds a child its inventory
    with open(corpus.path + ".inv", "rb") as f:
        for line in f:
            interpreter.add_data(json.loads(line))
    got = {idx: {name for _, name, _ in rows}
           for idx, rows in reference.audit_results(interpreter, lines)}
    lookup = corpus.cluster.namespace_objects()
    hits = collections.Counter()
    for idx, pod in enumerate(corpus.of_kind("Pod")[:len(lines)]):
        by_hand = Matchable(obj=pod, source=SOURCE_ORIGINAL,
                            namespace=lookup[pod["metadata"]["namespace"]])
        want = {name for name, match in SELECTORS.items()
                if matches(constraint(name, match)["spec"]["match"],
                           by_hand)}
        assert got[idx] == want, pod["metadata"]
        hits.update(want)
    # each selector takes some Pods and leaves some
    assert all(0 < hits[name] < len(lines) for name in SELECTORS), hits

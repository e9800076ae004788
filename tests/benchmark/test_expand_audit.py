"""``library-full-expand`` and its cell ``expand.audit-sweep``: the audit as
``python -m gatekeeper_tpu --audit-expand`` runs it, over a cluster whose
Deployments, ReplicaSets and StatefulSets are clusterloader2's load test's.
Held here: the configuration is ``library-full-exact``'s outside the keys
that make it this deployment; the generator's new keys write nothing new
into the six configurations that do not set them; the wiring installs the
block as the controllers do; the plain reference
(``benchmark/expansion_reference.py``) expands and mutates as upstream does,
with none of the program's code, and agrees with the program on every
generator of the rehearsal; ``correct`` holds totals and kept violations,
implied ones included, to it; the three readers read a recorded
observation; and the cell runs end to end at toy size, correct as the
program stands and not correct with the generator stage switched off, the
mutators left out of the program's side, half the generators' resultants
dropped, the template's metadata left out of a resultant, or the Assign's
walk of a list stopped at its first element.  Nothing here times the
system under test."""

from __future__ import annotations

import collections
import copy
import hashlib
import importlib.util
import inspect
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (audit, cluster, expansion_reference,  # noqa: E402
                       manifest, reference, wiring)

CELL = "expand.audit-sweep"
CONTROL = "exact.audit-sweep"
CONFIG = "library-full-expand"
SEED = 2147483999
NEW_METRICS = ("expansion.busy_s_per_pass", "expansion.resultants_per_pass",
               "mutlane.apply_s_per_pass")
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "expand_audit_spans.json")


def config(name: str) -> dict:
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           name + ".json"))


# --- the configuration: library-full-exact's outside its own keys -------------

def test_the_configuration_is_library_full_exacts_outside_the_listed_keys():
    exact, expand = config("library-full-exact"), config(CONFIG)
    kinds, base_kinds = expand["cluster"].pop("kinds"), exact["cluster"].pop(
        "kinds")
    # clusterloader2's 3,000 Pods, 326 Deployments, 326 ReplicaSets and 2
    # StatefulSets a namespace, in the share the control gives Pods and
    # Deployments; the kinds that create no Pods as the control has them
    workloads = {"Pod": 3000, "Deployment": 326, "ReplicaSet": 326,
                 "StatefulSet": 2}
    share = base_kinds.pop("Pod") + base_kinds.pop("Deployment")
    assert share == 0.75
    for kind, n in workloads.items():
        assert kinds.pop(kind) == round(share * n / 3654, 6), kind
    assert kinds == base_kinds
    assert expand["cluster"]["namespaces"].pop("listed") is True
    assert expand["cluster"].pop("pod_templates") == "pod"
    assert expand["audit"].pop("expand_generated") is True
    sample = expand.pop("reference_sample")
    assert (sample.pop("Deployment"), sample.pop("ReplicaSet"),
            sample.pop("StatefulSet")) == (256, 256, 16)
    base = exact.pop("reference_sample")
    del base["Deployment"]
    assert sample == base
    block = expand.pop("expansion")
    assert len(block["templates"]) == 1 and len(block["mutators"]) == 2
    assert expand.pop("objects") == exact.pop("objects") == 131072
    assert list(expand["reduced"]) == list(exact["reduced"]) == ["objects"]
    # the words that say which deployment this is, why it was cut, what it
    # assumes, what it guarantees and its toy size
    for key in ("name", "source", "source_detail", "reduced", "rehearse"):
        assert expand.pop(key) != exact.pop(key), key
    assumed, guarantees = expand.pop("assumed"), expand.pop("guarantees")
    assert set(exact.pop("assumed")) < set(assumed)
    for key in ("cluster.pod_templates", "cluster.namespaces.listed",
                "audit.expand_generated", "expansion", "reference_sample"):
        assert key in assumed, key
    assert "revisionHistoryLimit" in assumed["cluster.kinds"]
    assert "clusterloader2" in assumed["cluster.kinds"]
    assert "flag" in assumed["audit.expand_generated"]
    assert "as recalled" in assumed["expansion"]
    assert guarantees.pop("implied")
    assert guarantees == exact.pop("guarantees")
    assert expand == exact


def test_the_block_names_the_docs_template_and_two_assigns():
    system = wiring.expansion_system(config(CONFIG))
    [template] = system.templates()
    assert template.name == "expand-deployments"
    assert template.template_source == "spec.template"
    assert template.generated_gvk == {"kind": "Pod", "group": "",
                                      "version": "v1"}
    assert template.apply_to[0]["kinds"] == [
        "DaemonSet", "Deployment", "ReplicaSet", "StatefulSet"]
    mutators = system.mutation_system.active()
    assert sorted(type(m).__name__ for m in mutators) == [
        "AssignMutator", "AssignMutator"]
    assert sorted(m.id.name for m in mutators) == [
        "always-pull-image", "k8spspdefaultallowprivilegeescalation"]


def test_without_the_block_there_is_no_expansion_system():
    assert wiring.expansion_system(config("library-full-exact")) is None


def test_the_program_gets_the_systems_and_the_flag_as_main_passes_them():
    source = inspect.getsource(wiring.Program)
    assert "expansion_system=self.expansion" in source
    assert 'expand_generated=a.get("expand_generated", False)' in source
    for name in ("full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
                 "c500sel.audit-sweep", "cel.audit-sweep", CONTROL):
        cell = manifest.Cell(name)
        assert "expansion" not in cell.config
        assert "expand_generated" not in cell.config["audit"]


# --- the generator: nothing new without the keys ---------------------------------

# sha256 of each rehearsal shard as ``audit.make_corpus`` writes it (the JSONL,
# ``.inv``, ``.sample`` and ``.counts`` hashed together, as
# ``test_cluster_selectable.corpus_digest`` does), seed 2147483999, taken with
# benchmark/cluster.py as it stood before the keys ``pod_templates`` and the
# kind ``ReplicaSet`` existed
PARENT_REHEARSAL = {
    "library-full": "c1a708bac6e29cdfc836ed95552609a2"
                    "519911601c1e0a4bad02906be32ddc2c",
    "psp-pods": "d6598f7ff4044b4fabdfdc3286c792c7"
                "4c3442c2cd88b3f14199a9669003e72d",
    "library-c500": "1a5394ca2853fa95443e3c43eb5b93f8"
                    "73ecebfb38283206b21568866251e462",
    "library-c500sel": "a5045f79ff74c24c75cd0c64d3ab57fd"
                       "78e38b926f173b72a6a39436d29721ba",
    "library-cel": "c1a708bac6e29cdfc836ed95552609a2"
                   "519911601c1e0a4bad02906be32ddc2c",
    "library-full-exact": "c1a708bac6e29cdfc836ed95552609a2"
                          "519911601c1e0a4bad02906be32ddc2c",
}


def shard_digests(cfg: dict, tmp) -> list:
    n_shards = -(-cfg["objects"] // cluster.SHARD)
    per_kind = {k: -(-v // n_shards)
                for k, v in cfg["reference_sample"].items()}
    out = []
    for shard in range(n_shards):
        path = os.path.join(str(tmp), f"shard.{shard}.jsonl")
        cluster.write_shard(cfg["cluster"], cfg["objects"], SEED, shard,
                            path, cfg["referential_kinds"], per_kind)
        h = hashlib.sha256()
        for ext in ("", ".inv", ".sample", ".counts"):
            with open(path + ext, "rb") as f:
                h.update(ext.encode() + b"\0" + f.read())
        out.append(h.hexdigest())
    return out


@pytest.mark.parametrize("name", sorted(PARENT_REHEARSAL))
def test_a_configuration_without_the_keys_writes_the_parents_corpus(
        name, tmp_path):
    cfg = manifest.apply_rehearsal(config(name))
    assert "pod_templates" not in cfg["cluster"]
    assert "ReplicaSet" not in cfg["cluster"]["kinds"]
    assert shard_digests(cfg, tmp_path) == [PARENT_REHEARSAL[name]]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """The configuration's rehearsal corpus, its one shard, as objects."""
    cfg = manifest.apply_rehearsal(config(CONFIG))
    path = str(tmp_path_factory.mktemp("expand") / "shard.jsonl")
    cluster.write_shard(cfg["cluster"], cfg["objects"], SEED, 0, path,
                        cfg["referential_kinds"], cfg["reference_sample"])
    with open(path, "rb") as f:
        return [json.loads(line) for line in f]


def test_a_workloads_pod_template_is_drawn_as_a_pod_is(corpus):
    by_kind = collections.defaultdict(list)
    for obj in corpus:
        by_kind[obj["kind"]].append(obj)
    gens = (by_kind["Deployment"] + by_kind["ReplicaSet"]
            + by_kind["StatefulSet"])
    assert 0.11 < len(gens) / len(corpus) < 0.16
    assert 0.8 < len(by_kind["ReplicaSet"]) / len(by_kind["Deployment"]) < 1.25
    assert by_kind["StatefulSet"]
    containers = collections.Counter(
        len(g["spec"]["template"]["spec"]["containers"]) for g in gens)
    assert set(containers) == {1, 2, 3}
    fields = collections.Counter(
        key for g in gens for c in g["spec"]["template"]["spec"]["containers"]
        for key in c)
    assert fields["securityContext"] and fields["resources"]
    for g in gens:
        tmpl = g["spec"]["template"]
        labels = tmpl["metadata"]["labels"]
        assert labels["app"].startswith("app")
        assert g["spec"]["selector"]["matchLabels"]["app"] == labels["app"]
        if g["kind"] == "ReplicaSet":
            h = labels["pod-template-hash"]
            assert h == cluster.template_hash(g["metadata"]["name"])
            assert g["metadata"]["labels"] == labels
            assert g["spec"]["selector"]["matchLabels"] == {
                "app": labels["app"], "pod-template-hash": h}
        else:
            assert "pod-template-hash" not in labels
        if g["kind"] == "StatefulSet":
            assert g["spec"]["serviceName"] == g["metadata"]["name"]
    # every ReplicaSet is the one its Deployment runs, at its replicas
    assert all(rs["spec"]["replicas"] > 0 for rs in by_kind["ReplicaSet"])


def test_the_first_two_templates_of_each_kind_are_the_widest(corpus):
    for kind in ("Deployment", "ReplicaSet", "StatefulSet"):
        first = [o for o in corpus if o["kind"] == kind][:2]
        widths = [len(o["spec"]["template"]["spec"]["containers"])
                  for o in first]
        assert widths == [3, 3], kind
        # every field a compliant Pod sets on the first, every deviation
        # on the second, as the first two Pods of the cluster
        high, low = (o["spec"]["template"]["spec"] for o in first)
        assert high["automountServiceAccountToken"] is False
        assert all("livenessProbe" in c for c in high["containers"])
        assert len(low["volumes"]) == 3 and low["hostPID"] is True
        assert all(c["securityContext"]["privileged"] is True
                   for c in low["containers"])
        # and sets no container's allowPrivilegeEscalation, which the
        # configuration's second Assign then sets
        assert not any("allowPrivilegeEscalation" in c["securityContext"]
                       for c in low["containers"])


def test_a_template_hash_is_written_as_kubernetes_writes_one():
    h = cluster.template_hash("rs-7")
    assert h == cluster.template_hash("rs-7") != cluster.template_hash("rs-8")
    assert 1 <= len(h) <= 10
    assert set(h) <= set("bcdfghjklmnpqrstvwxz2456789")


def test_an_unknown_template_rule_is_refused():
    spec = copy.deepcopy(config(CONFIG)["cluster"])
    spec["pod_templates"] = "deployment"
    with pytest.raises(ValueError, match="pod_templates"):
        cluster.Cluster(spec, 10, 1)


# --- the plain reference expands a generator line --------------------------------

def expansion(mutators: bool = True):
    block = config(CONFIG)["expansion"]
    return expansion_reference.Expansion(
        block if mutators else dict(block, mutators=[]))


@pytest.fixture(scope="module")
def interpreter():
    return wiring.interpreter_client(config(CONFIG))


def deployment(escalation_set: bool) -> dict:
    c0 = {"name": "c0", "image": "openpolicyagent/app1:v1",
          "securityContext": {"readOnlyRootFilesystem": True}}
    if escalation_set:
        c0["securityContext"]["allowPrivilegeEscalation"] = False
    return {"apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "dep-9", "namespace": "ns-3"},
            "spec": {"replicas": 3, "template": {
                "metadata": {"labels": {"app": "app1"}},
                "spec": {"containers": [c0]}}}}


ESCALATION = "K8sPSPAllowPrivilegeEscalationContainer"


def test_the_reference_reviews_each_resultant_after_the_mutators(interpreter):
    [(ident, rows)] = reference.implied_results(
        interpreter, expansion(), deployment(False))
    assert ident == ["Pod", "ns-3", "dep-9-pod"]
    assert rows and all(msg.startswith("[Implied by expand-deployments] ")
                        and action == "warn" for _k, _n, msg, action in rows)
    # the Assign set allowPrivilegeEscalation false before the review
    assert ESCALATION not in {kind for kind, *_ in rows}
    # and without the mutators the same resultant violates it
    [(_, unmutated)] = reference.implied_results(
        interpreter, expansion(mutators=False), deployment(False))
    assert ESCALATION in {kind for kind, *_ in unmutated}


def test_only_a_generator_line_gets_what_it_implies(interpreter):
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "pod-1", "namespace": "ns-3"},
           "spec": {"containers": [{"name": "c0", "image": "x"}]}}
    lines = [b"7\t" + json.dumps(pod).encode(),
             b"8\t" + json.dumps(deployment(True)).encode()]
    out = reference.audit_results(interpreter, lines, expansion())
    assert [len(entry) for entry in out] == [2, 3]
    assert reference.audit_results(interpreter, lines) == [e[:2] for e in out]
    implied = reference.implied_by_index([out])
    assert list(implied) == [8]
    [(rid, per)] = implied[8]
    assert rid == ("Pod", "ns-3", "dep-9-pod")
    assert all(action == "warn" for msgs in per.values()
               for _msg, action in msgs)
    # a generator line's own results are read as any line's
    assert set(reference.by_index([out])) == {7, 8}


def test_the_reference_shares_no_code_with_the_program():
    with open(expansion_reference.__file__) as f:
        assert "gatekeeper_tpu" not in f.read()
    imports = sorted(
        line.split()[1] for line in
        inspect.getsource(reference.implied_results).splitlines()
        if line.strip().startswith(("from ", "import ")))
    # the interpreter's review, as for a listed line, and nothing else
    assert imports == ["benchmark.expansion_reference",
                       "gatekeeper_tpu.apis.constraints",
                       "gatekeeper_tpu.match.match",
                       "gatekeeper_tpu.target.review"]


def test_the_reference_and_the_program_imply_the_same_pods(corpus):
    """Every generator of the rehearsal, expanded and mutated by the
    reference and by the program's host systems, gives the same objects."""
    cfg = manifest.apply_rehearsal(config(CONFIG))
    system = wiring.expansion_system(cfg)
    spaces = cluster.Cluster(cfg["cluster"], cfg["objects"],
                             SEED).namespace_objects()
    ref = expansion()
    gens = [o for o in corpus if ref.generates(o)]
    assert len(gens) > 500
    for obj in gens:
        got = system.expand(copy.deepcopy(obj),
                            namespace=spaces[obj["metadata"]["namespace"]])
        want = ref.expand(obj)
        assert [(r.template_name, r.enforcement_action, r.obj) for r in got] \
            == [(t.name, t.action, res) for t, res in want]


POD = {"apiVersion": "v1", "kind": "Pod",
       "metadata": {"name": "web-1", "namespace": "prod"},
       "spec": {"containers": [{"name": "a"}, {"name": "b", "x": 1}]}}


def assign(location: str, value, match=None, tests=()) -> dict:
    params: dict = {"assign": {"value": value}}
    if tests:
        params["pathTests"] = [{"subPath": p, "condition": c}
                               for p, c in tests]
    return {"kind": "Assign", "metadata": {"name": "m"}, "spec": {
        "applyTo": [{"groups": [""], "kinds": ["Pod"], "versions": ["v1"]}],
        "match": match or {}, "location": location, "parameters": params}}


@pytest.mark.parametrize("doc,containers", [
    # a glob walks every element and creates the missing fields on the way
    (assign("spec.containers[name: *].s.p", False),
     [{"name": "a", "s": {"p": False}}, {"name": "b", "x": 1,
                                         "s": {"p": False}}]),
    # MustNotExist leaves an element that has the field
    (assign("spec.containers[name:*].x", 2,
            tests=[("spec.containers[name:*].x", "MustNotExist")]),
     [{"name": "a", "x": 2}, {"name": "b", "x": 1}]),
    # a key names one element, and creates it where it is missing
    (assign("spec.containers[name: b].x", 3),
     [{"name": "a"}, {"name": "b", "x": 3}]),
    (assign('spec.containers[name: "c"].x', 4),
     [{"name": "a"}, {"name": "b", "x": 1}, {"name": "c", "x": 4}]),
    # MustExist stops where the element is missing
    (assign("spec.containers[name: c].x", 4,
            tests=[("spec.containers[name: c]", "MustExist")]),
     [{"name": "a"}, {"name": "b", "x": 1}]),
])
def test_the_reference_assigns_as_upstream_does(doc, containers):
    pod = copy.deepcopy(POD)
    m = expansion_reference.Assign(doc)
    assert m.apply(pod) is (pod["spec"]["containers"]
                            != POD["spec"]["containers"])
    assert pod["spec"]["containers"] == containers
    assert m.apply(pod) is False


@pytest.mark.parametrize("match,source,matches", [
    ({}, "Original", True),
    ({"source": "Generated"}, "Original", False),
    ({"source": "Generated"}, "Generated", True),
    ({"source": "Original"}, "Generated", False),
    ({"scope": "Cluster"}, "Generated", False),
    ({"kinds": [{"apiGroups": [], "kinds": []}]}, "Generated", True),
    ({"kinds": [{"apiGroups": ["apps"], "kinds": ["Pod"]}]}, "Generated",
     False),
    ({"kinds": [{"apiGroups": ["*"], "kinds": ["Pod"]}]}, "Generated", True),
    ({"namespaces": ["pr*"]}, "Generated", True),
    ({"namespaces": ["production"]}, "Generated", False),
    ({"excludedNamespaces": ["*od"]}, "Generated", False),
    ({"name": "nginx-*"}, "Generated", False),
    ({"name": "web-*"}, "Generated", True),
])
def test_the_reference_matches_as_upstream_does(match, source, matches):
    m = expansion_reference.Assign(assign("spec.x", 1, match))
    assert m.matches(POD, source) is matches


@pytest.mark.parametrize("doc", [
    dict(assign("metadata.labels.owner", "admin"), kind="AssignMetadata"),
    assign("spec.x", 1, {"labelSelector": {"matchLabels": {"a": "b"}}}),
    assign("spec.containers[name: a]", {"name": "a"}),
])
def test_what_the_reference_does_not_write_it_refuses(doc):
    with pytest.raises(ValueError):
        expansion_reference.Assign(doc).apply(copy.deepcopy(POD))


# --- correct: totals and kept violations, implied ones included -------------------

A, DEP = ("Pod", "ns-0", "a"), ("Deployment", "ns-0", "dep-1")
R = ("Pod", "ns-0", "dep-1-pod")
K1 = ("K8sRequiredProbes", "must-have-probes")
IDENT = {0: A, 1: DEP}
RESULTS = {0: {K1: ["a lacks a probe"]}}
IMPLIED = {1: [(R, {K1: [("[Implied by t] dep-1-pod lacks a probe", "warn"),
                        ("[Implied by t] dep-1-pod lacks another", "warn")]})]}
ORDER = [0, 1, 0, 1]
BASE = [(A, "a lacks a probe", "deny")] * 2
OWED = [(R, "[Implied by t] dep-1-pod lacks a probe", "warn"),
        (R, "[Implied by t] dep-1-pod lacks another", "warn")] * 2


def audited(totals: dict, kept: list):
    V = collections.namedtuple(
        "V", "kind namespace name message enforcement_action")
    Run = collections.namedtuple(
        "Run", "total_violations kept total_objects incomplete")
    return Run(totals, {K1: [V(*obj, msg, act) for obj, msg, act in kept]},
               len(ORDER), False)


@pytest.mark.parametrize("totals,kept,differ", [
    # the listed object's results, then what the generators imply
    ({K1: 6}, BASE + OWED, {}),
    # the generator stage off: nothing implied
    ({K1: 2}, BASE, {"sample_totals_differ": 1, "sample_kept_differ": 1}),
    # a resultant reviewed before it was mutated reads another total
    ({K1: 8}, BASE + OWED, {"sample_totals_differ": 1}),
    # implied violations kept before the listed ones they follow
    ({K1: 6}, OWED[:2] + BASE + OWED[2:], {"sample_kept_differ": 1}),
    # the template's enforcementAction not applied
    ({K1: 6}, BASE + [(o, m, "deny") for o, m, _a in OWED],
     {"sample_kept_differ": 1}),
    # the prefix left off
    ({K1: 6}, BASE + [(o, m.partition("] ")[2], a) for o, m, a in OWED],
     {"sample_kept_differ": 1}),
])
def test_the_sample_audit_is_held_to_what_the_generators_imply(totals, kept,
                                                               differ):
    counts: dict = {}
    problems = audit.sample_audit_problems(
        audited(totals, kept), ORDER, RESULTS, IDENT, 20, True, counts,
        IMPLIED)
    assert {k: v for k, v in counts.items() if v} == differ
    assert len(problems) == sum(differ.values())


def test_without_implied_results_the_comparison_is_the_one_before():
    # the configurations that do not expand pass no ``implied``: a listed
    # object's kept violation is compared on its message alone
    counts: dict = {}
    assert audit.sample_audit_problems(
        audited({K1: 2}, BASE), ORDER, RESULTS, IDENT, 20, True,
        counts) == []
    assert not any(counts.values())


# --- the three readers, on a recorded observation ----------------------------------

def reader(name: str):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture(scope="module")
def recorded() -> dict:
    return manifest.read_json(RECORDED)


def test_the_stage_is_its_outer_spans_alone(recorded):
    outer = [sp for sp in recorded["spans"]
             if sp["name"] == "expansion.stage"
             and sp["attributes"].get("phase") == "audit"]
    inner = [sp for sp in recorded["spans"]
             if sp["name"] == "expansion.stage" and sp not in outer]
    assert len(outer) == len(inner) == recorded["passes"] == 2
    # each inner span lies inside an outer one, so counting both would
    # count the stage's expand twice
    assert {sp["parent_id"] for sp in inner} == {sp["span_id"]
                                                 for sp in outer}
    got = reader("expansion.busy_s_per_pass")(recorded)
    assert got == pytest.approx(sum(sp["duration_s"] for sp in outer) / 2)
    assert got < sum(sp["duration_s"] for sp in outer + inner) / 2


def test_the_resultants_are_the_generators(recorded):
    got = reader("expansion.resultants_per_pass")(recorded)
    assert got == recorded["generators"] == 822


def test_the_lane_is_every_apply_span(recorded):
    spans = [sp for sp in recorded["spans"] if sp["name"] == "mutlane.apply"]
    assert len(spans) == 2
    got = reader("mutlane.apply_s_per_pass")(recorded)
    assert got == pytest.approx(sum(sp["duration_s"] for sp in spans) / 2)
    assert got < reader("expansion.busy_s_per_pass")(recorded)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_with_nothing_to_read_returns_nothing(name, recorded):
    read = reader(name)
    assert read({"passes": 2, "spans": []}) is None
    assert read({**recorded, "passes": 0}) is None
    # a window whose spans hold no stage (the stage off) reads nothing,
    # never 0
    other = [sp for sp in recorded["spans"]
             if sp["name"] not in ("expansion.stage", "mutlane.apply")]
    assert read({**recorded, "spans": other}) is None


# --- the manifest -----------------------------------------------------------------------

def test_the_manifest_holds_the_configuration_the_cell_and_its_metrics():
    assert manifest.check() == []
    m = manifest.read_json(manifest.MANIFEST)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    doc = config(CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == doc["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["objects"] == list(doc["reduced"])
    work = next(w for w in m["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": CONFIG, "traffic": "audit-sweep",
                    "chips": 1, "why": work["why"]}
    assert "17613" in work["why"] and CONTROL in work["why"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    # appended after the control wherever the control is listed
    for metric in m["end_to_end"] + m["per_layer"]:
        listed = metric.get("workloads", [])
        if CONTROL in listed:
            assert listed[-1] == CELL, metric["name"]
    for name in NEW_METRICS:
        p = next(p for p in m["per_layer"] if p["name"] == name)
        assert p["workloads"] == [CELL] and p["moves"] == "audit_pass_s"
        assert p["source"] == "program_span"


def test_the_cell_reports_its_controls_metrics_and_the_stages():
    cell, control = manifest.Cell(CELL), manifest.Cell(CONTROL)
    assert cell.end_to_end == control.end_to_end
    mine = [p["name"] for p in cell.per_layer]
    assert mine == [p["name"] for p in control.per_layer] + list(NEW_METRICS)


# --- the cell, end to end at toy size ------------------------------------------------

def rehearse(capsys, *args: str) -> tuple:
    """(the result line, the notes) of one rehearsal of the cell."""
    from benchmark import run as run_py

    assert run_py.main(["--workload", CELL, "--rehearse", "--seed",
                        str(SEED), "--seconds", "4", *args]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert "rehearsal" in line and list(line)[-1] == "compared"
    assert line["correct"] is all(c["value"] <= c["limit"]
                                  for c in line["compared"].values())
    with open(os.path.join(ROOT, "benchmark", ".cache", CELL,
                           "notes.json")) as f:
        return line, json.load(f)


def test_rehearse_the_cell(capsys):
    """The generator stage at toy size on whatever JAX finds: correct, the
    generators' implied results present, one resultant a generator, the
    lister on its per-object loop."""
    line, notes = rehearse(capsys, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert notes["problems"] == []
    assert line["compared"]["reference_implied_empty"]["value"] == 0
    assert notes["reference_implied_results"] > 0
    metrics = line["metrics"]
    assert metrics["expansion.resultants_per_pass"]["value"] == \
        notes["generators"] > 0
    assert 0 < metrics["mutlane.apply_s_per_pass"]["value"] < \
        metrics["expansion.busy_s_per_pass"]["value"]
    assert metrics["list.fast_share"]["value"] == 0.0
    listed = {p["name"] for p in manifest.Cell(CELL).per_layer}
    assert listed - set(metrics) <= {"sweep_device_roofline"}


def generator_stage_off(monkeypatch) -> None:
    """The program run without ``--audit-expand`` under a configuration
    that states it."""
    real = wiring.Program.build_audit

    def build_audit(self, lister):
        stated = self.config
        self.config = dict(stated, audit=dict(stated["audit"],
                                              expand_generated=False))
        try:
            return real(self, lister)
        finally:
            self.config = stated

    monkeypatch.setattr(wiring.Program, "build_audit", build_audit)


def mutators_left_out_of_the_program(monkeypatch) -> None:
    """The program's systems without the mutators; the reference, which
    reads the configuration's files itself, keeps them."""
    real = wiring.expansion_system

    def expansion_system(config):
        return real(dict(config, expansion=dict(config["expansion"],
                                                mutators=[])))

    monkeypatch.setattr(wiring, "expansion_system", expansion_system)


def half_the_resultants_left_out(monkeypatch) -> None:
    """Every second generator of a chunk expands to nothing."""
    from gatekeeper_tpu.mutlane.expand_stage import ExpansionStage

    real = ExpansionStage.expand_batch

    def expand_batch(self, bases, namespaces=None, source=""):
        out = real(self, bases, namespaces, source)
        for r in out[1::2]:
            r.resultants = []
        return out

    monkeypatch.setattr(ExpansionStage, "expand_batch", expand_batch)


def template_metadata_left_out(monkeypatch) -> None:
    """The program's ``expandResource`` keeps the resultant's name,
    Namespace and owner and leaves out the labels and annotations of the
    pod template."""
    from gatekeeper_tpu.expansion.system import ExpansionSystem

    real = ExpansionSystem._expand_resource

    def _expand_resource(obj, namespace, template):
        out = real(obj, namespace, template)
        out["metadata"] = {k: v for k, v in out["metadata"].items()
                           if k in ("name", "namespace", "ownerReferences")}
        return out

    monkeypatch.setattr(ExpansionSystem, "_expand_resource",
                        staticmethod(_expand_resource))


def list_walk_stopped_at_the_first_element(monkeypatch) -> None:
    """The program's Assign walks ``[name: *]`` to the first container
    alone."""
    from gatekeeper_tpu.mutation import core
    from gatekeeper_tpu.mutation.path_parser import ListNode

    real = core._mutate

    def _mutate(node, path, depth, setter, tester):
        part = path[depth]
        if isinstance(part, ListNode) and part.glob and isinstance(node,
                                                                   list):
            node = node[:1]
        return real(node, path, depth, setter, tester)

    monkeypatch.setattr(core, "_mutate", _mutate)


@pytest.mark.parametrize("fault", [generator_stage_off,
                                   mutators_left_out_of_the_program,
                                   half_the_resultants_left_out,
                                   template_metadata_left_out,
                                   list_walk_stopped_at_the_first_element])
def test_the_faults_come_out_not_correct(capsys, monkeypatch, fault):
    """The harness's look for a chip skipped (``--rehearse``), the rest of a
    run driven with the generator stage broken underneath: ``correct`` is
    false on the sample's totals, and the device's own verdicts on the
    listed sample still agree with the interpreter's."""
    fault(monkeypatch)
    line, notes = rehearse(capsys)
    assert line["correct"] is False
    assert line["compared"]["sample_totals_differ"]["value"] > 0
    assert line["compared"]["device_pairs_differ"]["value"] == 0
    assert notes["problems"]

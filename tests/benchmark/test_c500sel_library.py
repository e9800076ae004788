"""``library-c500sel`` and its cell ``c500sel.audit-sweep`` (PR 32), after
``test_c500_library.py``: the committed library is what
``benchmark/libraries/make_c500sel.py`` writes, its rows are
``library-c500``'s with every ``match`` rescoped by labels, the labels of the
configuration's cluster select what ``library-c500`` lists by name, the
manifest resolves, and the two per-layer metrics read what they should.
Nothing here times the system under test."""

from __future__ import annotations

import filecmp
import json
import os
import sys

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cluster, manifest, readers, wiring  # noqa: E402
from benchmark.libraries import make_c500, make_c500sel  # noqa: E402
from gatekeeper_tpu.match.match import label_selector_matches  # noqa: E402

CELL = "c500sel.audit-sweep"
CONTROL = "c500.audit-sweep"
CELLS = ["full.audit-sweep", "psp.audit-sweep", CONTROL, CELL]
NEW = ["masks.selector_row_share", "masks.selector_s_per_pass"]
COMMITTED = os.path.join(ROOT, "benchmark", "libraries", "c500sel")
MATCH_KEYS = {"kinds", "excludedNamespaces", "scope", "labelSelector",
              "namespaceSelector"}
EXEMPT = "policy.example.com/exempt"


def config(name: str = "library-c500sel") -> dict:
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           name + ".json"))


def load(directory: str) -> dict:
    """{template directory: [constraint documents]} as committed."""
    out = {}
    for name in sorted(os.listdir(directory)):
        with open(os.path.join(directory, name, "samples",
                               "constraint.yaml")) as f:
            out[name] = [d for d in yaml.safe_load_all(f) if d]
    return out


def pairs() -> list:
    """[(library-c500's document, this library's)], row for row."""
    old = load(os.path.join(ROOT, "benchmark", "libraries", "c500"))
    new = load(COMMITTED)
    assert list(old) == list(new)
    assert all(len(old[n]) == len(new[n]) for n in old)
    return [(a, b) for n in old for a, b in zip(old[n], new[n])]


def selected(selector: dict, namespaces: dict) -> list:
    return sorted(name for name, obj in namespaces.items()
                  if label_selector_matches(
                      selector, obj["metadata"].get("labels") or {}))


def namespaces() -> dict:
    return cluster.Cluster(config()["cluster"], cluster.SHARD,
                           seed=1).namespace_objects()


# --- the library ----------------------------------------------------------

def test_the_generator_reproduces_the_committed_files(tmp_path):
    assert make_c500sel.write(str(tmp_path)) == 500
    names = sorted(os.listdir(COMMITTED))
    assert sorted(os.listdir(tmp_path)) == names and len(names) == 46
    for name in names:
        for rel in ("template.yaml",
                    os.path.join("samples", "constraint.yaml")):
            assert filecmp.cmp(os.path.join(tmp_path, name, rel),
                               os.path.join(COMMITTED, name, rel),
                               shallow=False), (name, rel)
        assert sorted(os.listdir(os.path.join(COMMITTED, name))) == [
            "samples", "template.yaml"]


@pytest.mark.parametrize("name,path", make_c500.templates())
def test_a_template_is_the_librarys_own(name, path):
    assert filecmp.cmp(os.path.join(COMMITTED, name, "template.yaml"),
                       os.path.join(path, "template.yaml"), shallow=False)


def test_the_rows_are_library_c500s_but_for_the_match_fields():
    for old, new in pairs():
        a, b = json.loads(json.dumps(old)), json.loads(json.dumps(new))
        ma, mb = a["spec"].pop("match"), b["spec"].pop("match")
        assert a == b  # kind, name, parameters, enforcementAction
        assert ma.get("kinds") == mb.get("kinds")
        assert set(mb) <= MATCH_KEYS, set(mb)


def test_no_row_lists_namespaces_and_every_row_has_a_namespace_selector():
    rows = [new["spec"]["match"] for _old, new in pairs()]
    assert len(rows) == 500
    assert not any("namespaces" in m for m in rows)
    assert all(m.get("namespaceSelector") for m in rows)
    assert not any("name" in m or "source" in m for m in rows)


def test_the_counts_in_the_configurations_file():
    docs = load(COMMITTED)
    lib = config()["library"]
    assert lib["constraints"] == make_c500sel.counts(docs)
    assert lib["constraints"] == {
        "baseline": 46, "tenant": 454, "tenant_match_labels_only": 341,
        "tenant_env_in": 57, "tenant_env_not_in": 56, "tenants": 50,
        "namespace_selector": 500, "label_selector": 103,
        "scope_namespaced": 41, "scope_cluster": 2, "namespaces_list": 0}
    assert lib["expect"] == {"templates": 46, "constraints": 500,
                             "on_interpreter_fallback": 0}


def test_a_baseline_exempts_by_label_and_carries_its_scope():
    seen = set()
    for old, new in pairs():
        if "namespaces" in old["spec"]["match"]:
            continue
        m = new["spec"]["match"]
        assert m["excludedNamespaces"] == ["kube-system",
                                           "gatekeeper-system"]
        assert m["namespaceSelector"] == {"matchExpressions": [
            {"key": EXEMPT, "operator": "DoesNotExist"}]}
        kinds = make_c500sel.kinds_of(m)
        want = ("Namespaced" if kinds <= make_c500sel.NAMESPACED else
                "Cluster" if kinds <= make_c500sel.CLUSTER_SCOPED else None)
        assert m.get("scope") == want, kinds
        seen.add(want)
    assert seen == {"Namespaced", "Cluster", None}


def test_a_tenants_label_selects_the_four_namespaces_library_c500_lists():
    """The 341 rows that list four names there: under the configuration's
    label rules ``matchLabels: {tenant: t<k>}`` selects exactly those."""
    ns = namespaces()
    assert len(ns) == 200
    n = 0
    for old, new in pairs():
        listed = old["spec"]["match"].get("namespaces")
        if not listed or listed[0].endswith("*"):
            continue
        selector = new["spec"]["match"]["namespaceSelector"]
        assert list(selector) == ["matchLabels"]
        assert selected(selector, ns) == sorted(listed)
        n += 1
    assert n == 341


def test_a_glob_row_selects_its_tenant_in_an_environment():
    ns = namespaces()
    forms = {"In": 0, "NotIn": 0}
    some = 0
    for old, new in pairs():
        listed = old["spec"]["match"].get("namespaces")
        if not listed or not listed[0].endswith("*"):
            continue
        selector = new["spec"]["match"]["namespaceSelector"]
        t = int(new["metadata"]["name"][1:3])
        assert selector["matchLabels"] == {"tenant": f"t{t}"}
        (expr,) = selector["matchExpressions"]
        assert expr["key"] == "env"
        forms[expr["operator"]] += 1
        got = selected(selector, ns)
        assert set(got) <= set(make_c500.tenant_namespaces(t))
        some += bool(got)
    assert forms == {"In": 57, "NotIn": 56}
    assert some > 100  # env is prod or staging on three namespaces in four


def test_a_label_selector_reads_a_label_its_kind_draws():
    draws = {kind: set(rules)
             for kind, rules in config()["cluster"]["labels"].items()}
    assert draws == make_c500sel.DRAWS
    forms = []
    rows = eligible = 0
    for _old, new in pairs():
        m = new["spec"]["match"]
        kinds = make_c500sel.kinds_of(m)
        if "tenant" in (m["namespaceSelector"].get("matchLabels") or {}) \
                and kinds <= set(draws):
            eligible += 1
        if "labelSelector" not in m:
            continue
        rows += 1
        assert kinds <= set(draws)
        keys = set(m["labelSelector"].get("matchLabels") or {}) | {
            e["key"] for e in m["labelSelector"].get("matchExpressions", ())}
        assert all(keys <= draws[k] for k in kinds), (kinds, keys)
        forms.append(json.dumps(m["labelSelector"], sort_keys=True))
    assert rows == 103 and rows == (eligible + 3) // 4
    # all five forms occur: matchLabels, In, Exists, DoesNotExist on the
    # object, and env In for Ingress, which draws env alone
    assert len(set(forms)) == 5


def test_the_exemption_label_exempts_about_one_namespace_in_ten():
    ns = namespaces()
    exempt = [n for n, o in ns.items()
              if EXEMPT in (o["metadata"].get("labels") or {})]
    assert 8 <= len(exempt) <= 32
    tenants = {o["metadata"]["labels"]["tenant"] for o in ns.values()}
    assert tenants == {f"t{k}" for k in range(50)}
    assert all(ns[f"ns-{i}"]["metadata"]["labels"]["tenant"]
               == f"t{i % 50}" for i in range(200))


# --- the configuration -----------------------------------------------------

def test_the_configuration_is_library_c500s_but_for_the_labels():
    c500, sel = config("library-c500"), config()
    fixture = manifest.read_json(os.path.join(
        ROOT, "tests", "benchmark", "configs",
        "c500-selectors-fixture.json"))
    assert sel["objects"] == c500["objects"] == 131072
    assert sel["audit"] == c500["audit"]
    for key in ("kinds", "pod", "deviations"):
        assert sel["cluster"][key] == c500["cluster"][key], key
    assert sel["cluster"]["labels"] == fixture["cluster"]["labels"]
    want = json.loads(json.dumps(fixture["cluster"]["namespaces"]))
    want["count"], want["labels"]["tenant"]["cycle"] = 200, 50
    assert sel["cluster"]["namespaces"] == want
    assert sel["referential_kinds"] == ["Ingress", "Namespace"]
    assert sel["reference_sample"] == dict(c500["reference_sample"],
                                           Namespace=256)
    assert list(sel["reduced"]) == ["objects"]
    for key, text in c500["guarantees"].items():
        assert sel["guarantees"][key] == text, key
    assert {"namespace_labels", "namespaces_present"} <= set(
        sel["guarantees"])
    for key in ("cluster.namespaces.labels", "cluster.labels",
                "library.match", "library.label_selector",
                "inventory.namespaces"):
        assert key in sel["assumed"], key
    assert sel["rehearse"]["cluster.namespaces.count"] < 200
    dirs = wiring.template_dirs(sel)
    assert [os.path.basename(d) for d in dirs] == [
        n for n, _ in make_c500.templates()]
    assert all(os.path.samefile(d, os.path.join(COMMITTED,
                                                os.path.basename(d)))
               for d in dirs)


def test_the_library_loads_as_the_harness_loads_it():
    client = wiring.interpreter_client(config())
    assert len(client.constraints()) == 500


def test_the_first_shard_lists_every_namespace_and_shows_every_label():
    """The first shard is the same for every seed and holds the cluster's
    vocabulary: the 200 Namespace objects, and every label value a selector
    or a column can meet."""
    spec = config()["cluster"]
    listed = []
    values: dict = {}
    for obj in cluster.Cluster(spec, cluster.SHARD, seed=1).objects(0):
        if obj["kind"] == "Namespace" \
                and not obj["metadata"]["name"].startswith("ns-x"):
            listed.append(obj["metadata"]["name"])
        for k, v in (obj["metadata"].get("labels") or {}).items():
            values.setdefault((obj["kind"], k), set()).add(v)
    assert listed == [f"ns-{i}" for i in range(200)]
    for kind, rules in spec["labels"].items():
        for key, rule in rules.items():
            assert values[(kind, key)] == set(rule["values"]), (kind, key)
    for key, rule in spec["namespaces"]["labels"].items():
        if "values" in rule:
            assert values[("Namespace", key)] >= set(rule["values"]), key


# --- the manifest ----------------------------------------------------------

def entries() -> dict:
    return manifest.read_json(manifest.MANIFEST)


def listed_in_order(workloads: list) -> bool:
    """The four cells of PR 32 are listed, in their order; later cells may
    stand anywhere among them."""
    return [w for w in workloads if w in CELLS] == CELLS


def test_the_manifest_resolves_with_the_cell_and_its_configuration():
    """Containment and order since PR 38: later PRs append their own cells
    and configurations (until then this test held the manifest to exactly
    four of each and ``tests/conftest.py`` deselected it under its old
    name)."""
    assert manifest.check() == []
    m = entries()
    assert listed_in_order([w["name"] for w in m["workloads"]])
    assert len(m["configs"]) >= 4
    entry = next(c for c in m["configs"] if c["name"] == "library-c500sel")
    assert entry["file"] == "benchmark/configs/library-c500sel.json"
    assert entry["source"] == config()["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["objects"]
    work = next(w for w in m["workloads"] if w["name"] == CELL)
    assert work == {
        "name": CELL, "config": "library-c500sel", "traffic": "audit-sweep",
        "chips": 1, "why": work["why"]}
    assert "131072 objects x 500 constraints" in work["why"]
    cell = manifest.Cell(CELL)
    assert {e["name"] for e in cell.end_to_end} == {"audit_pass_s",
                                                    "setup_s"}


def test_the_cell_reports_what_the_control_reports_with_the_two_new():
    """Containment and order since PR 38 (the list grows with every metric
    a later PR appends; the old name is the one ``tests/conftest.py``
    deselected)."""
    m = entries()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert listed_in_order(e2e["audit_pass_s"]["workloads"])
    control = [p["name"] for p in manifest.Cell(CONTROL).per_layer]
    mine = [p["name"] for p in manifest.Cell(CELL).per_layer]
    assert mine == control and len(mine) >= 28
    assert [n for n in mine if n in NEW] == NEW
    older = mine[:mine.index(NEW[0])]
    assert len(older) == 26  # what the control reported before PR 32
    per_layer = {p["name"]: p for p in m["per_layer"]}
    # no list: every cell reports it, the planned admission cells too
    # (test_benchmark_yardstick.py holds that they need entries only)
    assert "workloads" not in per_layer["entry.compiles_in_window"]
    assert "entry.compiles_in_window" in mine
    for name, better, unit in zip(NEW, ["higher", "lower"], ["1", "s"]):
        p = per_layer[name]
        assert listed_in_order(p["workloads"])
        assert (p["layer"], p["moves"], p["source"]) == (
            "masks", "audit_pass_s", "program_counter")
        assert (p["better"], p["unit"]) == (better, unit)
        spec = manifest.read_json(manifest.metric_path(name))
        assert (p["layer"], p["unit"]) == (spec["layer"], spec["unit"])
        assert spec["read"]["from"] == "perf"  # data, the general reader
        assert not os.path.exists(manifest.metric_path(name)[:-5] + ".py")


# --- the readers -------------------------------------------------------------

def read(name: str, evaluator: dict, passes: int = 2):
    spec = manifest.read_json(manifest.metric_path(name))
    obs = {"perf": {"manager": {}, "evaluator": evaluator},
           "passes": passes, "objects": 1000, "constraints": 500,
           "spans": [], "trace": None}
    out = readers.read_all([spec], obs)
    return out[name]["value"] if name in out else None


# evaluator.perf of the parent of PR 32 (no such keys) and of the change in
# a cell with and without selectors, two passes
PARENT = {"masks": 0.5, "mask_rows_fast": 8000.0, "mask_rows_slow": 0.0}
SELECTED = dict(PARENT, mask_rows_selector=8000.0, masks_selector=0.25,
                mask_ns_missing=0.0)
PLAIN = dict(PARENT, mask_rows_selector=0.0, masks_selector=0.0,
             mask_ns_missing=0.0)


@pytest.mark.parametrize("perf,share,seconds", [
    (SELECTED, 1.0, 0.125), (PLAIN, 0.0, 0.0), (PARENT, None, None),
    (dict(SELECTED, mask_rows_selector=2000.0), 0.25, 0.125)])
def test_the_two_metrics_read_the_counters_or_are_left_out(perf, share,
                                                           seconds):
    assert read("masks.selector_row_share", perf) == share
    assert read("masks.selector_s_per_pass", perf) == seconds


def test_a_window_without_passes_reads_nothing():
    assert read("masks.selector_s_per_pass", SELECTED, passes=0) is None


# --- the cell, end to end at toy size ------------------------------------------

@pytest.mark.slow
def test_rehearse_the_cell(capsys):
    """The whole audit path at toy sizes on whatever JAX finds: the 500
    selector-scoped constraints, the corpus with its 40 labelled Namespaces
    synced, the reference children, the sample's audit, the window, the
    readers.  A minute and a half (slow-marked as the control's is)."""
    from benchmark import run as run_py

    assert run_py.main(["--workload", CELL, "--rehearse", "--seed",
                        "2147483999", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert "rehearsal" in line
    metrics = line["metrics"]
    # (all 28 but the roofline share, which has no peak off the chip)
    assert set(NEW) <= set(metrics) and len(metrics) >= 27
    assert metrics["masks.slow_row_share"]["value"] == 0.0
    assert metrics["masks.selector_row_share"]["value"] == 1.0
    assert metrics["masks.selector_s_per_pass"]["value"] > 0.0
    assert metrics["entry.compiles_in_window"]["value"] == 0.0

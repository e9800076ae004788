"""``library-c500`` and its cell ``c500.audit-sweep`` (PR 27): the committed
library is what ``benchmark/libraries/make_c500.py`` writes, the constraint
set has the counts and shares ISSUE 27 names, the configuration is
``library-full``'s but for the library and the namespaces, the manifest
resolves, and the three per-layer metrics read what they should.  Nothing
here times the system under test."""

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
from benchmark.libraries import make_c500  # noqa: E402

CELL = "c500.audit-sweep"
CELLS = ["full.audit-sweep", "psp.audit-sweep", CELL]
NEW = ["masks.busy_s_per_pass", "masks.slow_row_share",
       "pack_h2d.mask_bytes_per_object"]
COMMITTED = os.path.join(ROOT, "benchmark", "libraries", "c500")
MATCH_KEYS = {"kinds", "namespaces", "excludedNamespaces"}


def config(name: str) -> dict:
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           name + ".json"))


def committed() -> dict:
    """{template directory: [constraint documents]} as committed."""
    out = {}
    for name in sorted(os.listdir(COMMITTED)):
        with open(os.path.join(COMMITTED, name, "samples",
                               "constraint.yaml")) as f:
            out[name] = [d for d in yaml.safe_load_all(f) if d]
    return out


def tenants_of(docs: list) -> list:
    return [d for d in docs if "namespaces" in d["spec"]["match"]]


# --- the library ----------------------------------------------------------

def test_the_generator_reproduces_the_committed_files(tmp_path):
    assert make_c500.write(str(tmp_path)) == 500
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


def test_another_seed_is_another_set_of_the_same_shape():
    a, b = make_c500.constraint_set(500), make_c500.constraint_set(501)
    assert a != b
    assert sum(map(len, a.values())) == sum(map(len, b.values())) == 500


@pytest.mark.parametrize("name,path", make_c500.templates())
def test_a_template_is_the_librarys_own(name, path):
    assert filecmp.cmp(os.path.join(COMMITTED, name, "template.yaml"),
                       os.path.join(path, "template.yaml"), shallow=False)
    # and its first constraint is the sample, cluster-wide, with the
    # exclusions added and nothing else changed
    sample = make_c500.sample_of(path)
    base = committed()[name][0]
    assert base["spec"]["match"].pop("excludedNamespaces") == [
        "kube-system", "gatekeeper-system", "ns-19*"]
    if not base["spec"]["match"] and "spec" not in sample:
        del base["spec"]  # uniqueserviceselector's sample has no spec
    assert base == sample


def test_the_set_has_the_counts_and_shares_the_issue_names():
    docs = committed()
    everything = [d for ds in docs.values() for d in ds]
    assert len(everything) == 500
    assert len({(d["kind"], d["metadata"]["name"])
                for d in everything}) == 500
    tenant = [d for ds in docs.values() for d in tenants_of(ds)]
    assert len(everything) - len(tenant) == 46
    assert all(len(ds) - len(tenants_of(ds)) == 1 for ds in docs.values())
    glob = [d for d in tenant
            if d["spec"]["match"]["namespaces"][0].endswith("*")]
    exact = [d for d in tenant if d not in glob]
    assert (len(exact), len(glob)) == (341, 113)  # of 454: 3/4 and 1/4
    # spread as evenly as the count allows, over the templates whose kind
    # the cluster holds
    per = {name: len(tenants_of(ds)) for name, ds in docs.items()}
    unscoped = sorted(n for n, k in per.items() if k == 0)
    assert unscoped == ["blockendpointeditdefaultrole",
                        "horizontalpodautoscaler", "poddisruptionbudget",
                        "storageclass"]
    assert {k for k in per.values() if k} == {10, 11}
    # 50 tenants in turn, four namespaces each, or the tenant's one prefix
    own = {tuple(make_c500.tenant_namespaces(t)) for t in range(50)}
    assert len({ns for group in own for ns in group}) == 200
    for d in exact:
        assert tuple(d["spec"]["match"]["namespaces"]) in own
    for d in glob:
        (pattern,) = d["spec"]["match"]["namespaces"]
        t = int(d["metadata"]["name"][1:3])
        assert pattern == f"ns-{t}*"
    seen = {int(d["metadata"]["name"][1:3]) for d in tenant}
    assert seen == set(range(50))


def test_no_matcher_of_the_set_needs_the_per_object_predicate():
    for name, ds in committed().items():
        sample = make_c500.sample_of(dict(make_c500.templates())[name])
        kinds = ((sample.get("spec") or {}).get("match") or {}).get("kinds")
        for d in ds:
            match = d["spec"]["match"]
            assert set(match) <= MATCH_KEYS, (name, set(match))
            assert match.get("kinds") == kinds
            assert d["spec"].get("enforcementAction") == (
                sample.get("spec") or {}).get("enforcementAction")


def test_a_tenant_constraint_draws_the_samples_parameters_or_a_variant():
    by_name = dict(make_c500.templates())
    varied = 0
    for name, ds in committed().items():
        sample = (make_c500.sample_of(by_name[name]).get("spec")
                  or {}).get("parameters")
        choices = [sample] + list(make_c500.VARIANTS.get(name, ()))
        for d in tenants_of(ds):
            assert d["spec"].get("parameters") in choices, name
            varied += d["spec"].get("parameters") != sample
    assert varied > 150
    assert set(make_c500.VARIANTS) <= set(by_name)


# --- the configuration -----------------------------------------------------

def test_the_configuration_is_library_fulls_but_for_library_and_namespaces():
    full, c500 = config("library-full"), config("library-c500")
    for key in ("audit", "referential_kinds", "reference_sample",
                "guarantees", "rehearse"):
        assert c500[key] == full[key], key
    # half of library-full's: the parent commit's traced run holds its three
    # whole passes in the window at this size and not at 262144
    assert c500["objects"] * 2 == full["objects"] == 262144
    for key in ("kinds", "pod", "deviations"):
        assert c500["cluster"][key] == full["cluster"][key], key
    assert c500["cluster"]["namespaces"] == {"count": 200, "zipf_s": 1.1}
    assert c500["library"]["expect"] == {
        "templates": 46, "constraints": 500, "on_interpreter_fallback": 0}
    assert list(c500["reduced"]) == ["objects"]
    for key in ("library.tenants", "library.match", "library.parameters",
                "cluster.namespaces"):
        assert key in c500["assumed"], key
    dirs = wiring.template_dirs(c500)
    assert [os.path.basename(d) for d in dirs] == [
        n for n, _ in make_c500.templates()]
    assert all(os.path.samefile(d, os.path.join(COMMITTED,
                                                os.path.basename(d)))
               for d in dirs)


def test_the_library_loads_as_the_harness_loads_it():
    client = wiring.interpreter_client(config("library-c500"))
    assert len(client.constraints()) == 500


def test_every_namespace_appears_in_the_first_shard():
    """The first shard is the same for every seed and holds the cluster's
    vocabulary: with all 200 names in it a second seed finds the compiled
    sweep programs in the XLA cache."""
    spec = config("library-c500")["cluster"]
    counts: dict = {}
    for obj in cluster.Cluster(spec, cluster.SHARD, seed=1).objects(0):
        ns = obj["metadata"].get("namespace")
        if ns:
            counts[ns] = counts.get(ns, 0) + 1
    assert len(counts) == 200 and min(counts.values()) >= 5
    assert {f"ns-{i}" for i in range(200)} == set(counts)


# --- the manifest ----------------------------------------------------------

def entries() -> dict:
    return manifest.read_json(manifest.MANIFEST)


def test_the_manifest_resolves_with_the_cell():
    assert manifest.check() == []
    m = entries()
    entry = {c["name"]: c for c in m["configs"]}["library-c500"]
    assert entry["file"] == "benchmark/configs/library-c500.json"
    assert entry["source"] == config("library-c500")["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["objects"]
    work = {w["name"]: w for w in m["workloads"]}[CELL]
    assert work == {
        "name": CELL, "config": "library-c500", "traffic": "audit-sweep",
        "chips": 1, "why": work["why"]}
    assert "131072 objects x 500 constraints" in work["why"]
    cell = manifest.Cell(CELL)
    assert {e["name"] for e in cell.end_to_end} == {"audit_pass_s",
                                                    "setup_s"}
    assert cell.config["library"]["expect"]["constraints"] == 500


def test_the_three_metrics_follow_the_older_ones_and_list_the_cell():
    per_layer = {p["name"]: p for p in entries()["per_layer"]}
    names = list(per_layer)
    assert [n for n in names[names.index("list.cpu_s_per_pass") + 1:]
            if n in NEW] == NEW
    for name, layer in zip(NEW, ["masks", "masks", "pack_h2d"]):
        p = per_layer[name]
        assert set(CELLS) <= set(p["workloads"])
        assert p["moves"] == "audit_pass_s" and p["layer"] == layer
        assert p["source"] == "program_counter" and p["better"] == "lower"
        spec = manifest.read_json(manifest.metric_path(name))
        assert (p["layer"], p["unit"]) == (spec["layer"], spec["unit"])
        assert spec["read"]["from"] == "perf"  # data, the general reader
        assert not os.path.exists(manifest.metric_path(name)[:-5] + ".py")


def test_the_cell_reports_the_pass_and_its_layers():
    e2e = {e["name"]: e for e in entries()["end_to_end"]}
    assert CELL in e2e["audit_pass_s"]["workloads"]
    reported = {p["name"] for p in manifest.Cell(CELL).per_layer}
    assert set(NEW) <= reported
    assert {"sweep_device_roofline", "fold_render.renders_per_pass",
            "device.idle_share_audit", "entry.compiles_in_window"} <= reported


# --- the readers -------------------------------------------------------------

def read(name: str, evaluator: dict, passes: int = 2, objects: int = 1000):
    spec = manifest.read_json(manifest.metric_path(name))
    obs = {"perf": {"manager": {}, "evaluator": evaluator},
           "passes": passes, "objects": objects, "constraints": 500,
           "spans": [], "trace": None}
    out = readers.read_all([spec], obs)
    return out[name]["value"] if name in out else None


# evaluator.perf of the parent of PR 27 and of the change, two passes of
# 1,000 objects
PARENT = {"masks": 24.0, "wire_pack": 1.0, "wire_bytes": 500000.0}
CHANGE = {"masks": 0.5, "wire_pack": 1.0, "wire_bytes": 500000.0,
          "mask_rows_fast": 8000.0, "mask_rows_slow": 0.0,
          "mask_wire_bytes": 124000.0}


def test_masks_seconds_read_on_the_parent_and_on_the_change():
    assert read("masks.busy_s_per_pass", PARENT) == 12.0
    assert read("masks.busy_s_per_pass", CHANGE) == 0.25
    assert read("masks.busy_s_per_pass", {}) is None
    assert read("masks.busy_s_per_pass", CHANGE, passes=0) is None


@pytest.mark.parametrize("fast,slow,want", [
    (8000.0, 0.0, 0.0), (7900.0, 100.0, 0.0125), (0.0, 8000.0, 1.0),
    (0.0, 0.0, None)])
def test_slow_row_share_is_slow_over_all_rows(fast, slow, want):
    got = read("masks.slow_row_share",
               dict(CHANGE, mask_rows_fast=fast, mask_rows_slow=slow))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", ["masks.slow_row_share",
                                  "pack_h2d.mask_bytes_per_object"])
def test_a_counter_metric_is_left_out_on_a_tree_without_the_counters(name):
    assert read(name, PARENT) is None
    assert read(name, dict(PARENT, mask_rows_slow=0.0)) is None


def test_mask_bytes_are_per_object_swept():
    assert read("pack_h2d.mask_bytes_per_object", CHANGE) == 62.0
    assert read("pack_h2d.mask_bytes_per_object", CHANGE,
                passes=0) is None


# --- the cell, end to end at toy size ------------------------------------------

@pytest.mark.slow
def test_rehearse_the_cell(capsys):
    """The whole audit path at toy sizes on whatever JAX finds: the 500
    constraints, the corpus over 200 namespaces, the reference children,
    the sample's audit, the window, the readers.  A minute and a half."""
    from benchmark import run as run_py

    assert run_py.main(["--workload", CELL, "--rehearse", "--seed",
                        "2147483999", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert "rehearsal" in line
    metrics = line["metrics"]
    assert set(NEW) <= set(metrics)
    assert metrics["masks.slow_row_share"]["value"] == 0.0
    assert metrics["entry.compiles_in_window"]["value"] == 0.0
    assert metrics["pack_h2d.mask_bytes_per_object"]["value"] > 30

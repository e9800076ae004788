"""``library-cel`` and its cell ``cel.audit-sweep`` (PR 34), after
``test_c500sel_library.py``: the committed library is what
``benchmark/libraries/make_cel.py`` writes, its roster is 38 kinds on CEL and
8 on Rego, the configuration is ``library-full``'s with the library replaced,
the manifest resolves with the cell listing what its control lists plus the
one new metric, and that metric reads what it should.  Nothing here times the
system under test."""

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

from benchmark import manifest, readers, wiring  # noqa: E402
from benchmark.libraries import make_cel  # noqa: E402

CELL = "cel.audit-sweep"
CONTROL = "full.audit-sweep"
NEW = "sweep_device.cel_row_share"
COMMITTED = os.path.join(ROOT, "benchmark", "libraries", "cel")
ENGINE = "K8sNativeValidation"
# the Pod-scope kinds: each must be among the CEL constraints
POD_SCOPE = {
    "allowedrepos", "automounttoken", "capabilities", "containerlimits",
    "containerrequests", "containerresources", "disallowedrepos",
    "disallowedtags", "disallowinteractivetty", "ephemeralstoragelimit",
    "forbiddensysctls", "hostfilesystem", "hostnamespace",
    "hostnetworkingports", "imagedigests", "readonlyrootfilesystem",
    "requiredprobes", "allowprivilegeescalation", "apparmor", "flexvolumes",
    "fsgroup", "procmount", "seccomp", "selinux", "users", "volumes"}


def config(name: str = "library-cel") -> dict:
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           name + ".json"))


def entries() -> dict:
    return manifest.read_json(manifest.MANIFEST)


def template_of(directory: str) -> dict:
    with open(os.path.join(directory, "template.yaml")) as f:
        return yaml.safe_load(f)


# --- the library ------------------------------------------------------------

def test_the_generator_reproduces_the_committed_files(tmp_path):
    assert make_cel.write(str(tmp_path)) == len(make_cel.POLICIES)
    names = sorted(os.listdir(COMMITTED))
    assert sorted(os.listdir(tmp_path)) == names == sorted(make_cel.POLICIES)
    for name in names:
        for rel in ("template.yaml",
                    os.path.join("samples", "constraint.yaml")):
            assert filecmp.cmp(os.path.join(tmp_path, name, rel),
                               os.path.join(COMMITTED, name, rel),
                               shallow=False), (name, rel)
        assert sorted(os.listdir(os.path.join(COMMITTED, name))) == [
            "samples", "template.yaml"]


@pytest.mark.parametrize("name", sorted(make_cel.POLICIES))
def test_a_template_is_the_stock_one_with_a_cel_block_and_no_rego(name):
    area = make_cel.POLICIES[name][0]
    stock_dir = os.path.join(wiring.LIBRARY, area, name)
    stock, mine = template_of(stock_dir), template_of(
        os.path.join(COMMITTED, name))
    # its own kind name, parameter schema and metadata
    assert mine["metadata"] == stock["metadata"]
    assert mine["spec"]["crd"] == stock["spec"]["crd"]
    (target,) = mine["spec"]["targets"]
    assert set(target) == {"target", "code"}  # no Rego beside the block
    assert target["target"] == stock["spec"]["targets"][0]["target"]
    (block,) = target["code"]
    assert block["engine"] == ENGINE
    source = block["source"]
    assert source["failurePolicy"] == "Fail" and source["validations"]
    assert "matchConditions" not in source and "matchCondition" not in source
    # the sample constraint is the stock one, unchanged
    assert filecmp.cmp(
        os.path.join(COMMITTED, name, "samples", "constraint.yaml"),
        os.path.join(stock_dir, "samples", "constraint.yaml"), shallow=False)


def test_pod_scope_blocks_are_written_in_upstreams_idiom():
    for name in sorted(POD_SCOPE & set(make_cel.POLICIES)):
        source = template_of(os.path.join(COMMITTED, name))[
            "spec"]["targets"][0]["code"][0]["source"]
        variables = {v["name"]: v["expression"]
                     for v in source.get("variables", [])}
        text = json.dumps(source)
        assert "variables.anyObject" in text, name
        assert "object." not in text.replace("anyObject.", ""), name
        if "containers" in variables:
            assert variables["containers"] == (
                "has(variables.anyObject.spec.containers) ? "
                "variables.anyObject.spec.containers : []")
            bad = variables["badContainers"]
            assert ".filter(container, " in bad and ".map(container, " in bad
            assert {"expression": "size(variables.badContainers) == 0",
                    "messageExpression":
                        'variables.badContainers.join("\\n")'} \
                in source["validations"]
        if "exemptImages" in variables:
            assert {"exemptImagePrefixes", "exemptImageExplicit"} <= set(
                variables)
            assert "ephemeralContainers" in variables
            assert 'string(image).replace("*", "")' in variables[
                "exemptImagePrefixes"]


def test_the_roster_is_38_on_cel_and_8_on_rego():
    lib = config()["library"]
    dirs = wiring.template_dirs(config())
    assert len(dirs) == len(set(dirs)) == 46
    assert lib["expect"] == {"templates": 46, "constraints": 46,
                             "on_interpreter_fallback": 0}
    assert lib["templates"] == make_cel.config_templates()
    on_cel, on_rego, kinds = [], [], set()
    for d in dirs:
        doc = template_of(d)
        kinds.add(doc["spec"]["crd"]["spec"]["names"]["kind"])
        target = doc["spec"]["targets"][0]
        engines = {c["engine"] for c in target.get("code", [])}
        if "rego" in target:
            # the program's client takes the Rego block of a template
            # that has both: no template of this library has both
            assert ENGINE not in engines, d
            on_rego.append(os.path.relpath(d, wiring.LIBRARY))
        else:
            assert engines == {ENGINE}, d
            on_cel.append(os.path.basename(d))
    assert len(kinds) == 46
    kept = config()["assumed"]["library.rego_kept"]
    assert sorted(on_rego) == sorted(kept) == sorted(make_cel.REGO_KEPT)
    assert len(on_rego) == 8 and len(on_cel) == 38 >= 32
    assert POD_SCOPE <= set(on_cel)
    assert set(make_cel.CEL_STOCK) == {"general/containerlimitscel",
                                       "general/noprivileged"}
    # the kinds are the stock library's own 46
    stock = {template_of(d)["spec"]["crd"]["spec"]["names"]["kind"]
             for d in wiring.template_dirs(config("library-full"))}
    assert kinds == stock


def test_the_configuration_is_library_fulls_with_the_library_replaced():
    full, cel = config("library-full"), config()
    differ = {k for k in set(full) | set(cel) if full.get(k) != cel.get(k)}
    assert differ == {"name", "source", "source_detail", "deployment",
                      "library", "assumed", "guarantees"}
    g_full, g_cel = dict(full["guarantees"]), dict(cel["guarantees"])
    assert "CEL evaluator" in g_cel.pop("verdicts")
    g_full.pop("verdicts")
    assert g_full == g_cel
    extra = set(cel["assumed"]) - set(full["assumed"])
    assert extra == {"library.cel_text", "library.engine",
                     "library.rego_kept", "library.cel_stock"}
    assert all(cel["assumed"][k] == v for k, v in full["assumed"].items())
    assert cel["objects"] == 262144 and list(cel["reduced"]) == ["objects"]
    assert cel["audit"] == {"chunk_size": 32768, "violations_limit": 20,
                            "exact_totals": False}
    assert cel["referential_kinds"] == ["Ingress"]


# --- the manifest -------------------------------------------------------------

def test_the_manifest_resolves_and_holds_the_cell_and_its_configuration():
    assert manifest.check() == []
    m = entries()
    cells = [w["name"] for w in m["workloads"]]
    # containment and relative order: every older cell stands before it
    older = ["full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
             "c500sel.audit-sweep"]
    assert [c for c in cells if c in older] == older
    assert all(cells.index(CELL) > cells.index(c) for c in older)
    by_name = {c["name"]: c for c in m["configs"]}
    assert set(by_name) >= {"library-full", "psp-pods", "library-c500",
                            "library-c500sel", "library-cel"}
    entry = by_name["library-cel"]
    assert entry["file"] == "benchmark/configs/library-cel.json"
    assert entry["source"] == config()["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["objects"]
    # what the deselected pin of test_c500sel_library.py held, as containment
    sel = by_name["library-c500sel"]
    assert sel["file"] == "benchmark/configs/library-c500sel.json"
    assert sel["source"] == config("library-c500sel")["source"]
    assert len(sel["source"]) <= 200 and sel["reduced"] == ["objects"]
    sel_work = {w["name"]: w for w in m["workloads"]}["c500sel.audit-sweep"]
    assert sel_work == {
        "name": "c500sel.audit-sweep", "config": "library-c500sel",
        "traffic": "audit-sweep", "chips": 1, "why": sel_work["why"]}
    assert "131072 objects x 500 constraints" in sel_work["why"]
    assert {e["name"] for e in manifest.Cell(
        "c500sel.audit-sweep").end_to_end} == {"audit_pass_s", "setup_s"}
    work = {w["name"]: w for w in m["workloads"]}[CELL]
    assert work == {"name": CELL, "config": "library-cel",
                    "traffic": "audit-sweep", "chips": 1,
                    "why": work["why"]}
    assert "262144 objects x 46 constraints" in work["why"]
    assert CONTROL in work["why"] and len(work["why"]) <= 200
    cell = manifest.Cell(CELL)
    assert {e["name"] for e in cell.end_to_end} == {"audit_pass_s",
                                                    "setup_s"}


def test_the_cell_lists_what_its_control_lists_and_the_new_metric():
    m = entries()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert {CONTROL, CELL} <= set(e2e["audit_pass_s"]["workloads"])
    control = [p["name"] for p in manifest.Cell(CONTROL).per_layer]
    mine = [p["name"] for p in manifest.Cell(CELL).per_layer]
    assert mine == control and NEW in mine
    per_layer = {p["name"]: p for p in m["per_layer"]}
    entry = dict(per_layer[NEW])
    assert set(entry.pop("workloads")) >= {
        "full.audit-sweep", "psp.audit-sweep", "c500.audit-sweep",
        "c500sel.audit-sweep", CELL}
    assert entry == {"name": NEW, "unit": "1", "better": "higher",
                     "source": "program_counter", "layer": "sweep_device",
                     "moves": "audit_pass_s"}
    # appended: the layer's older entries stand before it
    names = [p["name"] for p in m["per_layer"]]
    for older in ("sweep_device.busy_s_per_pass", "sweep_device_roofline",
                  "pack_h2d.fused_share"):
        assert names.index(NEW) > names.index(older)


# --- the new metric -----------------------------------------------------------

def metric() -> dict:
    with open(manifest.metric_path(NEW)) as f:
        return json.load(f)


def read(evaluator: dict, passes: int = 2):
    obs = {"perf": {"manager": {}, "evaluator": evaluator}, "passes": passes,
           "objects": 1000, "constraints": 46, "spans": [], "trace": None}
    out = readers.read_all([metric()], obs)
    return out[NEW]["value"] if NEW in out else None


def test_it_is_data_with_the_general_reader():
    spec = metric()
    assert spec["read"] == {
        "from": "perf", "of": "evaluator", "keys": ["sweep_rows_cel"],
        "over": {"of": "evaluator", "keys": ["sweep_rows"]}}
    assert spec["layer"] == "sweep_device" and spec["unit"] == "1"
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NEW + ".py"))


@pytest.mark.parametrize("cel,rows,want", [
    (4000.0, 5000.0, 0.8),   # most rows of the window lowered from CEL
    (216.0, 5000.0, 0.0432),  # the control: two CEL rows in the Pod group
    (0.0, 5000.0, 0.0),      # a library with no CEL kind
])
def test_share_is_cel_rows_over_all_rows(cel, rows, want):
    got = read({"sweep_rows_cel": cel, "sweep_rows": rows})
    assert got == pytest.approx(want)


def test_a_tree_without_the_counters_reads_nothing():
    # the parent: neither counter; the metric is left out, nothing raises
    assert read({"mask_rows_fast": 10.0}) is None
    assert read({}) is None


# --- the cell, end to end at toy size -------------------------------------------

@pytest.mark.slow
def test_rehearse_the_cell(capsys):
    """The whole audit path at toy sizes on whatever JAX finds: the 46
    templates of which 38 are CEL, the corpus, the reference children (the
    CEL evaluator and the Rego interpreter), the sample's audit, the window,
    the readers.  A minute and a half (slow-marked as the controls' are)."""
    from benchmark import run as run_py

    assert run_py.main(["--workload", CELL, "--rehearse", "--seed",
                        "2147483999", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert "rehearsal" in line
    metrics = line["metrics"]
    # (all but the roofline share, which has no peak off the chip)
    listed = {p["name"] for p in manifest.Cell(CELL).per_layer}
    assert listed - set(metrics) <= {"sweep_device_roofline"}
    assert metrics[NEW]["value"] > 0.6
    assert metrics["masks.slow_row_share"]["value"] == 0.0
    assert metrics["fold_render.memo_hit_share"]["value"] == 1.0
    assert metrics["entry.compiles_in_window"]["value"] == 0.0

"""A toy ``library-c500`` (the 46 templates, three constraints each where
the set has them: the cluster-wide baseline, a tenant's exact namespace
list, a tenant's prefix glob) over 2,048 objects of the configuration's
generator, through ``AuditManager.audit()`` on the reduced lane, against
the interpreter alone: totals and kept violations, messages and order
included (the benchmark's own comparison, ``correct`` (a)).  And the
counters the match layer keeps for a pass."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import audit as bench_audit  # noqa: E402
from benchmark import cluster, manifest, reference  # noqa: E402
from benchmark.libraries import make_c500  # noqa: E402
from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP  # noqa: E402
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager  # noqa: E402
from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.drivers.cel_driver import CELDriver  # noqa: E402
from gatekeeper_tpu.drivers.rego_driver import RegoDriver  # noqa: E402
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.observability import tracing  # noqa: E402
from gatekeeper_tpu.parallel import sharded  # noqa: E402
from gatekeeper_tpu.target.target import K8sValidationTarget  # noqa: E402
from gatekeeper_tpu.utils.unstructured import load_yaml_file  # noqa: E402

N_OBJECTS = 2048
CHUNK = 512
LIMIT = 20


def toy_constraints() -> dict:
    """{template: its baseline, its first exact-list tenant constraint,
    its first glob tenant constraint}."""
    out = {}
    for name, docs in make_c500.constraint_set().items():
        tenants = docs[1:]
        exact = [d for d in tenants
                 if len(d["spec"]["match"]["namespaces"]) > 1][:1]
        glob = [d for d in tenants
                if len(d["spec"]["match"]["namespaces"]) == 1][:1]
        out[name] = docs[:1] + exact + glob
    return out


def load(client) -> int:
    n = 0
    docs = toy_constraints()
    for name, path in make_c500.templates():
        client.add_template(load_yaml_file(
            os.path.join(path, "template.yaml"))[0])
        for doc in docs[name]:
            client.add_constraint(doc)
            n += 1
    return n


def build_world() -> dict:
    cfg = manifest.read_json(os.path.join(
        ROOT, "benchmark", "configs", "library-c500.json"))
    objects = list(cluster.Cluster(cfg["cluster"], N_OBJECTS,
                                   seed=27).objects(0))
    lines = [b"%d\t" % i + cluster.dumps(o) for i, o in enumerate(objects)]
    inventory = [o for o in objects if o["kind"] in cfg["referential_kinds"]]
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    n_constraints = load(client)
    assert not tpu.fallback_kinds()
    interp = Client(target=K8sValidationTarget(),
                    drivers=[RegoDriver(), CELDriver()],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    load(interp)
    for obj in inventory:
        client.add_data(obj)
        interp.add_data(obj)
    return {"client": client, "tpu": tpu, "interp": interp,
            "objects": objects, "lines": lines, "n": n_constraints}


@pytest.fixture(scope="module")
def world():
    return build_world()


def test_the_toy_set_has_three_constraints_a_template(world):
    # 42 templates x 3, and the four whose kind the cluster lacks x 1
    assert world["n"] == 42 * 3 + 4
    assert len(world["client"].constraints()) == world["n"]


def test_reduced_audit_is_the_interpreters(world, monkeypatch):
    from gatekeeper_tpu.utils.rawjson import RawJSON

    ev = sharded.ShardedEvaluator(world["tpu"], sharded.make_mesh(1),
                                  violations_limit=LIMIT, collect="reduced")
    raws = [line.partition(b"\t")[2] for line in world["lines"]]
    mgr = AuditManager(
        world["client"], lister=lambda: (RawJSON(r) for r in raws),
        config=AuditConfig(violations_limit=LIMIT, chunk_size=CHUNK,
                           pipeline="on", exact_totals=False),
        evaluator=ev)
    packed = []
    real_packbits = np.packbits

    def packbits(a, *args, **kw):
        out = real_packbits(a, *args, **kw)
        if a.dtype == np.bool_ and a.ndim == 2:
            packed.append((a.shape[0], out.nbytes))
        return out

    monkeypatch.setattr(sharded.np, "packbits", packbits)
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        got = mgr.audit()
    monkeypatch.undo()
    assert not got.incomplete and got.total_objects == N_OBJECTS

    # --- against the interpreter, as the benchmark's `correct` (a) --------
    results: dict = {}
    for idx, rows in reference.audit_results(world["interp"],
                                             world["lines"]):
        for kind, name, msg in rows:
            results.setdefault(idx, {}).setdefault(
                (kind, name), []).append(msg)
    ident = {i: (o["kind"], o["metadata"].get("namespace", ""),
                 o["metadata"]["name"])
             for i, o in enumerate(world["objects"])}
    problems = bench_audit.sample_audit_problems(
        got, list(range(N_OBJECTS)), results, ident, LIMIT)
    assert problems == []
    violated = {key for per in results.values() for key in per}
    # a mostly compliant cluster; at this size a rare tenant owns a few
    # dozen objects, and still the scoped constraints see something
    scoped = [k for k in violated if k[1][0] == "t" and k[1][3] == "-"]
    assert len(violated) >= 50 and len(scoped) >= 15, (len(violated),
                                                       len(scoped))

    # --- the counters of the match layer -----------------------------------
    perf = ev.perf
    router = sharded.make_kind_router(world["client"].constraints())
    by_group: dict = {}
    for obj in world["objects"]:
        g = router(obj["kind"])
        if g:
            by_group[g] = by_group.get(g, 0) + 1
    rows = 0
    for g, n in by_group.items():
        c_g = sum(1 for c in world["client"].constraints() if c.kind in g)
        rows += -(-n // CHUNK) * c_g
    assert perf["mask_rows_slow"] == 0
    assert perf["mask_rows_fast"] + perf["mask_rows_slow"] == rows
    # every dispatch packed one mask, and its bytes are what wire_bytes
    # holds beside the columns
    assert sum(c for c, _ in packed) == rows
    assert perf["mask_wire_bytes"] == sum(b for _, b in packed)
    assert 0 < perf["mask_wire_bytes"] < perf["wire_bytes"]
    spans = [s for t in tracer.traces() for s in t["spans"]
             if s["name"] == "device.sweep_dispatch.masks"]
    assert len(spans) == len(packed)
    assert sum(s["attributes"]["constraints"] for s in spans) == rows
    for s in spans:
        a = s["attributes"]
        assert a["rows_vectorized"] == a["constraints"]
        assert a["rows_predicate"] == 0


def test_a_selector_constraint_is_counted_and_stays_exact(world):
    """One labelSelector constraint joins the set: its row is answered
    from the selector table (until PR 32 by the per-object predicate) in
    every chunk of its group, and the verdicts stay the interpreter's."""
    from gatekeeper_tpu.apis.constraints import Constraint

    doc = json.loads(json.dumps(
        make_c500.constraint_set()["blocknodeport"][0]))
    doc["metadata"]["name"] = "selected-block-node-port"
    doc["spec"]["match"]["labelSelector"] = {
        "matchExpressions": [{"key": "app", "operator": "DoesNotExist"}]}
    con = Constraint.from_unstructured(doc)
    services = [o for o in world["objects"] if o["kind"] == "Service"]
    cons = [c for c in world["client"].constraints()
            if c.kind == "K8sBlockNodePort"] + [con]
    ev = sharded.ShardedEvaluator(world["tpu"], sharded.make_mesh(1),
                                  violations_limit=LIMIT, collect="reduced")
    swept = ev.sweep(cons, services, return_bits=True)
    kcons, _idx, _valid, _counts, hits = swept["K8sBlockNodePort"]
    assert ev.perf["mask_rows_slow"] == 0
    assert ev.perf["mask_rows_fast"] == len(cons)
    assert ev.perf["mask_rows_selector"] == 1
    ci = [c.name for c in kcons].index("selected-block-node-port")
    got = set(sharded.violation_rows(hits, ci, len(services)).tolist())
    want = {i for i, s in enumerate(services)
            if s["spec"]["type"] == "NodePort"
            and s["metadata"].get("namespace") not in make_c500.EXCLUDED
            and not s["metadata"]["namespace"].startswith("ns-19")}
    assert want and got == want

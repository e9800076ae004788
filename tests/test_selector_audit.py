"""A toy ``library-c500sel`` (six templates of the set with all their rows:
the baseline that exempts by label and carries a scope, the tenant rows
that select by ``namespaceSelector``, some by ``labelSelector`` too) over
2,048 objects of the configuration's generator, through
``AuditManager.audit()`` on the reduced lane with the Namespaces synced
(``Client.add_data``), against the interpreter alone: totals and kept
violations, messages and order included.  Then one Namespace changes its
``tenant`` label, through ``Client.add_data`` and in the cluster's listing,
and the next pass follows the label, still equals the interpreter's, and
renders anew only what it has not rendered before."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import audit as bench_audit  # noqa: E402
from benchmark import cluster, manifest, reference  # noqa: E402
from benchmark.libraries import make_c500, make_c500sel  # noqa: E402
from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP  # noqa: E402
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager  # noqa: E402
from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.drivers.cel_driver import CELDriver  # noqa: E402
from gatekeeper_tpu.drivers.rego_driver import RegoDriver  # noqa: E402
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.match.match import MatchError  # noqa: E402
from gatekeeper_tpu.observability import tracing  # noqa: E402
from gatekeeper_tpu.parallel import sharded  # noqa: E402
from gatekeeper_tpu.target.target import K8sValidationTarget  # noqa: E402
from gatekeeper_tpu.utils.rawjson import RawJSON  # noqa: E402
from gatekeeper_tpu.utils.unstructured import load_yaml_file  # noqa: E402

N_OBJECTS = 2048
CHUNK = 512
LIMIT = 20
# a template per kind group of the cluster; none reads data.inventory, so
# a synced Namespace changes no render, only who is matched
TEMPLATES = ["containerlimits", "blocknodeport", "httpsonly",
             "replicalimits", "requiredlabels", "disallowanonymous"]


def load(client) -> int:
    n = 0
    docs = make_c500sel.constraint_set()
    paths = dict(make_c500.templates())
    for name in TEMPLATES:
        client.add_template(load_yaml_file(
            os.path.join(paths[name], "template.yaml"))[0])
        for doc in docs[name]:
            client.add_constraint(doc)
            n += 1
    return n


def build_world() -> dict:
    cfg = manifest.apply_rehearsal(manifest.read_json(os.path.join(
        ROOT, "benchmark", "configs", "library-c500sel.json")))
    objects = list(cluster.Cluster(cfg["cluster"], N_OBJECTS,
                                   seed=32).objects(0))
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
    return {"client": client, "tpu": tpu, "interp": interp,
            "objects": objects, "n": n_constraints,
            "referential": cfg["referential_kinds"]}


@pytest.fixture(scope="module")
def world():
    return build_world()


def interpreter_problems(world, got) -> list:
    """The benchmark's own comparison (``correct`` (a)) of a pass with the
    interpreter's review of every object."""
    objects = world["objects"]
    lines = [b"%d\t" % i + cluster.dumps(o) for i, o in enumerate(objects)]
    results: dict = {}
    for idx, rows in reference.audit_results(world["interp"], lines):
        for kind, name, msg in rows:
            results.setdefault(idx, {}).setdefault(
                (kind, name), []).append(msg)
    ident = {i: (o["kind"], o["metadata"].get("namespace", ""),
                 o["metadata"]["name"]) for i, o in enumerate(objects)}
    return bench_audit.sample_audit_problems(
        got, list(range(len(objects))), results, ident, LIMIT)


def manager(world, ev) -> AuditManager:
    return AuditManager(
        world["client"],
        lister=lambda: (RawJSON(cluster.dumps(o)) for o in world["objects"]),
        config=AuditConfig(violations_limit=LIMIT, chunk_size=CHUNK,
                           pipeline="on", exact_totals=False),
        evaluator=ev)


def renders(mgr) -> tuple:
    """(interpreter renders, renders the memo answered) so far."""
    return mgr.perf["n_renders"], mgr.perf["render_memo_hits"]


def kept_pairs(got) -> set:
    return {(key, v.kind, v.namespace, v.name)
            for key, vs in got.kept.items() for v in vs}


def test_without_the_namespaces_the_pass_raises_as_the_interpreter_does(
        world):
    """Nothing synced yet: a namespaced object under a namespaceSelector
    has no Namespace to be selected on, which is an error of the oracle's
    and of the sweep's, never a silent match or miss."""
    ev = sharded.ShardedEvaluator(world["tpu"], sharded.make_mesh(1),
                                  violations_limit=LIMIT)
    pods = [o for o in world["objects"] if o["kind"] == "Pod"][:64]
    cons = [c for c in world["client"].constraints()
            if c.kind == "K8sContainerLimits"]
    with pytest.raises(MatchError, match="missing Namespace"):
        ev.sweep(cons, pods)
    assert ev.perf["mask_ns_missing"] == 64
    assert ev.perf["mask_rows_slow"] == len(cons)
    with pytest.raises(MatchError, match="missing Namespace"):
        world["interp"].review(pods[0], enforcement_point=AUDIT_EP)


def test_the_sweep_follows_a_namespaces_label(world):
    objects = world["objects"]
    for obj in objects:
        if obj["kind"] in world["referential"]:
            world["client"].add_data(obj)
            world["interp"].add_data(obj)
    ev = sharded.ShardedEvaluator(world["tpu"], sharded.make_mesh(1),
                                  violations_limit=LIMIT)
    mgr = manager(world, ev)
    # the manager hands the evaluator the target's Namespace cache
    assert ev.namespace_of == world["client"].target.cache.get
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        first = mgr.audit()
    assert not first.incomplete and first.total_objects == N_OBJECTS
    assert interpreter_problems(world, first) == []

    # every row went through a selector table, none through the predicate
    perf = ev.perf
    assert perf["mask_rows_slow"] == 0 and perf["mask_ns_missing"] == 0
    assert perf["mask_rows_selector"] == perf["mask_rows_fast"] > 0
    assert perf["masks_selector"] > 0
    spans = [s for t in tracer.traces() for s in t["spans"]]
    masks = {s["span_id"] for s in spans
             if s["name"] == "device.sweep_dispatch.masks"}
    selectors = [s for s in spans
                 if s["name"] == "ir.masks.selectors"]
    assert selectors and all(s["parent_id"] in masks for s in selectors)
    assert sum(s["attributes"]["rows_selector"]
               for s in selectors) == perf["mask_rows_selector"]
    assert all(s["attributes"]["namespaces"] <= 40 for s in selectors)
    assert any(s["attributes"]["namespaces"] for s in selectors)
    assert any(s["attributes"]["label_sets"] for s in selectors)
    renders_1, hits = renders(mgr)
    assert renders_1 >= len(kept_pairs(first)) > 0 and hits == 0

    # the same cluster again: the memo answers every render
    again = mgr.audit()
    assert again.total_violations == first.total_violations
    assert kept_pairs(again) == kept_pairs(first)
    assert renders(mgr) == (renders_1, renders_1)

    # ns-0, the most populous namespace, joins tenant t13 and then leaves it
    # for t14 (both have a row of K8sContainerLimits; t0 has none in this
    # toy): relabelled in the cluster (the lister hands the relabelled
    # object over) and synced, as a watch would
    at = next(i for i, o in enumerate(objects)
              if o["kind"] == "Namespace"
              and o["metadata"]["name"] == "ns-0")
    assert objects[at]["metadata"]["labels"]["tenant"] == "t0"
    row = {t: ("K8sContainerLimits", f"t{t}-container-must-have-limits")
           for t in (13, 14)}
    before = first
    seen = (renders_1, renders_1)
    for tenant, left in ((13, None), (14, 13)):
        moved = json.loads(json.dumps(objects[at]))
        moved["metadata"]["labels"]["tenant"] = f"t{tenant}"
        objects[at] = moved
        world["client"].add_data(moved)
        world["interp"].add_data(moved)
        got = mgr.audit()
        assert interpreter_problems(world, got) == []
        # the tenant's row counts ns-0's Pods now; the row of the tenant
        # it left counts what it counted at first; every other row is as
        # it was
        gained = got.total_violations[row[tenant]]
        assert gained >= first.total_violations[row[tenant]] + 3
        if left is not None:
            assert got.total_violations[row[left]] \
                == first.total_violations[row[left]]
            assert {p for p in kept_pairs(got) if p[0] == row[left]} \
                == {p for p in kept_pairs(first) if p[0] == row[left]}
        assert all(got.total_violations[k] == n
                   for k, n in first.total_violations.items()
                   if k not in row.values())
        # nothing stale: a kept violation was rendered before for this
        # very constraint and object (the memo's), or is rendered now
        new_pairs = kept_pairs(got) - kept_pairs(before)
        assert new_pairs and all(p[0] == row[tenant] and p[2] == "ns-0"
                                 for p in new_pairs)
        now = renders(mgr)
        rendered, answered = now[0] - seen[0], now[1] - seen[1]
        assert 0 < rendered <= len(new_pairs) and answered > 0
        assert rendered + answered >= len(kept_pairs(got))
        before, seen = got, now

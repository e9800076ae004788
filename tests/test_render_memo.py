"""The audit's render memo (``audit/render_memo.py``, ``AuditManager.
_render_fn``, ``TpuDriver.render_token``) on the toy ``library-c500``
world of ``tests/test_c500_toy_audit.py``, through ``AuditManager.
audit()`` on the reduced lane: a pass evaluates the interpreter only for
what changed since the last one, a hit returns what the miss returned,
every input of a render is in the key or forces a bypass, and the memo is
bounded by what a pass asks for."""

from __future__ import annotations

import contextlib
import copy
import json
import os
import random

import pytest

from benchmark import audit as bench_audit
from benchmark import cluster, reference
from benchmark.libraries import make_c500
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.audit.render_memo import RenderMemo
from gatekeeper_tpu.client.types import QueryResponse, Result
from gatekeeper_tpu.drivers.render_token import RenderToken
from gatekeeper_tpu.match.match import SOURCE_GENERATED, SOURCE_ORIGINAL
from gatekeeper_tpu.observability import tracing
from gatekeeper_tpu.parallel import sharded
from gatekeeper_tpu.utils.rawjson import RawJSON
from gatekeeper_tpu.utils.unstructured import load_yaml_file
from tests.test_c500_toy_audit import CHUNK, LIMIT, N_OBJECTS, build_world


@pytest.fixture(scope="module")
def world():
    w = build_world()
    w["ev"] = sharded.ShardedEvaluator(
        w["tpu"], sharded.make_mesh(1), violations_limit=LIMIT,
        collect="reduced")
    return w


class Corpus:
    """The listed cluster as bytes, one fresh unloaded ``RawJSON`` an
    object a pass, as a lister over the apiserver hands them over."""

    def __init__(self, world):
        self.world = world
        self.raws = [ln.partition(b"\t")[2] for ln in world["lines"]]

    def lister(self):
        return (RawJSON(r) for r in self.raws)

    def manager(self, evaluator=None, **cfg) -> AuditManager:
        cfg = {"violations_limit": LIMIT, "chunk_size": CHUNK,
               "pipeline": "on", "exact_totals": False, **cfg}
        return AuditManager(self.world["client"], lister=self.lister,
                            config=AuditConfig(**cfg),
                            evaluator=evaluator or self.world["ev"])

    def index_of(self, kind, namespace, name) -> int:
        for i, raw in enumerate(self.raws):
            o = json.loads(raw)
            m = o["metadata"]
            if (o["kind"], m.get("namespace", ""), m["name"]) == \
                    (kind, namespace, name):
                return i
        raise KeyError((kind, namespace, name))

    def edit(self, i, fn) -> None:
        obj = json.loads(self.raws[i])
        fn(obj)
        self.raws[i] = cluster.dumps(obj)

    def churn(self, share: float, rng, stamp: str) -> set:
        """Replace ``share`` of the objects: every one gets other bytes,
        every second one loses its labels and so changes its verdicts.
        Returns the new bytes."""
        new = set()
        picked = rng.sample(range(len(self.raws)),
                            max(1, int(share * len(self.raws))))
        for n, i in enumerate(picked):
            def fn(obj, n=n):
                obj["metadata"].setdefault("annotations", {})["churn"] = \
                    stamp
                if n % 2:
                    obj["metadata"].pop("labels", None)
            self.edit(i, fn)
            new.add(self.raws[i])
        return new


@contextlib.contextmanager
def interpreter_renders(tpu):
    """Every render that reaches the interpreter while the block runs, as
    (constraint key, the bytes of the object)."""
    seen: list = []
    real = tpu.render_query

    def render_query(target, constraint, review, cfg=None):
        obj = review.request.object
        seen.append((constraint.key(),
                     obj.raw if isinstance(obj, RawJSON) else None))
        return real(target, constraint, review, cfg)

    tpu.render_query = render_query
    try:
        yield seen
    finally:
        del tpu.render_query


def canon(run) -> tuple:
    """Totals and kept violations, messages, details and order included."""
    return dict(run.total_violations), {
        key: [(v.message, json.dumps(v.details, sort_keys=True), v.kind,
               v.namespace, v.name, v.enforcement_action) for v in vs]
        for key, vs in run.kept.items()}


def counters(mgr) -> tuple:
    p = mgr.perf
    return (p.get("render_memo_hits", 0), p.get("n_renders", 0),
            p.get("render_memo_bypass", 0))


# --- (i) a second pass over the same bytes -----------------------------------

def test_second_pass_over_the_same_bytes_renders_nothing(world):
    corpus = Corpus(world)
    mgr = corpus.manager()
    with interpreter_renders(world["tpu"]) as missed:
        first = mgr.audit()
    hits, n1, bypass = counters(mgr)
    assert (hits, bypass) == (0, 0) and n1 == len(missed) > 200
    assert mgr.perf["render"] > 0.0
    assert len(mgr._render_memo.prev) == n1 and not mgr._render_memo.cur

    mgr.perf = {}
    tracer = tracing.Tracer(seed=0)
    with interpreter_renders(world["tpu"]) as missed, \
            tracing.activate(tracer):
        second = mgr.audit()
    assert missed == []
    assert counters(mgr) == (n1, 0, 0)
    assert mgr.perf["render"] == 0.0
    assert canon(second) == canon(first)
    folds = [s for t in tracer.traces() for s in t["spans"]
             if s["name"] == "pipeline.stage.fold_render"]
    assert sum(s["attributes"]["render_memo_hits"] for s in folds) == n1

    # and both are the interpreter's, by the benchmark's `correct` (a)
    results: dict = {}
    for idx, rows in reference.audit_results(world["interp"],
                                             world["lines"]):
        for kind, name, msg in rows:
            results.setdefault(idx, {}).setdefault(
                (kind, name), []).append(msg)
    ident = {i: (o["kind"], o["metadata"].get("namespace", ""),
                 o["metadata"]["name"])
             for i, o in enumerate(world["objects"])}
    assert bench_audit.sample_audit_problems(
        second, list(range(N_OBJECTS)), results, ident, LIMIT) == []


@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_a_pass_of_nothing_but_hits_loads_no_kept_object(world, pipeline):
    """The memo answers every render and ``_violation`` reads the kept
    objects' names off their bytes (``utils/rawjson.peek_identity``), so
    the fold loads nothing: every object the second pass listed is still
    unloaded, still empty and still off the collector's lists."""
    import gc

    from gatekeeper_tpu.ops import native

    if native.load_listroute() is None:
        pytest.skip("native/listroutemod.c does not build here")
    corpus = Corpus(world)
    listed: list = []

    def lister():
        listed.clear()
        for raw in corpus.raws:
            obj = RawJSON(raw)
            listed.append(obj)
            yield obj

    mgr = corpus.manager(pipeline=pipeline)
    mgr.lister = lister
    first = mgr.audit()
    assert any(o._loaded for o in listed)  # the renders read their objects
    mgr.perf = {}
    second = mgr.audit()
    kept = sum(len(vs) for vs in second.kept.values())
    assert counters(mgr)[1:] == (0, 0) and kept > 200
    assert (mgr.perf["violation_peeked"], mgr.perf["violation_loaded"]) \
        == (kept, 0)
    assert len(listed) == N_OBJECTS
    assert not any(o._loaded for o in listed)
    assert not any(dict.__len__(o) for o in listed)
    assert not any(gc.is_tracked(o) for o in listed)
    assert canon(second) == canon(first)


def test_serial_schedule_hits_and_marks_its_fold_span(world):
    corpus = Corpus(world)
    mgr = corpus.manager(pipeline="off")
    first = mgr.audit()
    n1 = mgr.perf["n_renders"]
    mgr.perf = {}
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        second = mgr.audit()
    assert counters(mgr) == (n1, 0, 0) and canon(second) == canon(first)
    folds = [s for t in tracer.traces() for s in t["spans"]
             if s["name"] == "audit.chunk.collect_fold"]
    assert sum(s["attributes"]["render_memo_hits"] for s in folds) == n1


# --- (ii) one case per input of a render -------------------------------------

def _a_kept(run, kind):
    """(constraint key, violation): the first kept violation of a
    constraint of ``kind``."""
    for key, vs in run.kept.items():
        if key[0] == kind and vs:
            return key, vs[0]
    raise AssertionError(f"no kept violation of {kind}")


def _change_object_bytes(world, corpus, first):
    _key, v = _a_kept(first, "K8sRequiredLabels")
    i = corpus.index_of(v.kind, v.namespace, v.name)
    corpus.edit(i, lambda o: o["metadata"].setdefault(
        "annotations", {}).update(edited="yes"))
    changed = corpus.raws[i]
    return lambda ask: ask[1] == changed


def _change_constraint_parameters(world, corpus, first):
    key, _v = _a_kept(first, "K8sRequiredLabels")
    con = world["client"].get_constraint(*key)
    doc = copy.deepcopy(con.raw)
    doc.pop("status", None)
    doc["spec"]["parameters"] = {
        "labels": [{"key": "a-label-nothing-carries"}]}
    world["client"].add_constraint(doc)
    assert world["client"].get_constraint(*key) is not con
    return lambda ask: ask[0] == key


def _change_template_rego(world, corpus, first):
    path = dict(make_c500.templates())["requiredlabels"]
    doc = load_yaml_file(os.path.join(path, "template.yaml"))[0]
    target = doc["spec"]["targets"][0]
    assert "you must provide labels" in target["rego"]
    target["rego"] = target["rego"].replace("you must provide labels",
                                            "labels are owed")
    world["client"].add_template(doc)
    return lambda ask: ask[0][0] == "K8sRequiredLabels"


def _change_data(world, corpus, first):
    _key, v = _a_kept(first, "K8sUniqueIngressHost")
    twin = copy.deepcopy(json.loads(
        corpus.raws[corpus.index_of(v.kind, v.namespace, v.name)]))
    twin["metadata"]["name"] = "a-second-ingress-with-that-host"
    world["client"].add_data(twin)
    tpu = world["tpu"]
    referential = {c.kind for c in world["client"].constraints()
                   if tpu.render_token(c).data_epoch}
    assert referential == {"K8sUniqueIngressHost", "K8sUniqueServiceSelector",
                           "K8sStorageClass"}
    return lambda ask: ask[0][0] in referential


def _change_cel_parameters(world, corpus, first):
    """A CEL kind's render reads the Constraint's parameters too."""
    assert "K8sContainerLimitsCEL" in world["tpu"]._cel_kinds
    key, _v = _a_kept(first, "K8sContainerLimitsCEL")
    con = world["client"].get_constraint(*key)
    doc = copy.deepcopy(con.raw)
    doc.pop("status", None)
    doc["spec"]["parameters"] = {"memory": "3Mi"}
    world["client"].add_constraint(doc)
    assert world["client"].get_constraint(*key) is not con
    return lambda ask: ask[0] == key


def _change_cel_template(world, corpus, first):
    """A CEL kind's render reads its compiled template: the message is
    the template's."""
    path = dict(make_c500.templates())["containerlimitscel"]
    doc = load_yaml_file(os.path.join(path, "template.yaml"))[0]
    source = doc["spec"]["targets"][0]["code"][0]["source"]
    validation = source["validations"][0]
    assert validation["message"].startswith("container memory limit")
    validation["message"] = "a memory limit is owed"
    before = world["tpu"]._cel._templates["K8sContainerLimitsCEL"]
    world["client"].add_template(doc)
    assert world["tpu"]._cel._templates["K8sContainerLimitsCEL"] \
        is not before
    return lambda ask: ask[0][0] == "K8sContainerLimitsCEL"


@pytest.mark.parametrize("change", [
    _change_object_bytes, _change_constraint_parameters,
    _change_template_rego, _change_data,
    _change_cel_parameters, _change_cel_template,
], ids=["object-bytes", "constraint-parameters", "template-rego", "data",
        "cel-parameters", "cel-template"])
def test_only_what_reads_the_changed_input_misses(world, change):
    corpus = Corpus(world)
    mgr = corpus.manager()
    first = mgr.audit()
    reads_it = change(world, corpus, first)

    # a manager with no memory of a pass: every render it asks for reaches
    # the interpreter, so its asks are the pass's and its answers the
    # interpreter's
    with interpreter_renders(world["tpu"]) as asked:
        want = corpus.manager().audit()
    mgr.perf = {}
    with interpreter_renders(world["tpu"]) as missed:
        got = mgr.audit()
    assert canon(got) == canon(want)
    expect = [a for a in asked if reads_it(a)]
    assert expect and len(expect) < len(asked)
    assert sorted(missed) == sorted(expect)
    assert counters(mgr) == (len(asked) - len(expect), len(expect), 0)
    for changed, kind, text in (
            (_change_template_rego, "K8sRequiredLabels", "labels are owed"),
            (_change_cel_template, "K8sContainerLimitsCEL",
             "a memory limit is owed")):
        if change is changed:
            msgs = [v.message for k, vs in got.kept.items() for v in vs
                    if k[0] == kind]
            assert msgs and all(m.startswith(text) for m in msgs)


def test_source_is_in_the_key(world):
    corpus = Corpus(world)
    mgr = corpus.manager()
    first = mgr.audit()
    key, v = _a_kept(first, "K8sRequiredLabels")
    con = world["client"].get_constraint(*key)
    raw = corpus.raws[corpus.index_of(v.kind, v.namespace, v.name)]
    mgr.perf = {}
    original = mgr._render_fn(SOURCE_ORIGINAL)
    generated = mgr._render_fn(SOURCE_GENERATED)
    with interpreter_renders(world["tpu"]) as missed:
        a = original(con, RawJSON(raw))
        b = generated(con, RawJSON(raw))
        c = generated(con, RawJSON(raw))
        d = original(con, RawJSON(raw))
    assert missed == [(key, raw)]
    assert counters(mgr) == (3, 1, 0)
    assert a is d and b is c and a is not b
    assert [r.msg for r in a] == [r.msg for r in b] == [v.message]


# --- (iii) what bypasses the memo --------------------------------------------

EXTDATA_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "k8sasksaprovider"},
    "spec": {
        "crd": {"spec": {"names": {"kind": "K8sAsksAProvider"}}},
        "targets": [{
            "target": "admission.k8s.gatekeeper.sh",
            "rego": """package k8sasksaprovider
violation[{"msg": msg}] {
  response := external_data({"provider": "p", "keys": ["k"]})
  count(response.errors) > 0
  msg := "the provider said no"
}
""",
        }],
    },
}


def _bypass_external_data(world, con, raw):
    world["client"].add_template(EXTDATA_TEMPLATE)
    asks = world["client"].add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sAsksAProvider", "metadata": {"name": "asks"},
        "spec": {}})
    assert world["tpu"].render_token(asks) is None
    return asks, lambda: RawJSON(raw)


def _bypass_loaded(world, con, raw):
    def loaded():
        obj = RawJSON(raw)
        obj["kind"]
        return obj
    return con, loaded


def _bypass_dict(world, con, raw):
    return con, lambda: json.loads(raw)


@pytest.mark.parametrize("case", [
    _bypass_external_data, _bypass_loaded, _bypass_dict,
], ids=["external_data", "loaded-rawjson", "plain-dict"])
def test_what_the_key_cannot_hold_bypasses_and_is_counted(
        world, monkeypatch, case):
    corpus = Corpus(world)
    mgr = corpus.manager()
    first = mgr.audit()
    key, v = _a_kept(first, "K8sBlockNodePort")
    raw = corpus.raws[corpus.index_of(v.kind, v.namespace, v.name)]
    con, make = case(world, world["client"].get_constraint(*key), raw)
    # the interpreter stands in: what is counted is who reached it
    answer = [Result(target="t", msg="rendered", constraint=con.raw,
                     metadata={})]
    calls: list = []

    def render_query(target, constraint, review, cfg=None):
        calls.append(constraint.key())
        return QueryResponse(results=list(answer))

    monkeypatch.setattr(world["tpu"], "render_query", render_query,
                        raising=False)
    held = len(mgr._render_memo.prev)
    mgr.perf = {}
    render = mgr._render_fn()
    try:
        for _ in range(3):
            assert [r.msg for r in render(con, make())] == ["rendered"]
    finally:
        if case is _bypass_external_data:
            world["client"].remove_template("K8sAsksAProvider")
    assert calls == [con.key()] * 3
    assert counters(mgr) == (0, 3, 3)
    assert not mgr._render_memo.cur and len(mgr._render_memo.prev) == held


def test_within_a_chunk_an_object_the_fold_loaded_itself_still_hits(world):
    """The key takes the bytes of an object that was unloaded when the
    chunk first asked for it; the fold then loads it (``_violation``
    reads its name), and the next constraint's render of the same object
    in the same chunk must not take that for a mutation."""
    corpus = Corpus(world)
    mgr = corpus.manager()
    first = mgr.audit()
    key, v = _a_kept(first, "K8sBlockNodePort")
    con = world["client"].get_constraint(*key)
    raw = corpus.raws[corpus.index_of(v.kind, v.namespace, v.name)]
    mgr.perf = {}
    render = mgr._render_fn()
    obj = RawJSON(raw)
    with interpreter_renders(world["tpu"]) as missed:
        a = render(con, obj, cache_key=7)
        assert not obj._loaded
        obj["metadata"]
        b = render(con, obj, cache_key=7)
        # without the chunk's slot the loaded object is anybody's
        c = render(con, obj)
    assert a is b and [r.msg for r in c] == [r.msg for r in a]
    assert len(missed) == 1 and counters(mgr) == (2, 1, 1)


# --- (iv) churn --------------------------------------------------------------

@pytest.mark.parametrize("share", [0.01, 0.10])
def test_churn_misses_what_was_replaced_and_forgets_what_is_gone(
        world, share):
    rng = random.Random(f"churn:{share}")
    corpus = Corpus(world)
    mgr = corpus.manager()
    mgr.audit()
    asked_by_pass = []
    for stamp in ("pass-2", "pass-3"):
        new = corpus.churn(share, rng, stamp)
        with interpreter_renders(world["tpu"]) as asked:
            want = corpus.manager().audit()
        mgr.perf = {}
        with interpreter_renders(world["tpu"]) as missed:
            got = mgr.audit()
        assert canon(got) == canon(want)
        on_new = [a for a in asked if a[1] in new]
        assert on_new, "no kept violation sits on a replaced object"
        assert sorted(missed) == sorted(on_new)
        hits, n, bypass = counters(mgr)
        assert (n, bypass) == (len(on_new), 0)
        # the hit share is the unchanged kept violations' share
        assert hits / (hits + n) == 1 - len(on_new) / len(asked)
        assert hits / (hits + n) >= 1 - 4 * share
        asked_by_pass.append({(k, raw) for k, raw in asked})
    memo = mgr._render_memo
    assert not memo.cur
    held = {(token.constraint.key(), raw)
            for token, _source, raw in memo.prev}
    # what the last pass asked for, and nothing a pass before it did alone
    assert held == asked_by_pass[-1]
    assert asked_by_pass[0] - asked_by_pass[1]


def test_two_generations():
    memo = RenderMemo()
    memo.put("a", [1])
    assert memo.get("a") is None  # no pass has sized it: nothing is held
    memo.begin_pass(n_constraints=1, violations_limit=1)
    assert memo.cap == RenderMemo.PER_KEPT
    a, b, c = [1], [2], []
    memo.put("a", a)
    memo.put("b", b)
    memo.put("c", c)  # over the cap
    assert memo.get("a") is a and memo.get("c") is None
    memo.end_pass()
    memo.begin_pass(1, 1)
    assert memo.get("a") is a and "a" in memo.cur  # moved over
    memo.put("c", c)
    assert memo.get("c") is c  # an empty answer is an answer
    memo.end_pass()
    assert set(memo.prev) == {"a", "c"} and memo.get("b") is None


def test_a_token_compares_the_objects_it_holds():
    template, con, other = object(), object(), object()
    t = RenderToken(template, con, 3)
    assert t == RenderToken(template, con, 3)
    assert hash(t) == hash(RenderToken(template, con, 3))
    assert t != RenderToken(template, other, 3)
    assert t != RenderToken(other, con, 3)
    assert t != RenderToken(template, con, 4)
    assert t != (template, con, 3)
    assert t.template is template and t.constraint is con


# --- (v) the exact_totals lane and the cap -----------------------------------

def test_exact_totals_stops_inserting_at_the_cap_and_stays_correct(
        world, monkeypatch):
    # every hit renders for its count, as where no lowering proves one:
    # with the device's counts a pass asks for fewer renders than the cap
    for prog in world["tpu"]._programs.values():
        monkeypatch.setattr(prog.program, "count", None)
    corpus = Corpus(world)
    limit = 2
    ev = sharded.ShardedEvaluator(world["tpu"], sharded.make_mesh(1),
                                  violations_limit=limit)
    mgr = corpus.manager(exact_totals=True, violations_limit=limit,
                         evaluator=ev)
    with interpreter_renders(world["tpu"]) as asked:
        first = mgr.audit()
    cap = RenderMemo.PER_KEPT * len(world["client"].constraints()) * limit
    assert mgr._render_memo.cap == cap < len(asked)
    assert len(mgr._render_memo.prev) == cap
    mgr.perf = {}
    with interpreter_renders(world["tpu"]) as missed:
        second = mgr.audit()
    assert canon(second) == canon(first)
    assert counters(mgr) == (cap, len(asked) - cap, 0)
    assert missed == asked[cap:]
    assert len(mgr._render_memo.prev) == cap


# --- the differential --------------------------------------------------------

def test_memo_against_no_memo_over_three_passes_with_churn(world):
    rng = random.Random("differential")
    corpus = Corpus(world)
    with_memo, without = corpus.manager(), corpus.manager()
    hits = 0
    for n in range(3):
        if n:
            corpus.churn(0.10, rng, f"pass-{n}")
        without._render_memo = RenderMemo()
        want = without.audit()
        got = with_memo.audit()
        assert canon(got) == canon(want), n
        hits = with_memo.perf["render_memo_hits"]
    assert without.perf["render_memo_hits"] == 0
    assert hits > without.perf["n_renders"] / 2

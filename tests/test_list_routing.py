"""The audit lister's routing: one native call per chunk
(native/listroutemod.c) against the per-object loop it replaces
(ops/listroute.route_chunks_py).  Same objects, by identity, in the same
order in the same chunks, the same constraint subset with each, the same
``counter[0]``, the same exception at the same object; and the head scan
reads exactly what ``peek_kind``'s anchored regex reads.  The one thing the
native call does beside: it takes every unloaded, still empty ``RawJSON``
of the exact class off the cyclic collector's lists and counts it
(tests/test_rawjson_untracked.py has the mechanism by itself)."""

import gc
import random
from types import SimpleNamespace

import pytest

from gatekeeper_tpu.apis.constraints import Constraint
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.ops import listroute, native
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator
from gatekeeper_tpu.utils import rawjson
from gatekeeper_tpu.utils.rawjson import RawJSON, peek_kind

pytestmark = pytest.mark.skipif(
    native.load_listroute() is None,
    reason="native/listroutemod.c did not build")


def _con(kind, *kinds):
    match = {"kinds": [{"apiGroups": ["*"], "kinds": list(kinds)}]} \
        if kinds else {}
    return Constraint(kind=kind, name=kind.lower(), match=match,
                      parameters={}, enforcement_action="deny")


CONSTRAINTS = [_con("K8sA", "Pod"), _con("K8sB", "Pod", "Service"),
               _con("K8sC", "Namespace", "Deployment", "RoleBinding")]
WILD = CONSTRAINTS + [_con("K8sAll")]

KINDS = ["Pod", "Pod", "Service", "Pod", "Namespace", "Secret",
         "Deployment", "Pod", "Service", "ConfigMap"]


def _doc(i, kind, head="api"):
    meta = b'"metadata":{"name":"o%d","namespace":"ns%d"}' % (i, i % 3)
    k = kind.encode()
    if head == "api":
        return b'{"apiVersion":"v1","kind":"%s",%s}' % (k, meta)
    if head == "kind":
        return b'{"kind":"%s","apiVersion":"v1",%s}' % (k, meta)
    if head == "nested":  # the top-level kind after nested kind keys
        return (b'{"metadata":{"name":"o%d","ownerReferences":[{"kind":'
                b'"ReplicaSet","name":"rs"}]},"roleRef":{"kind":'
                b'"ClusterRole"},"apiVersion":"v1","kind":"%s"}' % (i, k))
    if head == "escaped":  # "K..." : only a parse can read it
        return (b'{"apiVersion":"v1","kind":"\\u00%02x%s",%s}'
                % (k[0], k[1:], meta))
    if head == "spaced":
        return b'{ "apiVersion": "v1", "kind": "%s", %s}' % (k, meta)
    raise AssertionError(head)


class _SubRaw(RawJSON):
    __slots__ = ()


def _raws(head, n=60):
    return lambda: [RawJSON(_doc(i, KINDS[i % len(KINDS)], head))
                    for i in range(n)]


def _loaded():
    objs = _raws("api")()
    for o in objs[::2]:
        o.get("kind")  # materialized: its bytes no longer speak for it
    return objs


def _dicts():
    return [{"apiVersion": "v1", "kind": KINDS[i % len(KINDS)],
             "metadata": {"name": f"o{i}"}} for i in range(60)]


def _mixed():
    rng = random.Random(26)
    heads = ["api", "kind", "nested", "escaped", "spaced"]
    objs = []
    for i in range(300):
        kind = rng.choice(KINDS)
        shape = rng.randrange(8)
        if shape < 5:
            objs.append(RawJSON(_doc(i, kind, heads[shape])))
        elif shape == 5:
            objs.append({"kind": kind, "metadata": {"name": f"o{i}"}})
        elif shape == 6:
            objs.append(_SubRaw(_doc(i, kind)))
        else:
            o = RawJSON(_doc(i, kind))
            o["kind"] = "Service"  # loaded and mutated: the dict decides
            objs.append(o)
    return objs


def _interleaved():
    # two groups fill inside a few objects of each other
    kinds = ["Pod", "Namespace"] * 3 + ["Pod", "Pod", "Namespace",
                                        "Namespace", "Pod"] * 4
    return [RawJSON(_doc(i, k)) for i, k in enumerate(kinds)]


def _bad_utf8():
    objs = _raws("api", 20)()
    objs[13] = RawJSON(b'{"apiVersion":"v1","kind":"P\xffd"}')
    return objs


def _raising(make, after, exc):
    def lister():
        for i, o in enumerate(make()):
            if i == after:
                raise exc
            yield o
    return lister


CASES = {
    # name: (objects, chunk_size, options)
    "head-apiversion-first": (_raws("api"), 8, {}),
    "head-kind-first": (_raws("kind"), 8, {}),
    "kind-after-nested-kinds": (_raws("nested"), 8, {}),
    "escaped-kind": (_raws("escaped"), 8, {}),
    "whitespace-in-the-head": (_raws("spaced"), 8, {}),
    "non-utf8-kind": (_bad_utf8, 4, {"pulled": 14}),
    "loaded-rawjson": (_loaded, 8, {}),
    "rawjson-subclass": (
        lambda: [_SubRaw(_doc(i, KINDS[i % 10])) for i in range(40)], 8, {}),
    "plain-dict": (_dicts, 8, {}),
    "kind-no-template-matches": (
        lambda: [RawJSON(_doc(i, "Secret")) for i in range(20)], 8, {}),
    "wildcard-constraint": (_mixed, 16, {"constraints": WILD}),
    "kind-filter": (_mixed, 16, {"kind_filter": {"Pod", "Namespace",
                                                 "Secret"}}),
    "expansion-tee-armed": (_mixed, 16, {"tee": True}),
    "two-groups-filling-together": (_interleaved, 3, {}),
    "chunk-size-1": (_mixed, 1, {}),
    "chunk-size-500": (_mixed, 500, {}),
    "mixed": (_mixed, 16, {}),
    "empty-lister": (lambda: [], 8, {}),
    "lister-raises": (_mixed, 16, {"raises": 137}),
    "lister-raises-at-once": (_mixed, 16, {"raises": 0}),
    "shard-chunks-2": (_mixed, 8, {"shard_chunks": 2}),
    "shard-chunks-2-kind-filter": (_mixed, 8, {
        "shard_chunks": 2, "kind_filter": {"Pod", "Service"}}),
}


def _acyclic(o):
    """What the native call may take off the collector's lists."""
    return (type(o) is RawJSON and o._loaded is False
            and type(o.raw) is bytes and dict.__len__(o) == 0)


def _run(make, chunk_size, opts, use_native, monkeypatch):
    """One pass of ``_chunk_source`` over fresh objects; what it did, with
    objects named by their position in the listing."""
    if not use_native:
        monkeypatch.setattr(native, "load_listroute", lambda: None)
    objs = make()
    pos = {id(o): i for i, o in enumerate(objs)}
    acyclic = [_acyclic(o) for o in objs]  # as the lister hands them over
    lister = (lambda: iter(objs))
    if "raises" in opts:
        lister = _raising(lambda: objs, opts["raises"],
                          RuntimeError("lister died"))
    mgr = AuditManager(
        None, lister=lister,
        config=AuditConfig(chunk_size=chunk_size,
                           shard_chunks=opts.get("shard_chunks", 0)))
    yields = []
    mgr._brownout_yield = lambda: yields.append(len(chunks))
    if opts.get("tee"):
        mgr.expansion_system = SimpleNamespace(templates=lambda: [
            SimpleNamespace(applies_to=lambda o: "1" in o["metadata"]["name"])
        ])
        mgr._gen_buf, mgr._gen_kinds = [object()], {"Deployment", "Pod"}
    constraints = opts.get("constraints", CONSTRAINTS)
    counter, chunks, error = [0], [], None
    try:
        for chunk, cons in mgr._chunk_source(
                constraints, opts.get("kind_filter"), True, counter):
            chunks.append(([pos[id(o)] for o in chunk],
                           [(c.kind, c.name) for c in cons]))
    except Exception as e:  # noqa: BLE001 — compared below
        error = (type(e), str(e))
    tee = None
    if opts.get("tee"):
        tee = ([pos[id(o)] for o in mgr._gen_buf], sorted(mgr._gen_ns))
    monkeypatch.undo()
    listed = opts.get("pulled", opts.get("raises", len(objs)))
    return SimpleNamespace(
        chunks=chunks, counter=counter[0], error=error, yields=yields,
        tee=tee, fast=mgr.perf["list_fast"], slow=mgr.perf["list_slow"],
        untracked=mgr.perf["list_untracked"], listed=listed,
        acyclic=sum(acyclic[:listed]),
        # off the lists: pulled, and still as acyclic as it arrived (a
        # peek_kind that had to parse loaded it, and so put it back)
        off_lists=[i for i, o in enumerate(objs) if not gc.is_tracked(o)],
        still_acyclic=[i for i, o in enumerate(objs[:listed])
                       if acyclic[i] and _acyclic(o)])


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_routing_is_the_per_object_loop(case, monkeypatch):
    make, chunk_size, opts = CASES[case]
    want = _run(make, chunk_size, opts, False, monkeypatch)
    got = _run(make, chunk_size, opts, True, monkeypatch)
    assert got.chunks == want.chunks
    assert got.counter == want.counter
    assert got.error == want.error
    assert got.yields == want.yields  # a brownout yield before each full chunk
    assert got.tee == want.tee
    # every object pulled off the lister is counted once, on either path
    assert (want.fast, want.slow) == (0, want.listed)
    assert got.fast + got.slow == got.listed
    # the loop untracks nothing; the native call every pulled object that
    # can hold no cycle, and nothing else
    assert want.untracked == 0 and want.off_lists == []
    native_ran = not opts.get("tee")
    assert got.untracked == (got.acyclic if native_ran else 0)
    assert got.off_lists == (got.still_acyclic if native_ran else [])
    if case == "non-utf8-kind":
        assert got.error[0] is UnicodeDecodeError
        # the one that raised was handed to peek_kind
        assert (got.fast, got.slow) == (13, 1) and got.counter == 13
        assert got.untracked == 14
    if "raises" in opts:
        assert got.error == (RuntimeError, "lister died")
    if opts.get("tee"):
        assert got.fast == 0  # an armed tee keeps the per-object loop
        assert got.tee[0] and got.tee[1]
    elif case in ("head-apiversion-first", "head-kind-first",
                  "kind-no-template-matches", "two-groups-filling-together"):
        assert got.slow == 0 and got.fast == got.listed == got.untracked
    elif case in ("kind-after-nested-kinds", "escaped-kind", "plain-dict",
                  "whitespace-in-the-head", "rawjson-subclass"):
        assert got.fast == 0
        # a head that settles nothing is as acyclic as one that does
        assert got.untracked == (
            0 if case in ("plain-dict", "rawjson-subclass") else got.listed)
        if case == "escaped-kind":  # peek_kind parsed each: tracked again
            assert got.off_lists == []
    elif case == "loaded-rawjson":
        assert got.fast == got.slow == got.untracked == 30
    elif got.listed:
        assert got.fast > 0 and got.slow > 0
    if case not in ("empty-lister", "lister-raises-at-once",
                    "kind-no-template-matches"):
        assert want.chunks, "the case routes nothing"
    else:
        assert got.chunks == [] and got.yields == []


def test_the_fallback_is_the_loop_when_the_module_does_not_build(
        monkeypatch):
    monkeypatch.setattr(native, "load_listroute", lambda: None)
    counter, counts = [0], [0, 0]
    objs = _raws("api", 10)()
    out = list(listroute.route_chunks(
        objs, lambda k: frozenset([k]), 4, counter, counts))
    assert [len(c) for _g, c in out] == [4, 2, 1, 1, 1, 1]
    assert counter == [10] and counts == [0, 10]
    assert all(gc.is_tracked(o) for o in objs)


def test_warm_pass_routes_with_the_audits_generator(monkeypatch):
    """``warm_pass(route=True)`` sweeps the corpus through the same
    generator, so the warmed chunks are the measured ones."""
    from gatekeeper_tpu.parallel import sharded

    seen = {}

    def recording(which):
        real = listroute.route_chunks

        def route_chunks(objects, router, chunk_size, *a, **kw):
            pos = {id(o): i for i, o in enumerate(objects)}
            for g, buf in real(objects, router, chunk_size, *a, **kw):
                seen.setdefault(which, []).append(
                    (sorted(g), [pos[id(o)] for o in buf]))
                yield g, buf
        return route_chunks

    # an evaluator none of whose groups lowers: the scan stops at the
    # group's state, which is all this test needs of it
    ev = SimpleNamespace(driver=SimpleNamespace(_programs={}),
                         _col_stats={})
    monkeypatch.setattr(sharded, "merge_pad_stats", lambda stats: None)
    for which in ("native", "loop"):
        with monkeypatch.context() as m:
            m.setattr(listroute, "route_chunks", recording(which))
            if which == "loop":
                m.setattr(native, "load_listroute", lambda: None)
            ShardedEvaluator.warm_pass(ev, CONSTRAINTS, _mixed(),
                                       chunk_size=16, route=True)
    assert seen["native"] == seen["loop"] and len(seen["native"]) > 5
    # and they are the chunks the audit makes of the same listing
    mgr = AuditManager(None, lister=_mixed,
                       config=AuditConfig(chunk_size=16))
    audit = [len(chunk) for chunk, _cons in
             mgr._chunk_source(CONSTRAINTS, None, True, [0])]
    assert audit == [len(chunk) for _g, chunk in seen["native"]]


# --- the head scan against peek_kind ----------------------------------------

_PIECES = [b'{', b'}', b'[', b']', b':', b',', b' ', b'\n', b'"',
           b'"apiVersion"', b'"kind"', b'"Pod"', b'"v1"', b'"apps/v1"',
           b'"kind":"Pod"', b'"apiVersion":"v1"', b'"metadata":{}',
           b'"kind":"kind"', b'"kind":7', b'"kind":null',
           b'"a":{"kind":"Inner"}', b'"P\\u006fd"', b'"P\\"d"', b'"P\\\\"',
           b'"\xc3\xa9"', b'"P\xffd"', b'"kind":"P\xffd"', b'"":""', b'\\']


def _heads(n, seed):
    rng = random.Random(seed)
    wellformed = [b'{"apiVersion":"v1","kind":"%s","x":1}',
                  b'{"kind":"%s","apiVersion":"v1"}',
                  b'{"apiVersion":"a\\"b","kind":"%s"}',
                  b'{"apiVersion":"v1", "kind":"%s"}',
                  b'{"apiVersion":"v1","kind":"%s","kind":"Other"}',
                  b'{"apiVersion":"","kind":"%s"}',
                  b'{"x":1,"kind":"%s"}']
    for i in range(n):
        if i % 3 == 0:
            raw = rng.choice(wellformed) % rng.choice(
                [b"Pod", b"", b"K\xc3\xa9", b"P\xffd", b"P\\u006fd", b"a b"])
        else:
            raw = (b'{"' if rng.random() < 0.7 else b"") + b"".join(
                rng.choice(_PIECES) for _ in range(rng.randrange(1, 9)))
            if raw.startswith(b'{""'):
                raw = raw[:2] + raw[3:]
        if rng.random() < 0.25:  # truncated anywhere
            raw = raw[:rng.randrange(len(raw) + 1)]
        yield raw


def test_head_scan_is_peek_kinds_head_match():
    mod = native.load_listroute()
    settled = unsettled = 0
    for raw in _heads(6000, seed=2026):
        m = rawjson._HEAD_KIND.match(raw)
        got = mod.head_kind(raw)
        assert got == (m.group(1) if m else None), raw
        settled += got is not None
        unsettled += got is None
    assert settled > 1000 and unsettled > 1000


def test_routing_reads_the_kind_peek_kind_reads():
    """Whole objects through the native call, one per chunk: the group it
    files each under is the kind ``peek_kind`` gives, and where
    ``peek_kind`` raises (bytes that are no JSON) it raises the same."""
    fast = [0, 0]
    for raw in _heads(3000, seed=7):
        try:
            want = peek_kind(RawJSON(raw))
        except Exception as e:  # noqa: BLE001 — the native call's too
            with pytest.raises(type(e)):
                list(listroute.route_chunks(
                    [RawJSON(raw)], lambda k: frozenset([k]), 1, [0], fast))
            continue
        out = list(listroute.route_chunks(
            [RawJSON(raw)], lambda k: frozenset([k]), 1, [0], fast))
        assert [sorted(g) for g, _chunk in out] == [[want]], raw
    assert fast[0] > 300 and fast[1] > 300

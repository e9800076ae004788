"""``utils/rawjson.peek_identity`` (``native/listroutemod.c:identity``): the
four strings a kept violation names its object by, read from the bytes of
an unloaded ``RawJSON``.  Wherever it answers, it answers what
``json.loads`` and ``AuditManager._violation``'s reads of the dict give;
wherever that is in doubt it answers None and the caller loads the object.
"""

from __future__ import annotations

import gc
import json
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cluster, manifest  # noqa: E402
from gatekeeper_tpu.ops import native  # noqa: E402
from gatekeeper_tpu.utils.rawjson import (RawJSON, backfill_gvk,  # noqa: E402
                                          peek_identity)
from gatekeeper_tpu.utils.unstructured import (gvk_of,  # noqa: E402
                                               split_api_version)

pytestmark = pytest.mark.skipif(
    native.load_listroute() is None,
    reason="native/listroutemod.c does not build here")


def by_the_dict(raw: bytes) -> tuple:
    """What ``_violation`` read before it peeked: (group, version, kind,
    name, namespace) through a loaded object."""
    obj = RawJSON(raw)
    group, version, kind = gvk_of(obj)
    meta = obj.get("metadata") or {}
    return (group, version, kind, meta.get("name", "") or "",
            meta.get("namespace", "") or "")


def by_the_bytes(raw: bytes):
    got = peek_identity(RawJSON(raw))
    if got is None:
        return None
    assert all(type(s) is str for s in got) and len(got) == 4
    api_version, kind, name, namespace = got
    return (*split_api_version(api_version), kind, name, namespace)


# --- every kind the generator writes ------------------------------------------

def _generated(config: str, seed: int, per_kind: int = 64) -> list:
    """The configuration's cluster at its rehearse size, the first
    ``per_kind`` objects of every kind: a few hundred documents."""
    cfg = manifest.apply_rehearsal(manifest.read_json(os.path.join(
        ROOT, "benchmark", "configs", config + ".json")))
    seen: dict = {}
    out = []
    for o in cluster.Cluster(cfg["cluster"], cfg["objects"],
                             seed=seed).objects(0):
        seen[o["kind"]] = seen.get(o["kind"], 0) + 1
        if seen[o["kind"]] <= per_kind:
            out.append(cluster.dumps(o))
    return out


_CORPUS = {c: _generated(c, seed=35)
           for c in ("library-full", "library-c500sel")}
_KINDS = sorted({(c, json.loads(r)["kind"])
                 for c, raws in _CORPUS.items() for r in raws})


def test_the_corpus_holds_the_generators_kinds():
    kinds = {k for _c, k in _KINDS}
    assert {"Pod", "Service", "Ingress", "Deployment", "Namespace"} <= kinds
    assert len(kinds) >= 7  # the RBAC kinds beside them


@pytest.mark.parametrize("config,kind", _KINDS)
def test_generated_objects_read_the_same_both_ways(config, kind):
    raws = [r for r in _CORPUS[config] if json.loads(r)["kind"] == kind]
    assert raws
    for raw in raws:
        got = by_the_bytes(raw)
        assert got is not None, raw
        assert got == by_the_dict(raw)
        assert got[2] == kind and got[3]


# --- the hard cases, one by one -------------------------------------------------

_POD = {"apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "web-0", "namespace": "shop",
                     "labels": {"app": "web"}},
        "spec": {"containers": [{"name": "c", "image": "nginx"}]}}


def _dumps(obj, **kw) -> bytes:
    return json.dumps(obj, **({"separators": (",", ":")} | kw)).encode()


def _metadata_after_spec():
    return _dumps({"spec": _POD["spec"], "kind": "Pod", "apiVersion": "v1",
                   "metadata": _POD["metadata"]})


def _nested(where):
    decoy = {"metadata": {"name": "decoy", "namespace": "decoy"},
             "name": "decoy", "namespace": "decoy", "kind": "Decoy",
             "apiVersion": "decoy/v0"}
    obj = json.loads(_dumps(_POD))
    if where == "annotation":
        obj["metadata"]["annotations"] = {
            "kubectl.kubernetes.io/last-applied-configuration":
                json.dumps(decoy)}
    elif where == "label":
        obj["metadata"]["labels"] = {"name": "decoy", "namespace": "decoy",
                                     "metadata": "decoy"}
    elif where == "template":
        obj = {"apiVersion": "apps/v1", "kind": "Deployment",
               "spec": {"template": decoy, "selector": decoy},
               "metadata": {"name": "web", "namespace": "shop"}}
    elif where == "ownerReferences":
        obj["metadata"] = {"ownerReferences": [decoy, decoy],
                           **obj["metadata"], "managedFields": [decoy]}
    return _dumps(obj)


_EXACT = {
    "head-form": _dumps(_POD),
    "backfilled-item": backfill_gvk(
        _dumps({"metadata": {"name": "a", "namespace": "b"},
                "apiVersion": "apps/v1", "kind": "Deployment"}),
        "v1", "DeploymentList"),
    "backfilled-item-without-its-own": backfill_gvk(
        _dumps({"metadata": {"name": "a", "namespace": "b"}}),
        "networking.k8s.io/v1", "Ingress"),
    "backfilled-empty-item": backfill_gvk(b"{ }", "v1", "Pod"),
    "duplicate-metadata-the-last-wins-whole": (
        b'{"kind":"Pod","metadata":{"name":"first","namespace":"ns"},'
        b'"metadata":{"name":"second"}}'),
    "duplicate-name-inside-metadata": (
        b'{"kind":"Pod","metadata":{"name":"first","name":"second",'
        b'"namespace":"x","namespace":"y"}}'),
    "metadata-then-null": (
        b'{"kind":"Pod","metadata":{"name":"first"},"metadata":null}'),
    "null-then-string": b'{"kind":null,"kind":"Pod","apiVersion":null}',
    "metadata-after-spec": _metadata_after_spec(),
    "nested-in-an-annotation-value": _nested("annotation"),
    "nested-in-a-label": _nested("label"),
    "nested-in-spec-template": _nested("template"),
    "nested-in-ownerReferences": _nested("ownerReferences"),
    "escapes-in-other-strings": _dumps(
        {**_POD, "spec": {"cmd": 'say "hi"', "path": "C:\\\\tmp\\x",
                          "u": "\u00e9\n\t", 'k"ey': "\\"}}),
    "escaped-key-elsewhere": (
        b'{"kind":"Pod","spec":{"na\\u006de":"decoy"},'
        b'"metadata":{"name":"n"}}'),
    "indented": _dumps(_POD, indent=2, separators=None),
    "spaced": _dumps(_POD, separators=(" , ", " : ")),
    "whitespace-around": b'{ \n"kind"\t:\r\n"Pod" , "metadata" : { } } \n',
    "cluster-scoped": _dumps({"apiVersion": "v1", "kind": "Namespace",
                              "metadata": {"name": "shop"}}),
    "generateName-and-no-name": _dumps(
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"generateName": "web-", "namespace": "shop"}}),
    "metadata-null": b'{"apiVersion":"v1","kind":"Pod","metadata":null}',
    "metadata-absent": b'{"apiVersion":"batch/v1","kind":"Job"}',
    "metadata-empty": b'{"kind":"Pod","metadata":{}}',
    "name-null": b'{"kind":"Pod","metadata":{"name":null,"namespace":"n"}}',
    "empty-strings": b'{"apiVersion":"","kind":"","metadata":{"name":""}}',
    "empty-object": b"{}",
    "group-with-two-slashes": b'{"apiVersion":"a/b/c","kind":"K"}',
    "utf-8-in-the-four": _dumps(
        {"kind": "Pod", "metadata": {"name": "caf\u00e9", "namespace": "\u65e5"}},
        ensure_ascii=False),
    "utf-8-elsewhere": _dumps(
        {**_POD, "spec": {"note": "\u00fcber \U0001f600"}},
        ensure_ascii=False),
    "numbers-of-every-form": (
        b'{"kind":"Pod","spec":[0,-0,1.5,-2e3,3E-2,4.0e+1,123456789012],'
        b'"metadata":{"name":"n","generation":7}}'),
    "deep-but-not-too-deep": (
        b'{"spec":' + b'[{"a":' * 20 + b"1" + b"}]" * 20
        + b',"kind":"Pod","metadata":{"name":"n"}}'),
}


@pytest.mark.parametrize("case", sorted(_EXACT))
def test_it_answers_what_the_dict_answers(case):
    raw = _EXACT[case]
    got = by_the_bytes(raw)
    assert got is not None
    assert got == by_the_dict(raw)


def test_the_decoys_never_show():
    for case in sorted(_EXACT):
        assert "decoy" not in "".join(by_the_bytes(_EXACT[case])).lower()
    assert by_the_bytes(_EXACT["backfilled-item"])[:3] == \
        ("apps", "v1", "Deployment")
    assert by_the_bytes(_EXACT["duplicate-metadata-the-last-wins-whole"]) \
        == ("", "", "Pod", "second", "")
    assert by_the_bytes(_EXACT["generateName-and-no-name"])[3:] == \
        ("", "shop")


_NONE = {
    "escape-inside-a-name": b'{"kind":"Pod","metadata":{"name":"a\\"b"}}',
    "unicode-escape-inside-a-namespace":
        b'{"kind":"Pod","metadata":{"namespace":"caf\\u00e9"}}',
    "escape-inside-the-kind": b'{"kind":"P\\u006fd"}',
    "escape-inside-the-apiVersion": b'{"apiVersion":"apps\\/v1"}',
    "escaped-top-level-key": b'{"k\\u0069nd":"Pod"}',
    "escaped-key-in-metadata": b'{"metadata":{"n\\u0061me":"x"}}',
    "numeric-name": b'{"kind":"Pod","metadata":{"name":7}}',
    "boolean-namespace": b'{"kind":"Pod","metadata":{"namespace":false}}',
    "kind-an-object": b'{"kind":{"a":1}}',
    "apiVersion-a-list": b'{"apiVersion":["v1"]}',
    "metadata-a-list": b'{"kind":"Pod","metadata":[{"name":"x"}]}',
    "metadata-a-string": b'{"kind":"Pod","metadata":"x"}',
    "metadata-zero": b'{"kind":"Pod","metadata":0}',
    "truncated": _dumps(_POD)[:-1],
    "truncated-in-a-string": _dumps(_POD)[:30],
    "trailing-garbage": _dumps(_POD) + b"x",
    "two-documents": _dumps(_POD) + _dumps(_POD),
    "bom": b"\xef\xbb\xbf" + _dumps(_POD),
    "leading-space": b" " + _dumps(_POD),
    "a-list": b'[{"kind":"Pod"}]',
    "empty": b"",
    "invalid-utf-8-in-a-name": b'{"kind":"Pod","metadata":{"name":"\xff"}}',
    "invalid-utf-8-elsewhere":
        b'{"kind":"Pod","spec":"\xc3","metadata":{"name":"n"}}',
    "control-byte-in-a-string":
        b'{"kind":"Pod","spec":"a\nb","metadata":{"name":"n"}}',
    "bad-escape": b'{"kind":"Pod","spec":"\\x"}',
    "escape-then-nul": b'{"kind":"Pod","spec":"\\\x00"}',
    "short-unicode-escape": b'{"kind":"Pod","spec":"\\u12"}',
    "trailing-comma": b'{"kind":"Pod",}',
    "trailing-comma-in-a-list": b'{"kind":"Pod","spec":[1,]}',
    "missing-colon": b'{"kind" "Pod"}',
    "missing-comma": b'{"kind":"Pod" "spec":1}',
    "unquoted-key": b'{kind:"Pod"}',
    "single-quotes": b"{'kind':'Pod'}",
    "leading-zero": b'{"kind":"Pod","spec":01}',
    "bare-minus": b'{"kind":"Pod","spec":-}',
    "dot-without-digits": b'{"kind":"Pod","spec":1.}',
    "nan": b'{"kind":"Pod","spec":NaN}',
    "infinity": b'{"kind":"Pod","spec":-Infinity}',
    "a-number-int-would-refuse": b'{"kind":"Pod","spec":' + b"9" * 5000 + b"}",
    "mismatched-brackets": b'{"kind":"Pod","spec":[}]}',
    "too-deep": b'{"spec":' + b"[" * 200 + b"]" * 200 + b',"kind":"Pod"}',
    "nul-byte": b'{"kind":"Pod"}\x00',
    "utf-16": '{"kind":"Pod"}'.encode("utf-16-le"),
}


@pytest.mark.parametrize("case", sorted(_NONE))
def test_it_answers_none_where_exactness_is_in_doubt(case):
    assert peek_identity(RawJSON(_NONE[case])) is None


def test_a_loaded_object_a_dict_a_subclass_and_other_bytes_are_none():
    raw = _dumps(_POD)
    assert peek_identity(RawJSON(raw)) == ("v1", "Pod", "web-0", "shop")
    loaded = RawJSON(raw)
    loaded["kind"]
    assert loaded._loaded and peek_identity(loaded) is None
    assert peek_identity(dict(_POD)) is None
    assert peek_identity(None) is None

    class Sub(RawJSON):
        __slots__ = ()

    assert peek_identity(Sub(raw)) is None
    assert peek_identity(RawJSON(bytearray(raw))) is None
    assert peek_identity(RawJSON(raw.decode())) is None
    # the module's own guard, without the wrapper's
    mod = native.load_listroute()
    for other in (loaded, Sub(raw), dict(_POD), raw, None,
                  RawJSON(bytearray(raw))):
        assert mod.identity(other) is None
    stuffed = RawJSON(raw)
    dict.__setitem__(stuffed, "kind", "Other")
    assert mod.identity(stuffed) is None


def test_without_the_module_it_answers_none(monkeypatch):
    monkeypatch.setattr(native, "load_listroute", lambda: None)
    assert peek_identity(RawJSON(_dumps(_POD))) is None


def test_a_peeked_object_stays_unloaded_and_off_the_collectors_lists():
    from gatekeeper_tpu.ops.listroute import route_chunks

    raws = _CORPUS["library-full"][:64]
    counts = [0, 0, 0]
    objs = [o for _g, chunk in route_chunks(
        (RawJSON(r) for r in raws), lambda kind: ("g",), 1000, [0], counts)
        for o in chunk]
    assert counts[2] == len(objs) == 64
    for obj, raw in zip(objs, raws):
        assert not gc.is_tracked(obj)
        got = peek_identity(obj)
        assert got is not None and got[1] == json.loads(raw)["kind"]
        assert not obj._loaded and not gc.is_tracked(obj)
        assert dict.__len__(obj) == 0 and obj.raw is raw
    # and reading through the dict afterwards is what it always was
    assert objs[0]["metadata"]["name"] == peek_identity(RawJSON(raws[0]))[2]
    assert objs[0]._loaded and gc.is_tracked(objs[0])


@pytest.mark.parametrize("seed", range(4))
def test_mutated_bytes_never_get_another_answer_than_the_dicts(seed):
    """Byte-level damage to real objects: wherever the scan still answers,
    ``json.loads`` accepts the document and the dict says the same."""
    rng = random.Random(seed)
    raws = _CORPUS["library-c500sel"]
    junk = b'{}[]",:\\ \n0123456789.eE-+tfnul\x00\x1f\x7f\x80\xc3\xa9\xff'
    answered = 0
    for _ in range(1500):
        b = bytearray(rng.choice(raws))
        for _ in range(rng.randint(1, 3)):
            j = rng.randrange(len(b))
            op = rng.random()
            if op < 0.4:
                b[j] = rng.choice(junk)
            elif op < 0.7:
                del b[j]
            else:
                b.insert(j, rng.choice(junk))
        raw = bytes(b)
        got = by_the_bytes(raw)
        if got is not None:
            answered += 1
            assert got == by_the_dict(raw), raw
    assert answered > 50

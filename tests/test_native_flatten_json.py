"""Differential tests for the threaded JSON flattener (flattenjsonmod.c).

The JSON lane interns strings in thread-partition order, so vocab *order*
differs from the sequential Python walk.  Exactness contract instead: run
the JSON lane FIRST, then the Python oracle over the SAME vocab — every
Python intern is then a lookup hit, so all sid arrays must be
bit-identical.  (Same trick as the audit pipeline: one shared driver
vocab, consistency is what matters, not order.)
"""

import functools
import json
import os
import random

import numpy as np
import pytest

from gatekeeper_tpu.ops import native
from gatekeeper_tpu.ops.flatten import (
    Axis,
    CanonCol,
    Flattener,
    KeySetCol,
    MapKeyCol,
    ParentIdxCol,
    RaggedCol,
    RaggedKeySetCol,
    ScalarCol,
    Schema,
    Vocab,
    round_up,
)
from gatekeeper_tpu.utils.rawjson import RawJSON, as_raw

jmod = native.load_json()


def rich_schema():
    containers = Axis(((("spec", "containers"),),
                       (("spec", "initContainers"),)))
    ports = Axis(((("spec", "containers"), ("ports",)),
                  (("spec", "initContainers"), ("ports",))))
    labels = Axis(((("metadata", "labels"),),))
    s = Schema()
    s.scalars = [
        ScalarCol(("spec", "hostNetwork")),
        ScalarCol(("spec", "priority")),
        ScalarCol(("metadata", "name")),
        ScalarCol(("spec", "nodeName")),
        ScalarCol(("__review__", "kind", "group")),
        ScalarCol(("__review__", "kind", "kind")),
        ScalarCol(("__review__", "operation")),
        ScalarCol(("__review__", "namespace")),
        ScalarCol(("__review__", "userInfo", "username")),
    ]
    s.raggeds = [
        RaggedCol(containers, ("securityContext", "privileged")),
        RaggedCol(containers, ("name",)),
        RaggedCol(containers, ()),
        RaggedCol(ports, ("hostPort",)),
        RaggedCol(labels, ()),
    ]
    s.keysets = [KeySetCol(("metadata", "labels")),
                 KeySetCol(("metadata", "annotations"))]
    s.map_keys = [MapKeyCol(labels)]
    s.ragged_keysets = [RaggedKeySetCol(axis=containers, subpath=()),
                        RaggedKeySetCol(axis=containers,
                                        subpath=("resources", "limits"))]
    s.parent_idx = [ParentIdxCol(axis=ports, parent=containers)]
    s.canons = [CanonCol(("metadata", "labels")),
                CanonCol(("spec", "selector"), ns_scoped=True)]
    return s


def rich_objects(n, seed=0):
    rng = random.Random(seed)
    objs = []
    strings = ["a", "", "b" * 50, "unié中文", "tab\there",
               'quote"back\\slash', "line\nbreak", "☃ snowman"]
    for i in range(n):
        containers = []
        for j in range(rng.randint(0, 5)):
            c = {"name": f"c{j}-{rng.choice(strings)}"}
            if rng.random() < 0.5:
                c["securityContext"] = {"privileged": rng.choice(
                    [True, False, "x", 1, None, {"m": 1}, [1]])}
            if rng.random() < 0.4:
                c["ports"] = [{"hostPort": rng.choice(
                    [rng.randint(1, 70000), 2.5, -1, 1e300, "80"])}
                    for _ in range(rng.randint(0, 3))]
            if rng.random() < 0.4:
                c["resources"] = {"limits": {
                    rng.choice(["cpu", "memory", "gpu"]): "1"
                    for _ in range(rng.randint(0, 3))}}
            if rng.random() < 0.2:
                c["flag"] = False  # truthy-key filter in ragged keysets
            containers.append(c)
        obj = {
            "apiVersion": rng.choice(["v1", "apps/v1", "batch/v1", ""]),
            "kind": rng.choice(["Pod", "Deployment", "ReplicaSet"]),
            "metadata": {
                "name": f"o{i}",
                "namespace": rng.choice(["default", "kube-system", ""]),
            },
            "spec": {"containers": containers},
        }
        if rng.random() < 0.4:
            obj["metadata"]["labels"] = {
                f"k{x}{rng.choice(strings)}": rng.choice(
                    [f"v{x}", True, False, None, 3])
                for x in range(rng.randint(1, 5))
            }
        if rng.random() < 0.2:
            obj["metadata"]["annotations"] = {
                "a": "b", "c": False, "d": rng.choice(strings)}
        if rng.random() < 0.2:
            obj["metadata"]["generateName"] = "gen-"
        if rng.random() < 0.3:
            obj["spec"]["hostNetwork"] = rng.choice([True, False, "maybe"])
        if rng.random() < 0.3:
            obj["spec"]["priority"] = rng.choice(
                [1, 2.5, -3, "high", None, 10 ** 400, -(10 ** 400), 0.1])
        if rng.random() < 0.3:
            obj["spec"]["nodeName"] = rng.choice(strings)
        if rng.random() < 0.3:
            obj["spec"]["selector"] = rng.choice([
                {"app": f"a{i % 7}", "tier": rng.choice(strings)},
                {"x": 3, "app": "mixed-types"},  # non-string pair skipped
                {},
                ["not", "a", "map"],
                "scalar",
            ])
        if rng.random() < 0.2:
            obj["spec"]["initContainers"] = [
                {"name": "init", "ports": [{"hostPort": 53}]}]
        objs.append(obj)
    return objs


def assert_batches_equal(schema, a, b):
    np.testing.assert_array_equal(a.group_sid, b.group_sid)
    np.testing.assert_array_equal(a.kind_sid, b.kind_sid)
    np.testing.assert_array_equal(a.ns_sid, b.ns_sid)
    np.testing.assert_array_equal(a.name_sid, b.name_sid)
    for spec in schema.scalars:
        np.testing.assert_array_equal(
            a.scalars[spec].kind, b.scalars[spec].kind, err_msg=str(spec))
        np.testing.assert_array_equal(
            a.scalars[spec].num, b.scalars[spec].num, err_msg=str(spec))
        np.testing.assert_array_equal(
            a.scalars[spec].sid, b.scalars[spec].sid, err_msg=str(spec))
    for axis in schema.axes():
        np.testing.assert_array_equal(a.axis_counts[axis],
                                      b.axis_counts[axis])
    for spec in schema.raggeds:
        np.testing.assert_array_equal(
            a.raggeds[spec].kind, b.raggeds[spec].kind, err_msg=str(spec))
        np.testing.assert_array_equal(
            a.raggeds[spec].num, b.raggeds[spec].num, err_msg=str(spec))
        np.testing.assert_array_equal(
            a.raggeds[spec].sid, b.raggeds[spec].sid, err_msg=str(spec))
    for spec in schema.keysets:
        np.testing.assert_array_equal(a.keysets[spec].sid,
                                      b.keysets[spec].sid)
        np.testing.assert_array_equal(a.keysets[spec].count,
                                      b.keysets[spec].count)
    for spec in schema.map_keys:
        np.testing.assert_array_equal(a.map_keys[spec].sid,
                                      b.map_keys[spec].sid)
    for spec in schema.parent_idx:
        np.testing.assert_array_equal(a.parent_idx[spec].idx,
                                      b.parent_idx[spec].idx)
    for spec in schema.ragged_keysets:
        np.testing.assert_array_equal(a.ragged_keysets[spec].sid,
                                      b.ragged_keysets[spec].sid)
        np.testing.assert_array_equal(a.ragged_keysets[spec].count,
                                      b.ragged_keysets[spec].count)
    for spec in getattr(schema, "canons", []):
        np.testing.assert_array_equal(a.canons[spec], b.canons[spec],
                                      err_msg=str(spec))


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_matches_python_shared_vocab():
    schema = rich_schema()
    objs = rich_objects(400)
    raws = [as_raw(o) for o in objs]
    vocab = Vocab()
    # JSON lane first: it creates every interning; the Python oracle then
    # only looks up, so sids must agree bitwise.
    nat = Flattener(schema, vocab).flatten_raw(raws, pad_n=512)
    py = Flattener(schema, vocab, use_native=False).flatten(objs, pad_n=512)
    assert_batches_equal(schema, py, nat)
    # genname presence column
    want = np.zeros(512, np.uint8)
    for i, o in enumerate(objs):
        if "generateName" in (o.get("metadata") or {}):
            want[i] = 1
    np.testing.assert_array_equal(nat.has_generate_name, want)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_thread_counts_agree():
    """1-thread and 8-thread runs decode to the same strings (ids may
    differ — vocabularies are independent)."""
    schema = rich_schema()
    objs = rich_objects(300, seed=7)
    raws = [as_raw(o) for o in objs]
    outs = []
    for nt in ("1", "8"):
        os.environ["GTPU_FLATTEN_THREADS"] = nt
        try:
            v = Vocab()
            outs.append((v, Flattener(schema, v).flatten_raw(
                raws, pad_n=320)))
        finally:
            del os.environ["GTPU_FLATTEN_THREADS"]
    (v1, b1), (v8, b8) = outs

    def decode(v, arr):
        flat = arr.ravel()
        return [v.string(s) if s >= 0 else None for s in flat.tolist()]

    assert decode(v1, b1.name_sid) == decode(v8, b8.name_sid)
    for spec in schema.raggeds:
        assert decode(v1, b1.raggeds[spec].sid) == \
            decode(v8, b8.raggeds[spec].sid)
        np.testing.assert_array_equal(b1.raggeds[spec].kind,
                                      b8.raggeds[spec].kind)
    for spec in schema.keysets:
        assert decode(v1, b1.keysets[spec].sid) == \
            decode(v8, b8.keysets[spec].sid)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_invalid_raises():
    """Truly malformed bytes raise through BOTH lanes: the C reject
    falls back to the dict lane, whose json.loads reject propagates
    as a ValueError into the audit chunk retry/drop machinery."""
    schema = rich_schema()
    raws = [as_raw({"kind": "Pod"}), RawJSON(b"{not json")]
    with pytest.raises(ValueError):
        Flattener(schema, Vocab()).flatten_raw(raws, pad_n=8)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_c_reject_falls_back_to_dict_lane():
    """Input the C parser rejects but json.loads accepts (nesting past
    the C 256-depth cap) lands on the dict lane with oracle-identical
    columns instead of failing the batch."""
    deep = (b'{"kind":"Pod","metadata":{"name":"deep"},"spec":'
            + b'{"a":' * 300 + b"1" + b"}" * 300 + b"}")
    docs = [deep, b'{"kind":"Pod","metadata":{"name":"flat"}}']
    schema = rich_schema()
    vocab = Vocab()
    f = Flattener(schema, vocab)
    nat = f.flatten_raw([RawJSON(d) for d in docs], pad_n=8)
    assert f.lane_used in ("dict", "py")  # the fallback lane ran
    py = Flattener(schema, vocab, use_native=False).flatten(
        [json.loads(d) for d in docs], pad_n=8)
    assert_batches_equal(schema, py, nat)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_truncated_bytes_fall_back_then_raise():
    """Truncated page bytes (a torn ingest) fail the C parser AND the
    dict-lane reparse: the error must surface (chunk machinery retries
    or drops the chunk), never silently flatten as an empty row."""
    whole = as_raw({"kind": "Pod", "metadata": {"name": "x"}})
    torn = RawJSON(whole.raw[:-5])
    f = Flattener(rich_schema(), Vocab())
    with pytest.raises(ValueError):
        f.flatten_raw([torn], pad_n=8)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_weird_documents():
    schema = rich_schema()
    vocab = Vocab()
    cases = [b"{}", b"[1,2]", b"null", b'"str"', b"3.5",
             b'{"spec": null}', b'{"spec": {"containers": "x"}}',
             b'{"apiVersion": 7, "kind": null}',
             b'{"metadata": {"name": null, "namespace": 3}}',
             b'{"a": "\\u00e9\\u4e2d\\ud83d\\ude00"}']
    raws = [RawJSON(c) for c in cases]
    nat = Flattener(schema, vocab).flatten_raw(raws, pad_n=16)
    # dict-parseable cases must agree with the Python path; non-dict roots
    # behave as empty rows (identity "")
    objs = [json.loads(c) for c in cases]
    dict_rooted = [isinstance(o, dict) for o in objs]
    objs = [o if isinstance(o, dict) else {} for o in objs]
    py = Flattener(schema, vocab, use_native=False).flatten(objs, pad_n=16)
    nocanon = rich_schema()
    nocanon.canons = []
    assert_batches_equal(nocanon, py, nat)
    # canon columns: object-rooted rows match the oracle; a non-object
    # root stays -2 in the raw lane (the parse path's "yields nothing"),
    # where the {}-substituted oracle row interns "" instead
    for spec in schema.canons:
        for i, isdict in enumerate(dict_rooted):
            if isdict:
                assert nat.canons[spec][i] == py.canons[spec][i], (spec, i)
            else:
                assert nat.canons[spec][i] == -2, (spec, i)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_flatten_delegates_rawjson():
    """Flattener.flatten() auto-routes all-RawJSON batches to the native
    JSON lane; a materialized (possibly mutated) RawJSON disables it."""
    schema = rich_schema()
    objs = rich_objects(50, seed=3)
    vocab = Vocab()
    raws = [as_raw(o) for o in objs]
    nat = Flattener(schema, vocab).flatten(raws, pad_n=64)
    assert nat.has_generate_name is not None  # proof the JSON lane ran
    py = Flattener(schema, vocab, use_native=False).flatten(objs, pad_n=64)
    assert_batches_equal(schema, py, nat)
    # a touched (materialized) RawJSON stays on the JSON lane via
    # re-serialization of its current dict state — mutations are honored
    raws2 = [as_raw(o) for o in objs]
    _ = raws2[0]["kind"]
    raws2[1]["metadata"]["name"] = "mutated"  # diverges from .raw
    touched = Flattener(schema, vocab).flatten(raws2, pad_n=64)
    assert touched.has_generate_name is not None
    assert vocab.string(int(touched.name_sid[1])) == "mutated"
    objs2 = [dict(o) for o in objs]
    objs2[1] = json.loads(json.dumps(objs2[1]))
    objs2[1]["metadata"]["name"] = "mutated"
    py2 = Flattener(schema, vocab, use_native=False).flatten(
        objs2, pad_n=64)
    assert_batches_equal(schema, py2, touched)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_review_docs_override():
    """Provided review docs (webhook lane) override synthesized
    __review__ columns."""
    schema = rich_schema()
    objs = rich_objects(20, seed=9)
    reviews = [{"kind": {"group": "apps", "version": "v1",
                         "kind": "Deployment"},
                "operation": "CREATE", "name": f"n{i}", "namespace": "ns",
                "userInfo": {"username": f"u{i}"}}
               for i in range(len(objs))]
    vocab = Vocab()
    raws = [as_raw(o) for o in objs]
    nat = Flattener(schema, vocab).flatten_raw(raws, pad_n=32,
                                               reviews=reviews)
    py = Flattener(schema, vocab, use_native=False).flatten(
        objs, pad_n=32, reviews=reviews)
    assert_batches_equal(schema, py, nat)


def test_rawjson_mutation_and_copy_semantics():
    """Review findings: writes before first read must survive the lazy
    parse; deepcopy of a mutated instance must capture current state
    (the mutation system's clear()/update() rollback pattern)."""
    import copy

    r = as_raw({"kind": "Pod", "metadata": {"name": "a"}})
    r["kind"] = "Deployment"          # write before any read
    assert r["kind"] == "Deployment"  # parse must not clobber the write
    assert r["metadata"]["name"] == "a"

    r2 = as_raw({"kind": "Pod", "spec": {"x": 1}})
    r2["spec"]["x"] = 2               # materialize + mutate nested
    snap = copy.deepcopy(r2)
    assert snap["spec"]["x"] == 2     # deepcopy sees mutated state
    r2.clear()
    assert len(r2) == 0               # raw must not resurrect keys
    r2.update(snap)
    assert r2["spec"]["x"] == 2       # rollback pattern round-trips

    r3 = as_raw({"a": 1})
    assert copy.deepcopy(r3)["a"] == 1  # unloaded path still works


# --- the prefill is the workers', the widths the caller's (PR 37) ----------
#
# Every output array is PyArray_EMPTY: each row of it, the padding rows
# too, carries its prefill (0, -1, -2) only because some worker wrote it
# inside a released phase.  A row no thread filled would show as garbage
# against the Python flattener, which np.zeros() and np.full()s.

PADS = {"n": lambda n: n, "n+1": lambda n: n + 1, "2n": lambda n: 2 * n}


def _arrays(out):
    """Every array of a columnizer result, in the result's order."""
    def walk(x):
        if isinstance(x, np.ndarray):
            yield x
        elif isinstance(x, (list, tuple)):
            for e in x:
                yield from walk(e)
    return [a for key, val in out.items() if key != "fill_bytes"
            for a in walk(val)]


def _columnize(f, items, pad_n, nthreads, *more):
    schema = f.schema
    axes = schema.axes()
    specs = f._columnizer_specs(schema, axes,
                                {a: i for i, a in enumerate(axes)})
    return jmod.flatten_json_batch(items, *specs, f.vocab._to_id,
                                   f.vocab._to_str, pad_n, f.bucket,
                                   nthreads, *more)


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
@pytest.mark.parametrize("n_real", [0, 1, 127, 300])
@pytest.mark.parametrize("pad", sorted(PADS))
@pytest.mark.parametrize("nthreads", [1, 2, 13])
def test_json_prefill_by_the_workers_matches_python(nthreads, pad, n_real):
    """Threads x padding x rows: the thread count clamps to n / 128 + 1,
    so 300 rows run on 1, 2 and 3 threads, 127 and fewer on one; with no
    row at all the one thread's own range is empty and the padding rows
    are all it fills (the partition leaves a last thread's range empty in
    no other case)."""
    schema = rich_schema()
    objs = rich_objects(n_real, seed=11)
    raws = [as_raw(o) for o in objs]
    pad_n = PADS[pad](n_real)
    vocab = Vocab()
    f = Flattener(schema, vocab)
    f.nthreads = nthreads
    nat = f.flatten_raw(raws, pad_n=pad_n)
    assert f.lane_used == "raw"
    py = Flattener(schema, vocab, use_native=False).flatten(
        objs, pad_n=pad_n)
    assert nat.n == py.n == pad_n
    assert_batches_equal(schema, py, nat)
    # every byte of every array was prefilled once, by a worker, the lock
    # released
    out = _columnize(Flattener(schema, Vocab()),
                     [r.raw for r in raws], pad_n, nthreads)
    assert out["fill_bytes"] == (sum(a.nbytes for a in _arrays(out)), 0)
    assert f.perf["fill_released_bytes"] == out["fill_bytes"][0]
    assert f.perf["fill_held_bytes"] == 0


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_invalid_in_the_middle_threads_range_leaves_the_vocab():
    schema = rich_schema()
    items = [as_raw(o).raw for o in rich_objects(300, seed=5)]
    items[150] = b'{"kind": "Pod", "metadata": {'  # thread 1 of 3's rows
    f = Flattener(schema, Vocab())
    before = (dict(f.vocab._to_id), list(f.vocab._to_str))
    with pytest.raises(ValueError, match="batch item 150"):
        _columnize(f, items, 320, 13)
    assert (f.vocab._to_id, f.vocab._to_str) == before
    # and the batch lands on the dict lane, which refuses it too
    with pytest.raises(ValueError):
        f.flatten_raw([RawJSON(b) for b in items], pad_n=320)
    assert (f.vocab._to_id, f.vocab._to_str) == before


FAMILIES = {
    "raggeds": lambda b: [a for c in b.raggeds.values()
                          for a in (c.kind, c.num, c.sid)],
    "keysets": lambda b: [a for c in b.keysets.values()
                          for a in (c.sid, c.count)],
    "map_keys": lambda b: [c.sid for c in b.map_keys.values()],
    "parent_idx": lambda b: [c.idx for c in b.parent_idx.values()],
    "ragged_keysets": lambda b: [a for c in b.ragged_keysets.values()
                                 for a in (c.sid, c.count)],
}
# the chunk against the targets: a bucket (2) and more narrower, the
# same, wider
RELATIONS = {"narrower": 3, "equal": 0, "wider": -3}


@functools.lru_cache(maxsize=None)
def _floors_and_parent(relation):
    """One chunk flattened twice over one vocabulary: with the targets as
    the columnizer's floors, and as the parent did it (the chunk's own
    widths, then ``_stabilize``'s second array and copy)."""
    schema = rich_schema()
    raws = [as_raw(o) for o in rich_objects(200, seed=23)]
    vocab = Vocab()
    own: dict = {}
    probe = Flattener(schema, vocab, bucket=2)
    probe.record_widths(probe.flatten_raw(raws, pad_n=256), own)
    targets = {k: max(1, v + RELATIONS[relation]) for k, v in own.items()}
    floors = Flattener(schema, vocab, bucket=2, width_targets=targets)
    with_floors = floors.flatten_raw(raws, pad_n=256)
    parent = Flattener(schema, vocab, bucket=2, width_targets=targets)
    parent._width_floors = lambda schema, axes: None
    as_parent = parent.flatten_raw(raws, pad_n=256)
    return schema, with_floors, as_parent, floors.perf, parent.perf


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("relation", sorted(RELATIONS))
def test_json_width_floors_give_stabilizes_shapes(relation, family):
    schema, with_floors, as_parent, perf, parent_perf = \
        _floors_and_parent(relation)
    got, want = FAMILIES[family](with_floors), FAMILIES[family](as_parent)
    assert got and len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()
    assert_batches_equal(schema, as_parent, with_floors)
    # the floors leave _stabilize nothing to pad; the parent's path pads
    # exactly where the chunk is narrower than the corpus
    assert perf["stabilize_repads"] == 0
    assert parent_perf["stabilize_repads"] == (relation == "narrower")
    assert perf["fill_held_bytes"] == parent_perf["fill_held_bytes"] == 0
    wider = perf["fill_released_bytes"] - parent_perf["fill_released_bytes"]
    assert (wider > 0) == (relation == "narrower")


@pytest.mark.skipif(jmod is None, reason="native json build unavailable")
def test_json_call_without_floors_is_the_call_as_it_was():
    """The worker pool's children pass no floors: the widths are the
    batch's own bucketed maxima, as with None and with floors of 0."""
    items = [as_raw(o).raw for o in rich_objects(150, seed=31)]
    outs = []
    for more in ((), (None,), (([0] * 3, [0] * 2, [0] * 2),)):
        f = Flattener(rich_schema(), Vocab())
        outs.append((_columnize(f, items, 160, 1, *more), f.vocab._to_str))
    (first, strs), rest = outs[0], outs[1:]
    for sid, cnt in first["keysets"] + first["ragged_keysets"]:
        assert sid.shape[-1] == round_up(int(cnt.max()), 8)
    for (_k, _n, sid), axis in zip(first["raggeds"], (0, 0, 0, 1, 2)):
        assert sid.shape[1] == round_up(int(first["axes"][axis].max()), 8)
    for out, strs2 in rest:
        assert strs2 == strs and out["fill_bytes"] == first["fill_bytes"]
        for a, b in zip(_arrays(out), _arrays(first)):
            assert a.shape == b.shape and a.tobytes() == b.tobytes()
    # items that are no exact bytes go through the buffer protocol
    f = Flattener(rich_schema(), Vocab())
    mixed = [bytearray(b) if i % 3 else memoryview(b)
             for i, b in enumerate(items)]
    mixed[0] = items[0]
    out = _columnize(f, mixed, 160, 1)
    assert f.vocab._to_str == strs
    for a, b in zip(_arrays(out), _arrays(first)):
        assert a.tobytes() == b.tobytes()
    f = Flattener(rich_schema(), Vocab())
    for bad in (([0] * 3, [0] * 2), ([0] * 2, [0] * 2, [0] * 2), 7,
                (["x"] * 3, [0] * 2, [0] * 2)):
        with pytest.raises(TypeError):
            _columnize(f, items, 160, 1, bad)

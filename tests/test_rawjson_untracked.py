"""An unloaded ``RawJSON`` and CPython's cyclic collector.

The audit lister's native router (native/listroutemod.c) takes every
unloaded, still empty ``RawJSON`` of the exact class off the collector's
lists: it refers to one ``bytes`` and one ``bool`` and can be part of no
cycle, and a pass's worth of such objects, each alive as long as its
chunk, is what promoted into the full collections.  ``utils/rawjson`` puts
the object back the moment it loads, by an explicit call and not by what
one interpreter version's ``dict`` happens to do."""

import copy
import gc
import pickle
import weakref

import pytest

from gatekeeper_tpu.ops import listroute, native
from gatekeeper_tpu.utils import rawjson
from gatekeeper_tpu.utils.rawjson import RawJSON

needs_native = pytest.mark.skipif(
    native.load_listroute() is None,
    reason="native/listroutemod.c did not build")

NESTED = (b'{"apiVersion":"v1","kind":"Pod","metadata":{"name":"p",'
          b'"labels":{"a":"b"}},"spec":{"containers":[{"name":"c"}]}}')
FLAT = b'{"apiVersion":"v1","kind":"Pod"}'  # no container inside


class _SubRaw(RawJSON):
    __slots__ = ()


def _route(objs, chunk_size=4):
    """The objects through ``route_chunks``, every kind its own group;
    the chunks and the three counts."""
    counts = [0, 0, 0]
    chunks = list(listroute.route_chunks(
        objs, lambda kind: frozenset([kind]), chunk_size, [0], counts))
    return chunks, counts


def _routed(raw=NESTED):
    """One unloaded RawJSON as a chunk of the native router holds it."""
    obj = RawJSON(raw)
    _route([obj])
    assert not gc.is_tracked(obj)
    return obj


# --- what the router takes off the lists, and what it leaves ----------------

@needs_native
def test_unloaded_exact_class_objects_leave_the_collectors_lists():
    objs = [RawJSON(NESTED) for _ in range(10)]
    assert all(gc.is_tracked(o) for o in objs)  # a dict subclass, from birth
    chunks, counts = _route(objs)
    assert [len(c) for _g, c in chunks] == [4, 4, 2]
    assert counts == [10, 0, 10]
    assert not any(gc.is_tracked(o) for _g, c in chunks for o in c)
    assert not any(o._loaded for o in objs)
    tracked = {id(o) for o in gc.get_objects()}
    assert not any(id(o) in tracked for o in objs)


def _loaded():
    o = RawJSON(NESTED)
    o.get("kind")
    return o


def _filled_behind_its_back():
    o = RawJSON(NESTED)
    dict.__setitem__(o, "held", [])  # unloaded, and holds a container
    return o


@needs_native
@pytest.mark.parametrize("make", [
    _loaded,
    lambda: _SubRaw(NESTED),
    lambda: {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "p"}},
    lambda: RawJSON(bytearray(NESTED)),
    _filled_behind_its_back,
], ids=["loaded", "subclass", "plain-dict", "raw-not-bytes",
        "unloaded-but-not-empty"])
def test_anything_else_stays_tracked(make):
    obj = make()
    chunks, counts = _route([RawJSON(NESTED), obj, RawJSON(NESTED)])
    assert sum(len(c) for _g, c in chunks) == 3
    assert gc.is_tracked(obj)
    assert counts[2] == 2 and counts[0] + counts[1] == 3


@needs_native
def test_an_object_whose_head_settles_nothing_is_untracked_all_the_same():
    # the kind after nested kinds: peek_kind's scan reads it, unloaded
    late = RawJSON(b'{"metadata":{"ownerReferences":[{"kind":"ReplicaSet"}]'
                   b'},"apiVersion":"v1","kind":"Pod"}')
    # an escaped kind: only a parse reads it, so peek_kind loads the object
    escaped = RawJSON(b'{"apiVersion":"v1","kind":"\\u0050od"}')
    chunks, counts = _route([late, escaped])
    assert counts == [0, 2, 2]
    assert not late._loaded and not gc.is_tracked(late)
    assert escaped._loaded and gc.is_tracked(escaped)


@needs_native
def test_dropped_objects_are_untracked_too():
    objs = [RawJSON(NESTED) for _ in range(3)]
    counts = [0, 0, 0]
    assert list(listroute.route_chunks(
        objs, lambda kind: frozenset(), 4, [0], counts)) == []
    assert counts == [3, 0, 3]
    assert not any(gc.is_tracked(o) for o in objs)


def test_the_per_object_loop_leaves_every_object_tracked(monkeypatch):
    monkeypatch.setattr(native, "load_listroute", lambda: None)
    objs = [RawJSON(NESTED) for _ in range(10)]
    chunks, counts = _route(objs)
    assert [len(c) for _g, c in chunks] == [4, 4, 2]
    assert counts == [0, 10, 0]
    assert all(gc.is_tracked(o) for o in objs)


# --- back on the lists before it can hold a container -----------------------

@needs_native
@pytest.mark.parametrize("raw", [NESTED, FLAT], ids=["nested", "flat"])
@pytest.mark.parametrize("touch", [
    lambda o: o.get("kind"),
    lambda o: o["kind"],
    lambda o: len(o),
    lambda o: "kind" in o,
    lambda o: list(o.items()),
    lambda o: o.__setitem__("held", [1, 2]),
    lambda o: o.update(held={}),
    lambda o: o.setdefault("held", []),
    lambda o: o.pop("kind"),
    lambda o: o.clear(),
    lambda o: o == {},
    lambda o: o.copy(),
], ids=["get", "getitem", "len", "contains", "items", "setitem-list",
        "update", "setdefault", "pop", "clear", "eq", "copy"])
def test_a_load_puts_the_object_back(raw, touch):
    """Whatever loads it: tracked again, a document without one nested
    container too (there 3.12's dict would not have tracked itself, so
    this is the explicit call)."""
    obj = _routed(raw)
    touch(obj)
    assert obj._loaded and gc.is_tracked(obj)


@needs_native
def test_the_retrack_is_the_explicit_call(monkeypatch):
    """Without ``track()`` a flat document leaves the loaded object off the
    lists on this interpreter: what ``_mark_loaded`` is there for."""
    obj = _routed(FLAT)
    monkeypatch.setattr(rawjson, "_gc_track", None)
    obj.get("kind")
    assert obj._loaded
    if gc.is_tracked(obj):
        pytest.skip("this interpreter's dict tracks itself on any insert")
    monkeypatch.undo()
    rawjson._mark_loaded(obj)
    assert gc.is_tracked(obj)


@needs_native
@pytest.mark.parametrize("load_first", [False, True],
                         ids=["unloaded", "loaded"])
@pytest.mark.parametrize("trip", [
    copy.copy, copy.deepcopy,
    lambda o: pickle.loads(pickle.dumps(o)),
], ids=["copy", "deepcopy", "pickle"])
def test_round_trips_give_tracked_objects(trip, load_first):
    obj = _routed()
    if load_first:
        obj["held"] = [obj.get("kind")]
    twin = trip(obj)
    assert type(twin) is RawJSON and twin is not obj
    assert gc.is_tracked(twin)
    assert twin._loaded == load_first
    assert twin == obj  # loads both
    assert gc.is_tracked(twin) and gc.is_tracked(obj)
    if load_first:
        assert twin["held"] == ["Pod"]


@needs_native
def test_restore_loaded_tracks_whatever_it_is_given():
    twin = rawjson._restore_loaded(FLAT, {"kind": "Pod"})
    assert twin._loaded and gc.is_tracked(twin) and twin == {"kind": "Pod"}


@needs_native
def test_a_cycle_through_a_once_untracked_object_is_collected():
    class Canary:
        pass

    obj = _routed()
    canary = Canary()
    gone = weakref.ref(canary)
    obj["me"] = obj          # the cycle goes through the RawJSON itself
    obj["canary"] = canary
    del obj, canary
    gc.collect()
    assert gone() is None


@needs_native
def test_an_untracked_object_dies_by_refcount_and_survives_collections():
    objs = [RawJSON(NESTED) for _ in range(2000)]
    chunks, _counts = _route(objs, chunk_size=500)
    del objs
    gc.collect()
    assert all(o.raw == NESTED and not o._loaded
               for _g, c in chunks for o in c)
    assert chunks[0][1][0].get("kind") == "Pod"
    del chunks
    gc.collect()


# --- what it is for: nothing promotes into the old generation ---------------

def _old_generation_rawjson(objs):
    """How many of ``objs`` a collection of the two young generations
    promotes into the old one, held by a chunk as a pass holds them."""
    gc.collect()
    _route(objs, chunk_size=len(objs))
    gc.collect(1)
    ids = {id(o) for o in objs}
    return sum(1 for o in gc.get_objects(generation=2) if id(o) in ids)


@needs_native
def test_routed_objects_do_not_promote_into_the_old_generation(monkeypatch):
    # CPython starts a full collection when the objects promoted since the
    # last one pass a quarter of the old generation
    assert _old_generation_rawjson(
        [RawJSON(NESTED) for _ in range(512)]) == 0
    monkeypatch.setattr(native, "load_listroute", lambda: None)
    assert _old_generation_rawjson(
        [RawJSON(NESTED) for _ in range(512)]) == 512


# --- track(), and a process without the module ------------------------------

@needs_native
def test_track_is_a_noop_on_what_is_tracked_or_cannot_be():
    track = native.load_listroute().track
    assert rawjson._gc_track is track
    for obj in ([], {"a": []}, RawJSON(NESTED), 7, b"bytes", "str", None,
                (1, 2), object()):
        was = gc.is_tracked(obj)
        assert track(obj) is None
        assert gc.is_tracked(obj) or not was
    obj = _routed()
    track(obj)
    track(obj)  # twice: the second finds it tracked
    assert gc.is_tracked(obj) and not obj._loaded


def test_rawjson_works_where_the_module_did_not_build(monkeypatch):
    monkeypatch.setattr(rawjson, "_gc_track", None)
    obj = RawJSON(NESTED)
    assert obj["kind"] == "Pod" and obj._loaded and gc.is_tracked(obj)
    twin = pickle.loads(pickle.dumps(obj))
    assert twin == obj and gc.is_tracked(twin)

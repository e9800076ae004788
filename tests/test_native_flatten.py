"""Differential test: the native flattener must produce bit-identical columns
(and identical vocab interning) to the Python reference implementation."""

import random

import numpy as np
import pytest

from gatekeeper_tpu.ops import native
from gatekeeper_tpu.ops.flatten import (
    Axis,
    Flattener,
    KeySetCol,
    RaggedCol,
    ScalarCol,
    Schema,
    Vocab,
)


def make_schema():
    containers = Axis(((("spec", "containers"),),
                       (("spec", "initContainers"),)))
    ports = Axis(((("spec", "containers"), ("ports",)),
                  (("spec", "initContainers"), ("ports",))))
    s = Schema()
    s.scalars = [ScalarCol(("spec", "hostNetwork")),
                 ScalarCol(("spec", "priority")),
                 ScalarCol(("metadata", "name"))]
    s.raggeds = [RaggedCol(containers, ("securityContext", "privileged")),
                 RaggedCol(containers, ("name",)),
                 RaggedCol(containers, ()),
                 RaggedCol(ports, ("hostPort",))]
    s.keysets = [KeySetCol(("metadata", "labels"))]
    return s


def make_objects(n, seed=0):
    rng = random.Random(seed)
    objs = []
    for i in range(n):
        containers = []
        for j in range(rng.randint(0, 4)):
            c = {"name": f"c{j}"}
            if rng.random() < 0.5:
                c["securityContext"] = {"privileged": rng.choice(
                    [True, False, "x", 1, None])}
            if rng.random() < 0.4:
                c["ports"] = [{"hostPort": rng.randint(1, 70000)}
                              for _ in range(rng.randint(0, 3))]
            containers.append(c)
        obj = {
            "apiVersion": rng.choice(["v1", "apps/v1", "batch/v1"]),
            "kind": rng.choice(["Pod", "Deployment"]),
            "metadata": {
                "name": f"o{i}",
                "namespace": rng.choice(["default", "kube-system", ""]),
            },
            "spec": {"containers": containers},
        }
        if rng.random() < 0.3:
            obj["metadata"]["labels"] = {
                f"k{x}": f"v{x}" for x in range(rng.randint(1, 4))
            }
        if rng.random() < 0.3:
            obj["spec"]["hostNetwork"] = rng.choice([True, False, "maybe"])
        if rng.random() < 0.3:
            obj["spec"]["priority"] = rng.choice([1, 2.5, -3, "high"])
        if rng.random() < 0.2:
            obj["spec"]["initContainers"] = [{"name": "init"}]
        objs.append(obj)
    return objs


@pytest.mark.skipif(native.load() is None, reason="native build unavailable")
def test_native_matches_python():
    schema = make_schema()
    objs = make_objects(300)
    v_py, v_c = Vocab(), Vocab()
    py = Flattener(schema, v_py, use_native=False).flatten(objs, pad_n=320)
    nat = Flattener(schema, v_c, use_native=True)._flatten_native(
        native.load(), objs, 320)

    assert v_py._to_str == v_c._to_str  # identical interning order
    np.testing.assert_array_equal(py.group_sid, nat.group_sid)
    np.testing.assert_array_equal(py.kind_sid, nat.kind_sid)
    np.testing.assert_array_equal(py.ns_sid, nat.ns_sid)
    np.testing.assert_array_equal(py.name_sid, nat.name_sid)
    for spec in schema.scalars:
        np.testing.assert_array_equal(py.scalars[spec].kind,
                                      nat.scalars[spec].kind, err_msg=str(spec))
        np.testing.assert_array_equal(py.scalars[spec].num,
                                      nat.scalars[spec].num)
        np.testing.assert_array_equal(py.scalars[spec].sid,
                                      nat.scalars[spec].sid)
    for axis in schema.axes():
        np.testing.assert_array_equal(py.axis_counts[axis],
                                      nat.axis_counts[axis])
    for spec in schema.raggeds:
        np.testing.assert_array_equal(py.raggeds[spec].kind,
                                      nat.raggeds[spec].kind, err_msg=str(spec))
        np.testing.assert_array_equal(py.raggeds[spec].num,
                                      nat.raggeds[spec].num)
        np.testing.assert_array_equal(py.raggeds[spec].sid,
                                      nat.raggeds[spec].sid)
    for spec in schema.keysets:
        np.testing.assert_array_equal(py.keysets[spec].sid,
                                      nat.keysets[spec].sid)
        np.testing.assert_array_equal(py.keysets[spec].count,
                                      nat.keysets[spec].count)


@pytest.mark.skipif(native.load() is None, reason="native build unavailable")
def test_native_empty_and_weird_inputs():
    schema = make_schema()
    mod = native.load()
    for objs in ([], [{}], [{"spec": None}], [{"spec": {"containers": "x"}}]):
        v1, v2 = Vocab(), Vocab()
        py = Flattener(schema, v1, use_native=False).flatten(objs, pad_n=8)
        nat = Flattener(schema, v2, use_native=True)._flatten_native(
            mod, objs, 8)
        for axis in schema.axes():
            np.testing.assert_array_equal(py.axis_counts[axis],
                                          nat.axis_counts[axis])
        for spec in schema.scalars:
            np.testing.assert_array_equal(py.scalars[spec].kind,
                                          nat.scalars[spec].kind)


@pytest.mark.skipif(native.load() is None, reason="native build unavailable")
def test_native_huge_int_saturates_no_pending_exception():
    # ADVICE r1: PyLong_AsDouble overflow must not leave a pending exception;
    # both flatteners saturate to +/-inf with the right sign
    schema = make_schema()
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "big"},
             "spec": {"priority": 10 ** 400}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "neg"},
             "spec": {"priority": -(10 ** 400)}}]
    v1, v2 = Vocab(), Vocab()
    py = Flattener(schema, v1, use_native=False).flatten(objs, pad_n=4)
    nat = Flattener(schema, v2, use_native=True)._flatten_native(
        native.load(), objs, 4)
    spec = schema.scalars[1]  # spec.priority
    np.testing.assert_array_equal(py.scalars[spec].num, nat.scalars[spec].num)
    assert np.isposinf(nat.scalars[spec].num[0])
    assert np.isneginf(nat.scalars[spec].num[1])
    # no pending exception corrupts the next unrelated call
    assert 1 + 1 == 2


@pytest.mark.skipif(native.load() is None, reason="native build unavailable")
def test_native_extract_extras_matches_python():
    """parent-idx and ragged-keyset columns: C extract_extras vs the Python
    loops, bit-identical (incl. vocab interning order)."""
    from gatekeeper_tpu.ops.flatten import ParentIdxCol, RaggedKeySetCol

    containers = Axis(((("spec", "containers"),),
                       (("spec", "initContainers"),)))
    drops = Axis(((("spec", "containers"),
                   ("securityContext", "capabilities", "drop")),
                  (("spec", "initContainers"),
                   ("securityContext", "capabilities", "drop"))))
    s = Schema()
    s.raggeds = [RaggedCol(containers, ("name",)),
                 RaggedCol(drops, ())]
    s.parent_idx = [ParentIdxCol(axis=drops, parent=containers)]
    s.ragged_keysets = [RaggedKeySetCol(axis=containers, subpath=())]

    rng = random.Random(5)
    objs = []
    for i in range(200):
        cs = []
        for j in range(rng.randint(0, 4)):
            c = {"name": f"c{j}"}
            if rng.random() < 0.6:
                c["securityContext"] = {"capabilities": {
                    "drop": [rng.choice(["ALL", "NET_RAW", "KILL"])
                             for _ in range(rng.randint(0, 3))]}}
            if rng.random() < 0.3:
                c["livenessProbe"] = {"tcpSocket": {}}
            if rng.random() < 0.2:
                c["extra"] = False  # truthy-key filter
            cs.append(c)
        spec = {"containers": cs}
        if rng.random() < 0.3:
            spec["initContainers"] = [{"name": "i", "securityContext": {
                "capabilities": {"drop": ["X"]}}}]
        objs.append({"apiVersion": "v1", "kind": "Pod",
                     "metadata": {"name": f"p{i}"}, "spec": spec})

    v_py, v_c = Vocab(), Vocab()
    py = Flattener(s, v_py, use_native=False).flatten(objs, pad_n=256)
    nat = Flattener(s, v_c, use_native=True).flatten(objs, pad_n=256)
    assert v_py._to_str == v_c._to_str
    for spec_ in s.parent_idx:
        np.testing.assert_array_equal(py.parent_idx[spec_].idx,
                                      nat.parent_idx[spec_].idx)
    for spec_ in s.ragged_keysets:
        np.testing.assert_array_equal(py.ragged_keysets[spec_].sid,
                                      nat.ragged_keysets[spec_].sid)
        np.testing.assert_array_equal(py.ragged_keysets[spec_].count,
                                      nat.ragged_keysets[spec_].count)


def test_a_second_thread_waits_for_the_first_load(monkeypatch):
    """Two flatten workers ask for one module at once on a process's
    first chunk: the second gets the module the first is still building,
    not "tried, none there" (which sent its chunk down the dict lane)."""
    import threading
    import time

    built = object()
    building = threading.Event()

    def slow_build(name, src_file):
        building.set()
        time.sleep(0.2)
        return built

    monkeypatch.setattr(native, "_build", slow_build)
    monkeypatch.setattr(native, "_mods", {})
    monkeypatch.setattr(native, "_tried", set())
    got = []
    first = threading.Thread(target=lambda: got.append(
        native._load_named("gtpu_flatten", "flattenmod.c")))
    first.start()
    assert building.wait(5.0)
    got.append(native._load_named("gtpu_flatten", "flattenmod.c"))
    first.join()
    assert got == [built, built]

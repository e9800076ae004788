"""Round-trip tests for the wire packing of sweep transfer columns.

Bytes on the host->device wire are narrowed: pack_transfer_cols narrows
column dtypes (uint16/uint8/nibble with a +1 bias for the -1 sentinel),
dictionary-remaps low-cardinality wide-range columns, and elides
corpus-constant columns — all driven by corpus stats so the wire layout
is identical for every chunk of a run.  These tests pin the exactness
contract: unpack(pack(cols)) == cols bit-for-bit, for every wire kind
and for chunks that drift outside the corpus stats (which must fall
back to wider dtypes, never produce wrong values).

Two implementations pack: ``pack_transfer_cols`` (one native call per
chunk, native/wirepackmod.c, wherever the stats settle the layout) and
``pack_transfer_cols_py`` (numpy; the reference, the fallback and the
path of a drifted chunk).  The round trips run as cases of both, and the
differential tests hold the two to the same bytes and the same layout.
"""

import numpy as np
import jax
import pytest

from gatekeeper_tpu.ops import native
from gatekeeper_tpu.parallel import sharded
from gatekeeper_tpu.parallel.sharded import (col_stats_update,
                                             pack_transfer_cols,
                                             pack_transfer_cols_py,
                                             unpack_transfer_cols)

N = 64

needs_native = pytest.mark.skipif(
    native.load_wirepack() is None,
    reason="native/wirepackmod.c did not build")

IMPLS = [pytest.param(pack_transfer_cols, id="native", marks=needs_native),
         pytest.param(pack_transfer_cols_py, id="numpy")]


def _mk_cols(rng):
    return {
        # u2 sid + nibble kind + integral-float num
        "a": {"sid": rng.integers(-1, 40000, (N, 8)).astype(np.int32),
              "kind": rng.integers(-1, 7, (N, 8)).astype(np.int8),
              "num": rng.integers(0, 60000, (N, 8)).astype(np.float32)},
        # dictionary remap (4 distinct values, range >> u1) + odd-width
        # nibble candidate that must fall back to u1
        "b": {"sid": rng.choice(
                  np.array([-1, 5, 70000, 123456], np.int32), (N, 4)),
              "count": rng.integers(0, 8, N).astype(np.int32)},
        # corpus-constant: elided to a layout scalar
        "c": np.full((N, 8), -1, np.int32),
        # genuine floats: passthrough
        "d": {"num": rng.standard_normal((N, 2)).astype(np.float32)},
    }


def _roundtrip(pack, cols, stats, n=N):
    bufs, layout = pack(cols, n, stats=stats)
    out = jax.jit(lambda b: unpack_transfer_cols(b, layout, n))(
        {k: np.ascontiguousarray(v) for k, v in bufs.items()})
    return bufs, layout, out


def _assert_equal(out, cols, names):
    for key, sub in names:
        x = np.asarray(out[key][sub] if sub else out[key])
        y = np.asarray(cols[key][sub] if sub else cols[key])
        assert x.dtype == y.dtype, (key, sub, x.dtype, y.dtype)
        assert np.array_equal(x, y), (key, sub)


ALL = [("a", "sid"), ("a", "kind"), ("a", "num"),
       ("b", "sid"), ("b", "count"), ("c", None), ("d", "num")]


def _drifted(cols):
    drift = {k: ({s: v.copy() for s, v in val.items()}
                 if isinstance(val, dict) else val.copy())
             for k, val in cols.items()}
    drift["b"]["sid"][0, 0] = 999999   # outside the corpus dictionary
    drift["a"]["kind"][0, 0] = 100     # outside the nibble range
    drift["c"][0, 0] = 7               # breaks the constant
    drift["a"]["num"][0, 0] = 0.5      # corpus-integral f4 drifts fractional
    drift["d"]["num"][0, 0] = 0.5      # (already non-integral: no-op)
    return drift


@pytest.mark.parametrize("pack", IMPLS)
def test_roundtrip_all_wire_kinds(pack):
    rng = np.random.default_rng(0)
    cols = _mk_cols(rng)
    stats = {}
    col_stats_update(stats, cols)
    bufs, layout, out = _roundtrip(pack, cols, stats)
    _assert_equal(out, cols, ALL)
    kinds = {e[2] for e in layout}
    # the fixture must actually exercise every wire kind
    assert {"<u2", "|n1", "|u1", "const", "<f4"} <= kinds
    # elision really dropped the constant column from the buffers
    total = sum(b.nbytes for b in bufs.values())
    assert total < sum(
        np.asarray(v).nbytes
        for val in cols.values()
        for v in (val.values() if isinstance(val, dict) else [val]))


@pytest.mark.parametrize("pack", IMPLS)
def test_drift_chunk_falls_back_wider_never_wrong(pack):
    rng = np.random.default_rng(1)
    cols = _mk_cols(rng)
    stats = {}
    col_stats_update(stats, cols)
    drift = _drifted(cols)
    _, _, out = _roundtrip(pack, drift, stats)
    _assert_equal(out, drift, ALL)


@pytest.mark.parametrize("pack", IMPLS)
def test_no_stats_passthrough(pack):
    rng = np.random.default_rng(2)
    cols = _mk_cols(rng)
    _, layout, out = _roundtrip(pack, cols, None)
    _assert_equal(out, cols, ALL)
    assert {e[2] for e in layout} == {"<i4", "|i1", "<f4"}


@pytest.mark.parametrize("pack", IMPLS)
def test_multichunk_stats_union_keeps_layout_stable(pack):
    rng = np.random.default_rng(3)
    chunks = [_mk_cols(rng) for _ in range(3)]
    stats = {}
    for ch in chunks:
        col_stats_update(stats, ch)
    layouts = []
    for ch in chunks:
        _, layout, out = _roundtrip(pack, ch, stats)
        _assert_equal(out, ch, ALL)
        layouts.append(layout)
    # one wire layout across every chunk: no mid-run retrace
    assert layouts[0] == layouts[1] == layouts[2]


# --- the native pass against the numpy form ---------------------------------

PAD = 80  # rows of a chunk; the last 16 are padding


def _wide_cols(rng, n=PAD, real=N):
    """Every wire kind from every source dtype the flattener emits, over
    ``real`` objects padded to ``n`` rows with each family's pad value."""
    def padded(a, pad):
        a[real:] = pad
        return a

    shared = padded(rng.integers(-1, 1000, (n, 6)).astype(np.int32), -1)
    wide = np.asfortranarray(                     # a non-contiguous source
        padded(rng.integers(-1, 9, (n, 4)).astype(np.int32), -1))
    assert n < 2 or not wide.flags["C_CONTIGUOUS"]
    return {
        "rg:a": {
            "sid": padded(rng.integers(-1, 40000, (n, 8)).astype(np.int32),
                          -1),                                  # <u2
            "kind": padded(rng.integers(-1, 7, (n, 8)).astype(np.int8),
                           0),                                  # |n1 from |i1
            "num": padded(rng.integers(0, 60000, (n, 8)).astype(np.float32),
                          0.0),                                 # f4 -> <u2
            "idx": padded(rng.integers(-1, 200, (n, 8)).astype(np.int64),
                          -1),                                  # <i8 -> |u1
            "count": padded(rng.integers(0, 8, n).astype(np.int32), 0),
        },                                                      # odd nibble
        "rg:b": {
            "sid": padded(rng.choice(np.array(
                [-1, 5, 70000, 123456], np.int32), (n, 4)), -1),  # dict
            "kind": padded(rng.integers(0, 5, (n, 3, 4)).astype(np.int32),
                           0),                       # |n1, a tail of two axes
            "num": padded(rng.integers(0, 12, (n, 2)).astype(np.float32),
                          0.0),                                 # f4 -> |n1
        },
        "c": np.full((n, 8), -1, np.int32),                     # const
        "cf": np.full(n, 2.5, np.float32),                      # const, float
        "cb": np.ones((n, 2), np.bool_),                        # const, bool
        "d": {"num": rng.standard_normal((n, 2)).astype(np.float32)},
        "e": rng.integers(-70000, 70000, (n, 4)).astype(np.int32),  # <i4
        "f": rng.integers(-5, 100000, (n, 4)).astype(np.int64)
        * 2 ** 20,                                              # <i8 as it is
        "g": rng.choice(np.array([-7, 3, 2 ** 40], np.int64), (n, 2)),
        "h": rng.integers(-1, 60, (n, 3)).astype(np.int8),      # |i1 -> |u1
        "i": rng.integers(0, 2, (n, 3)).astype(np.bool_),       # copied
        "j": np.zeros((n, 0), np.int32),                        # empty
        "k": shared, "k2": shared,                              # alias
        "w": wide,
        "fn:table": np.arange(7),                               # not shipped
    }


def _stats_of(*chunks):
    stats = {}
    for ch in chunks:
        col_stats_update(stats, ch)
    sharded.merge_pad_stats(stats)
    return stats


def _both(cols, n, stats):
    counts = {}
    got = pack_transfer_cols(cols, n, stats=stats, counts=counts)
    want = pack_transfer_cols_py(cols, n, stats=stats)
    return got, want, counts


def _assert_same(got, want):
    (bufs, layout), (bufs_py, layout_py) = got, want
    assert layout == layout_py
    assert [type(e[7]) for e in layout] == [type(e[7]) for e in layout_py]
    assert list(bufs) == list(bufs_py)
    for wdt, b in bufs_py.items():
        assert bufs[wdt].dtype == b.dtype and bufs[wdt].shape == b.shape
        assert bufs[wdt].flags["C_CONTIGUOUS"]
        assert bufs[wdt].tobytes() == b.tobytes(), wdt


def _unpacked(got, n):
    bufs, layout = got
    # int64 does not survive the device's 32 bits: the bytes are held
    layout = tuple(e for e in layout if e[0] not in ("f", "g"))
    return jax.jit(lambda b: unpack_transfer_cols(b, layout, n))(dict(bufs))


WIDE = [(k, s) for k, v in _wide_cols(np.random.default_rng(0)).items()
        if not k.startswith("fn:")
        for s in (sorted(v) if isinstance(v, dict) else [None])]


@needs_native
def test_native_pass_equals_numpy_on_every_wire_kind():
    rng = np.random.default_rng(10)
    chunks = [_wide_cols(rng) for _ in range(3)]
    stats = _stats_of(*chunks)
    for cols in chunks:
        got, want, counts = _both(cols, PAD, stats)
        _assert_same(got, want)
        layout = got[1]
        by = {(e[0], e[1]): e for e in layout}
        assert {(k, s): by[k, s][2] for k, s in WIDE} == {
            ("rg:a", "sid"): "<u2", ("rg:a", "kind"): "|n1",
            ("rg:a", "num"): "<u2", ("rg:a", "idx"): "|u1",
            ("rg:a", "count"): "|u1", ("rg:b", "sid"): "|u1",
            ("rg:b", "kind"): "|n1", ("rg:b", "num"): "|n1",
            ("c", None): "const", ("cf", None): "const",
            ("cb", None): "const", ("d", "num"): "<f4", ("e", None): "<i4",
            ("f", None): "<i8", ("g", None): "|u1", ("h", None): "|u1",
            ("i", None): "|b1", ("j", None): "<i4", ("k", None): "<u2",
            ("k2", None): "alias", ("w", None): "|n1"}
        assert by["rg:b", "sid"][7] == (-1, 5, 70000, 123456)
        assert by["g", None][7] == (-7, 3, 2 ** 40)
        # every shipped column went through the native call
        shipped = sum(e[2] not in ("alias", "const") for e in layout)
        assert counts == {"fused": shipped, "numpy": 0} and shipped == 17
        out = _unpacked(got, PAD)
        for key, sub in WIDE:
            if key in ("f", "g"):
                continue
            x = np.asarray(out[key][sub] if sub else out[key])
            y = cols[key][sub] if sub else cols[key]
            want_dt = np.int32 if y.dtype == np.int64 else y.dtype
            assert x.dtype == want_dt and np.array_equal(x, y), (key, sub)


def _drift_range(c):
    c["rg:a"]["sid"][3, 1] = 70000        # past <u2


def _drift_below(c):
    c["h"][5, 0] = -2                     # below the -1 the bias takes


def _drift_nibble(c):
    c["rg:a"]["kind"][0, 0] = 100


def _drift_dict(c):
    c["rg:b"]["sid"][7, 2] = 6            # inside the range, in no dictionary


def _drift_dict_wide(c):
    c["g"][1, 1] = 2 ** 41                # the hashed dictionary


def _drift_fraction(c):
    c["rg:a"]["num"][2, 2] = 0.5


def _drift_nan(c):
    c["rg:b"]["num"][4, 1] = np.nan


def _drift_const(c):
    c["c"][N - 1, 7] = 0


def _drift_const_float(c):
    c["cf"][0] = 2.25


def _drift_const_bool(c):
    c["cb"][9, 1] = False


def _drift_pad_only(c):
    c["rg:a"]["sid"][PAD - 1, 0] = 2 ** 20    # a padding row drifts too


DRIFTS = [_drift_range, _drift_below, _drift_nibble, _drift_dict,
          _drift_dict_wide, _drift_fraction, _drift_nan, _drift_const,
          _drift_const_float, _drift_const_bool, _drift_pad_only]


@needs_native
@pytest.mark.parametrize("drift", DRIFTS, ids=lambda f: f.__name__[7:])
def test_a_drifted_chunk_comes_back_whole_by_the_numpy_form(drift):
    rng = np.random.default_rng(11)
    clean, cols = _wide_cols(rng), _wide_cols(rng)
    stats = _stats_of(clean, cols)
    stable = pack_transfer_cols(cols, PAD, stats)[1]
    assert stable == pack_transfer_cols(clean, PAD, stats)[1]
    drift(cols)
    got, want, counts = _both(cols, PAD, stats)
    _assert_same(got, want)
    # the wider layout, one retrace, every column by the numpy form
    assert got[1] != stable
    shipped = sum(e[2] not in ("alias", "const") for e in got[1])
    assert counts == {"fused": 0, "numpy": shipped}
    out = _unpacked(got, PAD)
    for key, sub in WIDE:
        if key in ("f", "g"):
            continue
        x = np.asarray(out[key][sub] if sub else out[key])
        y = cols[key][sub] if sub else cols[key]
        assert np.array_equal(x, y, equal_nan=y.dtype.kind == "f"), \
            (key, sub)


@needs_native
def test_past_the_stats_inside_the_stored_type_is_no_drift():
    """The chunk's range widens a column only when it leaves the stored
    type: a value the stats never saw that still fits ships fused, with
    the layout and the bytes the numpy form gives."""
    rng = np.random.default_rng(12)
    cols = _wide_cols(rng)
    cols["rg:a"]["kind"][:] = np.minimum(cols["rg:a"]["kind"], 3)
    stats = _stats_of(cols)
    stable = pack_transfer_cols(cols, PAD, stats)[1]
    cols["rg:a"]["kind"][0, 0] = 14       # stats end at 3; a nibble holds 14
    cols["rg:a"]["sid"][0, 0] = 65534     # stats end below 40000
    got, want, counts = _both(cols, PAD, stats)
    _assert_same(got, want)
    assert got[1] == stable and counts["numpy"] == 0 and counts["fused"]
    cols["rg:a"]["kind"][0, 0] = 15       # 15 + 1 is past the nibble
    got, want, counts = _both(cols, PAD, stats)
    _assert_same(got, want)
    assert got[1] != stable and counts["fused"] == 0


@needs_native
@pytest.mark.parametrize("n", [0, 1, 255, 256, 257, 1000])
def test_row_counts_around_the_native_block(n):
    """Zero rows, one, and both sides of the 256-row block the native
    pass walks."""
    rng = np.random.default_rng(13)
    cols = _wide_cols(rng, n=n, real=max(0, n - 3))
    stats = _stats_of(_wide_cols(rng), cols)
    got, want, counts = _both(cols, n, stats)
    _assert_same(got, want)
    assert counts["numpy"] == 0


@needs_native
def test_stats_written_by_hand_never_give_another_answer():
    """Three-field stats, a fractional range, a constant an integer column
    cannot equal, a dictionary on a dtype the native pass does not look
    up: whatever the numpy form makes of them, the native pass makes the
    same or hands the chunk over."""
    rng = np.random.default_rng(14)
    cols = {"a": rng.integers(0, 9, (N, 2)).astype(np.int32),
            "b": rng.integers(0, 9, (N, 2)).astype(np.int16),
            "c": np.full((N, 2), 3, np.int32),
            "d": rng.integers(0, 9, (N, 2)).astype(np.float32)}
    for stats in (
            {("a", None): (0, 8, None)},
            {("a", None): (-0.5, 8.5, None, None, False)},
            {("c", None): (3, 3, 3.5, None, False)},
            {("c", None): (3, 3, 3.0, None, False)},
            {("b", None): (0, 8, 4, None, False)},
            {("d", None): (0.0, 8.0, None, frozenset({1, 2}), True)},
            {("d", None): (0.0, 70000.0, None, frozenset(range(9)), True)},
            {("a", None): (0, 70000, None, frozenset(range(9)), False),
             ("d", None): (0.0, 8.0, None, None, True)}):
        got, want, _ = _both(cols, N, stats)
        _assert_same(got, want)


@needs_native
def test_the_steps_that_failed_are_named():
    mod = native.load_wirepack()
    a = np.arange(8, dtype=np.int32).reshape(4, 2)
    buf = np.empty((4, 5), np.uint8)
    dv = np.array([0, 1, 2, 3, 4, 5, 6, 9], np.int64)
    steps = [(mod.BIAS, a, buf, 0, 1),
             (mod.NIBBLE, a + 8, buf, 2, 1),          # 15 + 1 does not fit
             (mod.DICT, a, buf, 3, dv),               # 7 is not in it
             (mod.CHECK, a, None, 0, 0),
             (mod.CHECK, np.zeros((4, 2), np.float32), None, 0, 0.0),
             (mod.CHECK, np.full((4, 2), 3, np.int8), None, 0, 3.0)]
    assert mod.pack(steps, 4) == [1, 2, 3]
    assert buf[:, 0:2].tolist() == (a + 1).tolist()
    assert mod.pack([], 4) == [] and mod.pack(steps, 0) == []
    with pytest.raises(ValueError):
        mod.pack([(mod.BIAS, a, buf, 4, 1)], 4)   # past the buffer
    with pytest.raises(ValueError):
        mod.pack([(mod.NIBBLE, a[:, :1].copy(), buf, 0, 1)], 4)
    with pytest.raises(ValueError):
        mod.pack([(mod.BIAS, a, buf, 0, 1)], 3)   # not [n, w]
    with pytest.raises(TypeError):
        mod.pack([(mod.BIAS, a.astype(np.float64), buf, 0, 1)], 4)
    with pytest.raises(ValueError):
        mod.pack([(mod.DICT, a, buf, 0, dv[:0])], 4)


def test_without_the_module_the_numpy_form_packs(monkeypatch):
    rng = np.random.default_rng(15)
    cols = _wide_cols(rng)
    stats = _stats_of(cols)
    monkeypatch.setattr(native, "load_wirepack", lambda: None)
    counts = {}
    got = pack_transfer_cols(cols, PAD, stats=stats, counts=counts)
    _assert_same(got, pack_transfer_cols_py(cols, PAD, stats=stats))
    shipped = sum(e[2] not in ("alias", "const") for e in got[1])
    assert counts == {"fused": 0, "numpy": shipped}
    # and without stats the module is not asked
    monkeypatch.setattr(native, "load_wirepack",
                        lambda: pytest.fail("no plan without stats"))
    counts = {}
    pack_transfer_cols(cols, PAD, stats=None, counts=counts)
    assert counts["fused"] == 0 and counts["numpy"] == 20

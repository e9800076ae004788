"""Round-trip tests for the wire packing of sweep transfer columns.

Bytes on the host->device wire are narrowed: pack_transfer_cols narrows
column dtypes (uint16/uint8/nibble with a +1 bias for the -1 sentinel),
dictionary-remaps low-cardinality wide-range columns, and elides
corpus-constant columns — all driven by corpus stats so the wire layout
is identical for every chunk of a run.  These tests pin the exactness
contract: unpack(pack(cols)) == cols bit-for-bit, for every wire kind
and for chunks that drift outside the corpus stats (which must fall
back to wider dtypes, never produce wrong values).
"""

import numpy as np
import jax
import pytest

from gatekeeper_tpu.parallel.sharded import (col_stats_update,
                                             pack_transfer_cols,
                                             unpack_transfer_cols)

N = 64


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


def _roundtrip(cols, stats):
    bufs, layout = pack_transfer_cols(cols, N, stats=stats)
    out = jax.jit(lambda b: unpack_transfer_cols(b, layout, N))(
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


def test_roundtrip_all_wire_kinds():
    rng = np.random.default_rng(0)
    cols = _mk_cols(rng)
    stats = {}
    col_stats_update(stats, cols)
    bufs, layout, out = _roundtrip(cols, stats)
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


def test_drift_chunk_falls_back_wider_never_wrong():
    rng = np.random.default_rng(1)
    cols = _mk_cols(rng)
    stats = {}
    col_stats_update(stats, cols)
    drift = {k: ({s: v.copy() for s, v in val.items()}
                 if isinstance(val, dict) else val.copy())
             for k, val in cols.items()}
    drift["b"]["sid"][0, 0] = 999999   # outside the corpus dictionary
    drift["a"]["kind"][0, 0] = 100     # outside the nibble range
    drift["c"][0, 0] = 7               # breaks the constant
    drift["a"]["num"][0, 0] = 0.5      # corpus-integral f4 drifts fractional
    drift["d"]["num"][0, 0] = 0.5      # (already non-integral: no-op)
    _, _, out = _roundtrip(drift, stats)
    _assert_equal(out, drift, ALL)


def test_no_stats_passthrough():
    rng = np.random.default_rng(2)
    cols = _mk_cols(rng)
    _, layout, out = _roundtrip(cols, None)
    _assert_equal(out, cols, ALL)
    assert {e[2] for e in layout} == {"<i4", "|i1", "<f4"}


def test_multichunk_stats_union_keeps_layout_stable():
    rng = np.random.default_rng(3)
    chunks = [_mk_cols(rng) for _ in range(3)]
    stats = {}
    for ch in chunks:
        col_stats_update(stats, ch)
    layouts = []
    for ch in chunks:
        _, layout, out = _roundtrip(ch, stats)
        _assert_equal(out, ch, ALL)
        layouts.append(layout)
    # one wire layout across every chunk: no mid-run retrace
    assert layouts[0] == layouts[1] == layouts[2]

"""The Pallas verdict-epilogue kernel must agree with the XLA twin
(parallel.sharded.topk_violations) under the valid-mask, for every grid
shape class the sweep produces.  These tests run the kernel through the
Pallas interpreter (``interpret=True`` — an argument only tests pass;
production call sites always compile it with Mosaic, and chip_smoke.py
repeats the comparison on the chip)."""

import numpy as np
import jax.numpy as jnp
import pytest

from gatekeeper_tpu.ops.pallas_topk import (fused_fold_pallas,
                                            topk_violations_counts_pallas,
                                            topk_violations_pallas)
from gatekeeper_tpu.parallel.sharded import topk_violations


def _agree(verdicts: np.ndarray, k: int):
    g = jnp.asarray(verdicts)
    xi, xv = topk_violations(g, k)
    pi, pv, pc = topk_violations_counts_pallas(g, k, interpret=True)
    xi, xv = np.asarray(xi), np.asarray(xv)
    pi, pv = np.asarray(pi), np.asarray(pv)
    assert np.array_equal(xv, pv), "valid masks differ"
    assert np.array_equal(np.where(xv, xi, -1), np.where(pv, pi, -1)), \
        "selected indices differ under the valid mask"
    # the kernel's fused count lane must be the exact row sums
    assert np.array_equal(np.asarray(pc), verdicts.sum(axis=1))


def test_dense_sparse_empty_rows():
    rng = np.random.default_rng(0)
    v = rng.random((46, 4096)) < 0.01      # sparse
    v[3] = False                            # empty row
    v[7] = True                             # full row
    v[11, -1] = True                        # lone hit at the tail
    _agree(v, 20)


def test_k_larger_than_hits_and_row():
    rng = np.random.default_rng(1)
    v = rng.random((5, 64)) < 0.2
    _agree(v, 20)   # k < n but > hits in most rows
    _agree(v, 64)   # k == n


def test_k_beyond_lane_tile_falls_back():
    rng = np.random.default_rng(3)
    v = rng.random((4, 512)) < 0.3
    _agree(v, 128)  # k >= _KPAD: routes through the XLA twin
    _agree(v, 200)


def test_lane_tiling_carries_selection_across_tiles():
    """N beyond one lane tile: the first-k selection and the counts
    accumulate across tiles (hits before, inside and after tile
    boundaries, rows that fill up early, rows whose hits start late)."""
    from gatekeeper_tpu.ops.pallas_topk import _TN

    rng = np.random.default_rng(7)
    n = 3 * _TN + 640                      # ragged last tile (padded)
    v = rng.random((9, n)) < 0.002
    v[0] = False
    v[0, [_TN - 1, _TN, 2 * _TN + 5]] = True   # straddles boundaries
    v[1] = False
    v[1, 3 * _TN + 600:] = True                # hits only in the tail
    v[2, :_TN] = True                          # full before tile 1
    v[3] = False
    _agree(v, 20)
    mask = rng.random((9, n)) < 0.6
    _fold_agree(v, mask, 20)


def test_row_padding_to_sublane_tile():
    rng = np.random.default_rng(2)
    for c in (1, 7, 8, 9, 46):
        v = rng.random((c, 512)) < 0.05
        _agree(v, 20)


def _fold_agree(grid_raw: np.ndarray, mask: np.ndarray, k: int):
    """fused_fold_pallas == XLA reference fold, bit for bit: top-k of
    the masked grid, masked row sums (violation totals), mask row sums
    (occupancy — the resident lane's device-vs-host mirror invariant)."""
    g, m = jnp.asarray(grid_raw), jnp.asarray(mask)
    masked = grid_raw & mask
    xi, xv = topk_violations(jnp.asarray(masked), min(k, masked.shape[1]))
    pi, pv, pc, po = fused_fold_pallas(g, m, k, interpret=True)
    xi, xv = np.asarray(xi), np.asarray(xv)
    pi, pv = np.asarray(pi), np.asarray(pv)
    assert np.array_equal(xv, pv), "valid masks differ"
    assert np.array_equal(np.where(xv, xi, -1), np.where(pv, pi, -1)), \
        "selected indices differ under the valid mask"
    assert np.array_equal(np.asarray(pc), masked.sum(axis=1))
    assert np.array_equal(np.asarray(po), mask.sum(axis=1))


def test_fused_fold_matches_xla_fold():
    rng = np.random.default_rng(4)
    grid = rng.random((46, 4096)) < 0.02   # raw verdicts (pre-mask)
    mask = rng.random((46, 4096)) < 0.7    # scope mask
    grid[5] = True                          # full row
    mask[9] = False                         # fully out-of-scope row
    grid[13] = False                        # clean row
    mask[21, :7] = True                     # sliver-scoped row
    _fold_agree(grid, mask, 20)


def test_fused_fold_shape_classes_and_k_edges():
    rng = np.random.default_rng(5)
    for c in (1, 7, 8, 46):
        grid = rng.random((c, 512)) < 0.1
        mask = rng.random((c, 512)) < 0.5
        _fold_agree(grid, mask, 20)
    grid = rng.random((4, 64)) < 0.3
    mask = rng.random((4, 64)) < 0.5
    _fold_agree(grid, mask, 64)    # k == n
    _fold_agree(grid, mask, 200)   # k > n: clamped


def test_fused_fold_k_beyond_lane_tile_falls_back():
    rng = np.random.default_rng(6)
    grid = rng.random((4, 512)) < 0.2
    mask = rng.random((4, 512)) < 0.6
    _fold_agree(grid, mask, 127)   # k == _KPAD - 1: XLA fallback
    _fold_agree(grid, mask, 300)


def test_first_k_are_lowest_indices():
    v = np.zeros((2, 256), bool)
    hits = [5, 17, 99, 100, 255]
    v[0, hits] = True
    idx, valid = topk_violations_pallas(jnp.asarray(v), 3,
                                        interpret=True)
    assert np.asarray(idx)[0, :3].tolist() == hits[:3]
    assert np.asarray(valid)[0].tolist() == [True, True, True]
    assert not np.asarray(valid)[1].any()


def test_production_call_sites_never_interpret():
    """Without the test-only argument the kernel goes to the compiler:
    off the TPU that is an error, never a silent interpreter run."""
    v = jnp.zeros((8, 256), bool)
    with pytest.raises(ValueError, match="interpret mode"):
        topk_violations_counts_pallas(v, 3)
    with pytest.raises(ValueError, match="interpret mode"):
        fused_fold_pallas(v, v, 3)

"""Pallas TPU kernel for the audit sweep's verdict epilogue.

The device side of a sweep chunk ends with, per constraint row of the
[C, N] verdict grid: the FIRST k violating object indices
(lowest-index-first — the reference's bounded max-heap LimitQueue,
pkg/audit/manager.go:161-202) and the exact violation count.  The XLA
path (parallel/sharded.topk_violations) expresses this as
``jax.lax.top_k`` over an index-scored grid — a full per-row sort-like
selection.  This kernel instead fuses count + first-k selection (and,
with a match mask, the mask apply + occupancy count) into one pass:
counts are row sums, and the first-k indices come from k iterations of
vectorized min+mask-out (O(k*N) VPU work, no sort).

Layout: the grid walks (row blocks of 8 constraints) x (lane tiles of
``_TN`` objects).  The single [8, 128] output block of a row block stays
resident in VMEM across its lane tiles and carries the running state:
lanes 0..k-1 the indices selected so far (sentinel N = no more
violations), lane k the violation count, lane k+1 the mask occupancy.
Tiles arrive in ascending index order, so a tile only ever APPENDS to
the selection — its candidates all sort after everything already kept.
VMEM use is therefore independent of N (a whole-row block at N=32,768
held ~6 [8, N] int32 temporaries plus the loop carry).  Operands are
widened to int32 by the caller-side wrapper: a bool (1-byte) operand
would need a (32, 128) tile, and the cast fuses into the producer.

The kernels agree bit for bit with ``topk_violations`` + row sums under
the valid-mask: tests/test_pallas_topk.py pins it through the Pallas
interpreter (``interpret=True`` is an argument only tests pass), and
``chip_smoke.py`` pins it compiled by Mosaic on the chip.  Callers on
non-TPU or multi-device meshes use the XLA twin instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_ROWS = 8      # constraint rows per program (i32 sublane tile)
_KPAD = 128    # output lane tile; needs k + 2 <= _KPAD
_TN = 2048     # object lanes per grid step


def _fold_kernel(k: int, n: int, masked: bool, *refs):
    """One (row block, lane tile) step of the fused fold; see the module
    docstring for the output-block layout the steps accumulate into."""
    from jax.experimental import pallas as pl

    if masked:
        grid_ref, mask_ref, out_ref = refs
    else:
        grid_ref, out_ref = refs
    t = pl.program_id(1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _KPAD), 1)

    @pl.when(t == 0)
    def _():
        out_ref[:] = jnp.where(lanes < k, n, 0).astype(jnp.int32)

    block = grid_ref[:]  # [_ROWS, tn] int32 0/1
    tn = block.shape[1]
    if masked:
        msk = mask_ref[:]
        block = block * msk
        occ = jnp.sum(msk, axis=1, keepdims=True)
    cnt = jnp.sum(block, axis=1, keepdims=True)  # [_ROWS, 1]
    idxs = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1) + t * tn
    cand = jnp.where(block != 0, idxs, n)
    out = out_ref[:]
    # slots already filled by earlier tiles: min(count so far, k)
    seen = jnp.sum(jnp.where(lanes == k, out, 0), axis=1, keepdims=True)
    found = jnp.minimum(seen, k)

    def body(j, state):
        cand, out = state
        m = jnp.min(cand, axis=1, keepdims=True)  # lowest remaining hit
        slot = found + j
        out = jnp.where((lanes == slot) & (slot < k), m, out)
        return jnp.where(cand == m, n, cand), out

    _, out = jax.lax.fori_loop(0, k, body, (cand, out))
    out = out + jnp.where(lanes == k, cnt, 0)
    if masked:
        out = out + jnp.where(lanes == k + 1, occ, 0)
    out_ref[:] = out


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _fold(grid: jnp.ndarray, mask, k: int, interpret: bool = False):
    """[C, _KPAD] int32 fold of a [C, N] verdict grid (and optional
    match mask): lanes 0..k-1 first-k indices (sentinel N), lane k the
    violation count, lane k+1 the mask occupancy."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, n = grid.shape
    c_pad = -(-c // _ROWS) * _ROWS
    tn = min(_TN, -(-n // 128) * 128)
    n_pad = -(-n // tn) * tn
    # zero padding is inert: padded rows/objects never violate or match
    operands = [jnp.pad(a.astype(jnp.int32),
                        ((0, c_pad - c), (0, n_pad - n)))
                for a in ((grid,) if mask is None else (grid, mask))]
    tile = pl.BlockSpec((_ROWS, tn), lambda i, t: (i, t),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_fold_kernel, k, n, mask is not None),
        grid=(c_pad // _ROWS, n_pad // tn),
        in_specs=[tile] * len(operands),
        out_specs=pl.BlockSpec((_ROWS, _KPAD), lambda i, t: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((c_pad, _KPAD), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="verdict_fold",  # the kernel's name in a device trace
    )(*operands)[:c]


def topk_violations_counts_pallas(verdicts: jnp.ndarray, k: int,
                                  interpret: bool = False):
    """(idx [C,k] i32, valid [C,k] bool, counts [C] i32) — the fused
    epilogue, counts included from the same pass.  Runs under the
    caller's jit so the fused sweep stays one dispatch.  Invalid slots
    carry idx 0 (the XLA twin's invalid-slot indices are arbitrary sort
    leftovers; consumers gate on ``valid``).  k beyond the 128-lane
    output tile falls back to the XLA twin."""
    c, n = verdicts.shape
    k = min(k, n)
    if k >= _KPAD:
        from gatekeeper_tpu.parallel.sharded import topk_violations

        idx, valid = topk_violations(verdicts, k)
        return idx, valid, jnp.sum(verdicts, axis=1, dtype=jnp.int32)
    out = _fold(verdicts, None, k, interpret=interpret)
    idx = out[:, :k]
    valid = idx < n
    return jnp.where(valid, idx, 0), valid, out[:, k]


def topk_violations_pallas(verdicts: jnp.ndarray, k: int,
                           interpret: bool = False):
    """Drop-in twin of parallel.sharded.topk_violations (no counts)."""
    idx, valid, _cnt = topk_violations_counts_pallas(verdicts, k,
                                                     interpret=interpret)
    return idx, valid


def fused_fold_pallas(grid_raw: jnp.ndarray, mask: jnp.ndarray, k: int,
                      interpret: bool = False):
    """(idx [C,k] i32, valid [C,k] bool, counts [C] i32, occ [C] i32)
    from the RAW (unmasked) verdict grid and the match mask in one
    fused kernel: the masked grid never materializes as an XLA
    intermediate.  Bit-identical to the XLA fold
    (``topk_violations(grid & mask, k)`` + totals + ``mask.sum``);
    callers fall back to the XLA twin when ``k`` exceeds the output
    tile's index+count+occupancy budget (k >= _KPAD - 1)."""
    c, n = grid_raw.shape
    k = min(k, n)
    if k >= _KPAD - 1:
        from gatekeeper_tpu.parallel.sharded import topk_violations

        masked = grid_raw & mask
        idx, valid = topk_violations(masked, k)
        return (idx, valid, jnp.sum(masked, axis=1, dtype=jnp.int32),
                jnp.sum(mask, axis=1, dtype=jnp.int32))
    out = _fold(grid_raw, mask, k, interpret=interpret)
    idx = out[:, :k]
    valid = idx < n
    return jnp.where(valid, idx, 0), valid, out[:, k], out[:, k + 1]

"""Multi-chip sharded evaluation: the audit sweep's scale-out plane.

Domain mapping of the parallelism axes (SURVEY.md §2.9: the reference is a
policy controller — its "parallelism" is request/constraint/object loops, not
DP/TP/PP; these are the TPU-native equivalents):

- **data axis ('data')**   — the object batch (the reference's per-object
  audit loop, manager.go:686). Sharded across chips over ICI; across hosts
  over DCN in multi-host deployments.
- **model axis ('model')** — the constraint axis (the reference's serial
  per-constraint loop, k8scel/driver.go:194). Constraint parameter tables
  shard across it when constraint counts are large; small tables replicate.
- ragged item axis stays local to a chip (sequence-analog; items of one
  object never split across chips).

XLA inserts the collectives: verdict grids are elementwise so sharded inputs
need none; the per-constraint top-k reduction gathers across the data axis
(all-gather of per-shard top-k candidates — the device analog of the
LimitQueue merge at pkg/audit/manager.go:886-945).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gatekeeper_tpu.ir.masks import selector_label_keys
from gatekeeper_tpu.ir.program import (as_grid, build_param_table, col_key,
                                        needed_fields, pack_batch_cols,
                                        slim_cols, vocab_tables)
from gatekeeper_tpu.ops import native
from gatekeeper_tpu.ops.flatten import Flattener, Schema, Vocab


def make_mesh(n_devices: Optional[int] = None,
              model_parallel: int = 1) -> Mesh:
    """A (data, model) mesh over available devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if n % model_parallel != 0:
        raise ValueError(f"{n} devices not divisible by mp={model_parallel}")
    arr = np.array(devs).reshape(n // model_parallel, model_parallel)
    return Mesh(arr, ("data", "model"))


_DICT_CAP = 254  # distinct values above this: no u1 dictionary remap


def col_stats_update(stats: dict, cols: dict) -> None:
    """Accumulate corpus-wide per-column (min, max, const-value,
    distinct-values) over the per-object transfer columns of one chunk.
    Consumed by :func:`pack_transfer_cols` to pick narrow wire dtypes,
    elide corpus-constant columns, and dictionary-remap low-cardinality
    columns — with a layout that is STABLE across every chunk of the run
    (layout is part of the jit key — a data-dependent per-chunk layout
    would retrace the fused sweep mid-run)."""
    for key in cols:
        if key.startswith(("fn:", "st:", "inv:", "ext:")):
            continue
        val = cols[key]
        items = sorted(val.items()) if isinstance(val, dict) \
            else [(None, val)]
        for sub, a in items:
            a = np.asarray(a)
            if a.size == 0:
                continue
            amn = a.min().item()
            amx = a.max().item()
            vals: Optional[frozenset] = None
            if a.dtype.str in ("<i4", "<i8"):
                # distinct-set tracking for the u1 dictionary remap
                # (low-cardinality wide-range columns, e.g. label-key
                # sids); capped — a high-cardinality column drops out
                u = np.unique(a)
                if len(u) <= _DICT_CAP:
                    vals = frozenset(int(x) for x in u)
            # float columns holding only integral values (ports,
            # replica counts) can ride integer wire dtypes
            intf = (a.dtype.str == "<f4"
                    and bool(np.all(a == np.trunc(a))))
            prev = stats.get((key, sub))
            if prev is None:
                stats[(key, sub)] = (amn, amx,
                                     amn if amn == amx else None, vals,
                                     intf)
            else:
                mn, mx, cv = prev[0], prev[1], prev[2]
                pv = prev[3] if len(prev) > 3 else None
                if pv is None or vals is None:
                    vals = None  # some chunk already overflowed the cap
                else:
                    vals = pv | vals
                    if len(vals) > _DICT_CAP:
                        vals = None
                stats[(key, sub)] = (
                    min(mn, amn), max(mx, amx),
                    cv if (cv is not None and amn == amx == cv) else None,
                    vals,
                    intf and (len(prev) < 5 or prev[4]))


_PAD_BY_SUB = {"kind": 0, "num": 0.0, "sid": -1, "idx": -1, "count": 0}


def merge_pad_stats(stats: dict) -> None:
    """Fold the ragged-family PAD values into corpus column stats.

    The warm-pass scan flattens chunks at their own (narrow) widths; the
    timed run pads every chunk up to the corpus-stable width targets,
    which can introduce pad values a scanned chunk never contained.
    Merging the pad value unconditionally keeps the stats a superset of
    every stabilized chunk's value set, so the narrowed/elided wire
    layout stays identical across all timed chunks (a layout that
    depended on a chunk's incidental lack of padding would retrace
    mid-sweep)."""
    for (key, sub), st in list(stats.items()):
        if not key.startswith(("rg:", "rks:", "mk:", "pi:", "ks:")):
            continue
        pad = _PAD_BY_SUB.get(sub)
        if pad is None:
            continue
        mn, mx, cv = st[0], st[1], st[2]
        vals = st[3] if len(st) > 3 else None
        intf = st[4] if len(st) > 4 else False
        ncv = cv if cv == pad else None
        if vals is not None:
            vals = vals | {int(pad)} if not isinstance(pad, float) else vals
            if len(vals) > _DICT_CAP:
                vals = None
        stats[(key, sub)] = (min(mn, pad), max(mx, pad), ncv, vals, intf)


def _wire_dtype(dt: str, mn: float, mx: float) -> tuple:
    """(store_dtype_str, bias) for a column whose corpus range is
    [mn, mx].  Integer columns with mn >= -1 ride unsigned narrow types
    with a +1 bias (missing-value sentinel -1 -> 0); "|n1" marks a
    nibble (two values per byte — type-tag columns span ~7 values);
    everything else travels as-is."""
    if dt in ("<i4", "<i8", "|i1") and mn >= -1:
        if mx + 1 <= 0xF:
            return "|n1", 1
        if mx + 1 <= 0xFF:
            return "|u1", 1
        if mx + 1 <= 0xFFFF:
            return "<u2", 1
    return dt, 0


def _transfer_columns(cols: dict):
    """The per-object columns in wire order, as ``(key, sub, array,
    alias)``: table columns (fn:/st:/inv:/ext:) left out, and ``alias``
    the ``(key, sub)`` that already ships this very numpy array (prefix-
    axis dedup, ops/flatten.dedup_schema: ship once, alias on device)."""
    seen: dict = {}  # id(array) -> (key, sub)
    for key in sorted(k for k in cols
                      if not k.startswith(("fn:", "st:", "inv:", "ext:"))):
        val = cols[key]
        items = sorted(val.items()) if isinstance(val, dict) \
            else [(None, val)]
        for sub, a in items:
            first = seen.setdefault(id(a), (key, sub))
            yield key, sub, a, first if first != (key, sub) else None


def pack_transfer_cols_py(cols: dict, pad_n: int,
                          stats: Optional[dict] = None) -> tuple:
    """Pack every per-object column into ONE [pad_n, W] buffer per dtype.

    Every transfer command carries a fixed cost on any host<->device
    link, so a sweep chunk's ~150 column arrays travel as a handful of
    device_puts.  Packing along axis 1 keeps each object's values
    together, so 'data'-axis sharding of the buffers is exactly the
    sharding the unpacked columns had.  Grouping by dtype keeps the
    in-jit unpack to plain same-type slices (a byte-level single buffer
    would unpack through narrow uint8 strips + bitcasts, which relayout
    badly on the 128-lane tile grid).

    ``stats`` ({(key, sub): (min, max, const|None)} from
    :func:`col_stats_update` over the whole corpus) enables the two
    optimizations that narrow the bytes on the wire (~2KB/object of
    int32 at the full library before them):

    - **dtype narrowing**: vocab-id/count/index columns store as
      uint8/uint16 with a +1 bias when the corpus range fits (vocab ids
      are ~36k for a 100k-object cluster -> uint16 halves the payload);
      widened back to the original dtype on device where casts fuse.
    - **constant elision**: columns constant across the corpus (absent
      fields: seLinuxOptions, procMount... on clusters that never set
      them) ship as a scalar in the static layout and materialize as a
      broadcast on device.

    Both decisions come from corpus stats so the layout — part of the
    jit key — is identical for every chunk; a chunk that exceeds the
    recorded range (cluster drift between audit runs) falls back to a
    wider dtype for that column, costing one retrace, never wrong
    results.

    Returns ({dtype_str: buf [pad_n, W_dtype]}, layout) where layout is
    a static tuple of (key, subkey, store_dtype, elem_offset, tail_shape,
    elem_width, orig_dtype, bias_or_const) consumed by
    :func:`unpack_transfer_cols` inside the jitted sweep; store_dtype
    "const" marks an elided column whose value rides in the last slot.
    Table columns (fn:/st:/inv: — shared, device-cached) are excluded.

    This is the numpy form: several passes a column, and what
    :func:`pack_transfer_cols` runs for a drifted chunk, without stats,
    and where ``native/wirepackmod.c`` does not build; the tests hold
    the native pass to its buffers byte for byte.
    """
    parts: dict = {}
    widths: dict = {}
    layout: list = []
    for key, sub, a, ref in _transfer_columns(cols):
        if ref is not None:
            layout.append((key, sub, "alias", 0, (), 0, a.dtype.str, ref))
            continue
        a = np.ascontiguousarray(a)
        dt = a.dtype.str
        tail = a.shape[1:]
        st = stats.get((key, sub)) if stats is not None else None
        dict_vals = None
        narrowable = dt in ("<i4", "<i8", "|i1") or (
            dt == "<f4" and st is not None and len(st) > 4 and st[4])
        if st is not None and (st[2] is not None or narrowable) \
                and a.size:
            amn = a.min().item()
            amx = a.max().item()
            if st[2] is not None and amn == amx == st[2]:
                # corpus-constant and this chunk agrees: elide
                layout.append((key, sub, "const", 0, tail, 0, dt,
                               st[2]))
                continue
            eff_mn = min(st[0], amn)
            eff_mx = max(st[1], amx)
            if not narrowable:
                wdt, bias = dt, 0
            elif dt == "<f4":
                # integral-float column (ports): integer wire dtype.
                # The chunk must re-verify integrality (a drifted
                # non-integral chunk would otherwise truncate —
                # range drift falls back, value drift must too) and
                # a no-fit range keeps the float dtype (falling
                # through to "<i4" would store floats uncast in the
                # int parts bucket).
                wdt, bias = _wire_dtype("<i4", eff_mn, eff_mx)
                if wdt == "<i4" or not bool(np.all(a == np.trunc(a))):
                    wdt, bias = dt, 0
            else:
                wdt, bias = _wire_dtype(dt, eff_mn, eff_mx)
            dct = st[3] if len(st) > 3 else None
            if dct is not None and wdt not in ("|u1", "|n1"):
                # u1 dictionary remap: wide-range low-cardinality
                # column (e.g. label-key sids) stores dictionary
                # indices; the sorted dictionary rides the static
                # layout and is gathered from a baked constant on
                # device.  Chunk values outside the corpus
                # dictionary (cluster drift) fall back to the plain
                # narrowed dtype — one retrace, never wrong results.
                dv = np.array(sorted(dct), np.int64)
                idx = np.searchsorted(dv, a.ravel())
                idx_c = np.minimum(idx, len(dv) - 1)
                if bool(np.all(dv[idx_c] == a.ravel())):
                    a = idx_c.astype(np.uint8).reshape(a.shape)
                    wdt, bias = "|u1", 0
                    dict_vals = tuple(int(x) for x in dv)
        else:
            wdt, bias = dt, 0
        w = int(np.prod(tail, dtype=np.int64)) if a.ndim > 1 else 1
        if wdt == "|n1" and w % 2:
            wdt = "|u1"  # nibble pairs need an even element count
        if wdt == "|n1":
            b = (a + bias).astype(np.uint8).reshape(pad_n, w)
            a = b[:, 0::2] | (b[:, 1::2] << 4)
            store_w = w // 2
        elif bias:
            a = (a + bias).astype(np.dtype(wdt))
            store_w = w
        else:
            store_w = w
        off = widths.get(wdt, 0)
        parts.setdefault(wdt, []).append(a.reshape(pad_n, store_w))
        layout.append((key, sub, wdt, off, tail, w, dt,
                       dict_vals if dict_vals is not None else bias))
        widths[wdt] = off + store_w
    bufs = {dt: np.concatenate(ps, axis=1) for dt, ps in parts.items()}
    return bufs, tuple(layout)


# source dtypes the native pass reads values of (any other it only copies)
_FUSED_SRC = ("<i4", "<i8", "|i1", "<f4", "|u1", "|b1")


def _wire_plan(mod, cols: dict, pad_n: int, stats: dict):
    """The chunk's wire layout as the corpus stats alone decide it, and
    one step a shipped or checked column for ``gtpu_wirepack.pack``:
    ``(op, source, wire dtype, offset, argument)``.

    :func:`pack_transfer_cols_py` folds the chunk's own range into each
    decision, which is why it must reduce a column before it can write
    it.  The chunk's range can only widen a column, so as long as every
    value fits the type the stats chose, an elided column is still the
    corpus constant, a dictionary column holds corpus values only and an
    integral float column is still integral, the stats decide alone:
    each step carries its condition, the native pass checks it while it
    writes, and one failed step sends the whole chunk to the numpy form.
    None: a decision rests on values of a dtype that pass does not
    read."""
    layout: list = []
    steps: list = []
    widths: dict = {}
    for key, sub, a, ref in _transfer_columns(cols):
        if ref is not None:
            layout.append((key, sub, "alias", 0, (), 0, a.dtype.str, ref))
            continue
        a = np.ascontiguousarray(a)
        dt = a.dtype.str
        tail = a.shape[1:]
        w = int(np.prod(tail, dtype=np.int64)) if a.ndim > 1 else 1
        st = stats.get((key, sub))
        intf = dt == "<f4" and st is not None and len(st) > 4 and st[4]
        narrowable = dt in ("<i4", "<i8", "|i1") or intf
        op, wdt, bias, arg = mod.COPY, dt, 0, None
        if st is not None and (st[2] is not None or narrowable) and a.size:
            if dt not in _FUSED_SRC:
                return None
            if st[2] is not None:
                if dt != "<f4" and not float(st[2]).is_integer():
                    return None  # by hand: no integer column equals it
                steps.append((mod.CHECK, a, None, 0, st[2]))
                layout.append((key, sub, "const", 0, tail, 0, dt, st[2]))
                continue
            if narrowable:
                wdt, bias = _wire_dtype("<i4" if intf else dt, st[0], st[1])
                if intf and wdt == "<i4":
                    wdt, bias = dt, 0
            dct = st[3] if len(st) > 3 else None
            if dct is not None and wdt not in ("|u1", "|n1"):
                if dt not in ("<i4", "<i8"):
                    return None
                op, wdt, bias = mod.DICT, "|u1", 0
                arg = np.array(sorted(dct), np.int64)
            elif wdt != dt:
                if wdt == "|n1" and w % 2:
                    wdt = "|u1"
                op = mod.NIBBLE if wdt == "|n1" else mod.BIAS
                arg = bias
        off = widths.get(wdt, 0)
        steps.append((op, a, wdt, off, arg))
        layout.append((key, sub, wdt, off, tail, w, dt,
                       tuple(int(x) for x in arg) if op == mod.DICT
                       else bias))
        widths[wdt] = off + (w // 2 if wdt == "|n1" else w)
    return tuple(layout), steps, widths


def pack_transfer_cols(cols: dict, pad_n: int,
                       stats: Optional[dict] = None,
                       counts: Optional[dict] = None) -> tuple:
    """:func:`pack_transfer_cols_py`'s buffers and layout, byte for byte
    and element for element, by one native call for the chunk
    (``native/wirepackmod.c``) wherever the corpus stats settle the
    layout: each column is read once and written once, into buffers
    allocated for this chunk (``device_put`` may still read a host
    buffer after it returns, so none is reused).  The numpy form packs
    the whole chunk where a column drifted out of the stats, where there
    are no stats, and where the module does not build.

    ``counts``, if given, receives ``fused`` and ``numpy``: the columns
    the layout ships (aliases and constants left out), under the path
    that packed them."""
    out = None
    mod = native.load_wirepack() if stats else None
    plan = _wire_plan(mod, cols, pad_n, stats) if mod is not None else None
    if plan is not None:
        layout, steps, widths = plan
        bufs = {wdt: np.empty((pad_n, width),
                              np.uint8 if wdt == "|n1" else np.dtype(wdt))
                for wdt, width in widths.items()}
        steps = [(op, a, bufs.get(wdt), off, arg)
                 for op, a, wdt, off, arg in steps]
        if not mod.pack(steps, pad_n):
            out = bufs, layout
    fused = out is not None
    if not fused:
        out = pack_transfer_cols_py(cols, pad_n, stats)
    if counts is not None:
        shipped = sum(1 for e in out[1] if e[2] not in ("alias", "const"))
        counts["fused"] = shipped if fused else 0
        counts["numpy"] = 0 if fused else shipped
    return out


def unpack_transfer_cols(bufs: dict, layout: tuple, pad_n: int) -> dict:
    """Rebuild the cols dict from dtype-grouped buffers inside jit:
    static same-dtype slices + widening casts + constant broadcasts, all
    fused by XLA (no data movement beyond the transfers that brought the
    buffers)."""
    cols: dict = {}
    aliases: list = []
    for key, sub, wdt, off, tail, w, dt, extra in layout:
        if wdt == "alias":
            aliases.append((key, sub, extra))
            continue
        odt = jax.dtypes.canonicalize_dtype(np.dtype(dt))
        if wdt == "const":
            arr = jnp.full((pad_n,) + tail, extra, dtype=odt)
        elif wdt == "|n1":
            buf = bufs[wdt]
            n = buf.shape[0]
            arr = jax.lax.slice_in_dim(buf, off, off + w // 2, axis=1)
            lo = arr & np.uint8(0xF)
            hi = arr >> np.uint8(4)
            arr = jnp.stack([lo, hi], axis=-1).reshape((n, w))
            arr = arr.reshape((n,) + tail).astype(odt)
            if extra:
                arr = arr - extra
        else:
            buf = bufs[wdt]
            n = buf.shape[0]
            arr = jax.lax.slice_in_dim(buf, off, off + w, axis=1)
            arr = arr.reshape((n,) + tail)
            if isinstance(extra, tuple):
                # dictionary remap: gather original values from the
                # baked (tiny, layout-static) dictionary constant
                arr = jnp.asarray(np.array(extra, dtype=odt))[arr]
            else:
                if wdt != dt:
                    arr = arr.astype(odt)
                if extra:
                    arr = arr - extra
        if sub is None:
            cols[key] = arr
        else:
            cols.setdefault(key, {})[sub] = arr
    for key, sub, (rkey, rsub) in aliases:
        src = cols[rkey] if rsub is None else cols[rkey][rsub]
        if sub is None:
            cols[key] = src
        else:
            cols.setdefault(key, {})[sub] = src
    return cols


def pack_flat_tables(tables: Sequence[dict]) -> tuple:
    """Flat pack of the per-kind parameter tables (hundreds of tiny
    [C, ...] arrays, ~KBs total) into one replicated 1-D buffer per
    dtype — same per-transfer-cost motivation as
    :func:`pack_transfer_cols`."""
    parts: dict = {}
    widths: dict = {}
    layout: list = []
    for i, table in enumerate(tables):
        for k in sorted(table):
            a = np.ascontiguousarray(table[k])
            dt = a.dtype.str
            off = widths.get(dt, 0)
            parts.setdefault(dt, []).append(a.reshape(-1))
            layout.append((i, k, dt, off, a.shape, int(a.size)))
            widths[dt] = off + int(a.size)
    bufs = {dt: np.concatenate(ps) for dt, ps in parts.items()}
    return bufs, tuple(layout)


def unpack_flat_tables(bufs: dict, layout: tuple, n_groups: int) -> list:
    """Inverse of :func:`pack_flat_tables`, inside jit."""
    out: list = [dict() for _ in range(n_groups)]
    for i, k, dt, off, shape, size in layout:
        sl = jax.lax.slice_in_dim(bufs[dt], off, off + size, axis=0)
        out[i][k] = sl.reshape(shape)
    return out


def shard_batch_arrays(cols: dict, mesh: Mesh,
                       table_cache: Optional[dict] = None) -> dict:
    """device_put column arrays with the object axis sharded over 'data'.

    Columns are [N] or [N, M]; N shards, M stays local (ragged items of one
    object live on one chip).  ``table_cache`` keeps the big shared lookup
    tables (vocab preds, inventory joins) device-resident across chunks —
    they only change when the vocab crosses a bucket or the data version
    moves, so re-uploading them per chunk wastes HBM bandwidth.
    """
    out = {}
    for key, val in cols.items():
        if key.startswith(("fn:", "st:", "inv:", "ext:")):
            # vocab-derived tables are shared lookup state: replicate.
            # Cache hit on content (the builders may return a fresh but
            # identical array per chunk; identity would re-upload the
            # same bytes every time).
            if table_cache is not None:
                hit = table_cache.get(key)
                if hit is not None and (
                        hit[0] is val
                        or (hit[0].shape == val.shape
                            and hit[0].dtype == val.dtype
                            and np.array_equal(hit[0], val))):
                    out[key] = hit[1]
                    continue
            dev = jax.device_put(
                val, NamedSharding(mesh, P(*([None] * val.ndim)))
            )
            if table_cache is not None:
                table_cache[key] = (val, dev)
            out[key] = dev
            continue
        if isinstance(val, dict):
            out[key] = {
                k: jax.device_put(
                    v, NamedSharding(mesh, P("data", *([None] * (v.ndim - 1))))
                )
                for k, v in val.items()
            }
        else:
            out[key] = jax.device_put(
                val, NamedSharding(mesh, P("data", *([None] * (val.ndim - 1))))
            )
    return out


def shard_param_table(table: dict, mesh: Mesh, shard_constraints: bool) -> dict:
    """Parameter rows: shard over 'model' when requested, else replicate."""
    spec_axis = "model" if shard_constraints else None
    out = {}
    for k, v in table.items():
        out[k] = jax.device_put(
            v, NamedSharding(mesh, P(spec_axis, *([None] * (v.ndim - 1))))
        )
    return out


COLLECT_LANES = ("reduced", "masks", "differential")

# budgeted-lane hit-buffer steps: each distinct size is one jit variant
# of the fused sweep (compiled once, warmable), so the ladder is short —
# 0 for drained-budget chunks, three small steps for the steady-state
# trickle, then the full per-chunk kept capacity
_HIT_STEPS = (0, 16, 64, 256)


def hit_bucket(need: int, cap: int) -> int:
    """Smallest static hit-buffer size covering ``need`` selected hits
    (``cap`` = the exhaustive bound, e.g. C*k for a kept selection)."""
    if need <= 0:
        return 0
    for b in _HIT_STEPS[1:]:
        if need <= b < cap:
            return b
    return cap


class HitRows:
    """Device-reduced violation coordinates for one kind's constraint
    rows: flat ``ci * pad_n + oi`` coords, canonically sorted
    (constraint-major, ascending object index) — the O(violations)
    replacement for the bit-packed verdict rows in the 5th slot of a
    sweep_collect entry.  ``rows(ci)`` yields the violating object
    indices of local constraint ``ci`` exactly as
    ``np.nonzero(np.unpackbits(bits[ci], count=n))[0]`` would."""

    __slots__ = ("flat", "pad_n", "n", "c", "_starts")

    def __init__(self, flat: np.ndarray, pad_n: int, n: int, c: int):
        self.flat = flat
        self.pad_n = pad_n
        self.n = n
        self.c = c
        self._starts = np.searchsorted(
            flat, np.arange(c + 1, dtype=np.int64) * pad_n)

    def rows(self, ci: int) -> np.ndarray:
        lo, hi = self._starts[ci], self._starts[ci + 1]
        oi = self.flat[lo:hi] - ci * self.pad_n
        return oi[oi < self.n]


def violation_rows(bits_or_hits, ci: int, n: int) -> np.ndarray:
    """Violating object indices of local constraint ``ci`` from either
    collect shape: bit-packed verdict rows (masks lane) or
    :class:`HitRows` (reduced lane) — the single fold-side accessor all
    exact/snapshot folds share, so both lanes are bit-identical by
    construction."""
    if isinstance(bits_or_hits, HitRows):
        return bits_or_hits.rows(ci)
    return np.nonzero(np.unpackbits(bits_or_hits[ci], count=n))[0]


def template_grids(kinds, builders, tables, cols: dict) -> list:
    """Every template's verdict grid [C_kind, N], each evaluated under a
    ``jax.named_scope`` of its kind: an operation of the compiled sweep
    then says in its HLO metadata which template it came from."""
    grids = []
    for kind, build, table in zip(kinds, builders, tables):
        with jax.named_scope(kind):
            grids.append(build(table, cols))
    return grids


def counted_kinds(progs, kinds, exact: bool) -> frozenset:
    """The kinds of an exact-lane host sweep whose count column carries
    result totals: those whose program has a proved ``count``."""
    if not exact:
        return frozenset()
    return frozenset(k for k in kinds
                     if progs[k].program.count is not None)


def _counters(progs, kinds, exact: bool):
    """Per kind the result-count builder (None where it has none), or None
    for a sweep that counts nothing."""
    counted = counted_kinds(progs, kinds, exact)
    if not counted:
        return None
    return [progs[k]._build_count() if k in counted else None
            for k in kinds]


def result_totals(counters, tables, cols: dict, hits) -> jnp.ndarray:
    """[C_total] int32 of an exact-lane sweep: for a row whose kind counts
    its results (``counters[i]`` not None), the results of its hits, or -1
    where a hit's count is not certified; for every other row its hits."""
    out = []
    lo = 0
    for counter, table in zip(counters, tables):
        ck = table["__row__"].shape[0]
        h = hits[lo: lo + ck]
        lo += ck
        if counter is None:
            out.append(jnp.sum(h, axis=1, dtype=jnp.int32))
            continue
        with jax.named_scope("count"):
            cnt, ok = counter(table, cols)
            total = jnp.sum(jnp.where(h, as_grid(cnt, h.shape), 0), axis=1,
                            dtype=jnp.int32)
            out.append(jnp.where(jnp.all(as_grid(ok, h.shape) | ~h, axis=1),
                                 total, -1))
    return jnp.concatenate(out)


class SweptRows(tuple):
    """One kind's rows of a collected sweep, ``(constraints, idx, valid,
    counts, bits)``.  ``results`` is True where ``counts`` holds each row's
    RESULT total (an exact-lane sweep of a kind whose program counts its
    results; -1 for a row a hit of which the device could not certify), and
    False where it holds the row's violating objects."""

    def __new__(cls, entry: tuple, results: bool = False):
        self = super().__new__(cls, entry)
        self.results = results
        return self


def topk_violations(verdicts: jnp.ndarray, k: int) -> tuple:
    """Per-constraint top-k violating object indices, lowest-index-first —
    the device analog of the reference's LimitQueue (bounded max-heap,
    audit/manager.go:161-202).

    verdicts: [C, N] bool.  Returns (idx [C, k] int32, valid [C, k] bool).
    Runs under jit; over a sharded N axis XLA all-gathers the per-shard
    candidates.
    """
    c, n = verdicts.shape
    k = min(k, n)
    # score = 1 for violation, tie-broken toward low indices: top_k of
    # (violation * N + (N - index)) picks violations with lowest indices first
    idxs = jnp.arange(n, dtype=jnp.int32)
    score = jnp.where(verdicts, n - idxs, 0).astype(jnp.int32)
    top_scores, top_idx = jax.lax.top_k(score, k)
    return top_idx, top_scores > 0


def _masks_fold(grid, k: int, return_bits: bool, use_pallas: bool,
                totals=None):
    """Masks-lane epilogue of a sweep, host and resident alike: the
    masked grid -> ONE packed int32 array [C_total, 2k+1] =
    [idx(k) | valid(k) | count] (+ the bit-packed verdict rows).
    ``totals`` (exact lane, :func:`result_totals`) stands for ``count``."""
    with jax.named_scope("fold"):
        if use_pallas:
            from gatekeeper_tpu.ops.pallas_topk import \
                topk_violations_counts_pallas

            idx, valid, counts = topk_violations_counts_pallas(grid, k)
        else:
            idx, valid = topk_violations(grid, k)
            counts = jnp.sum(grid, axis=1, dtype=jnp.int32)
        if totals is not None:
            counts = totals
        packed = jnp.concatenate(
            [idx, valid.astype(jnp.int32), counts[:, None]], axis=1)
        if return_bits:
            # bit-packed verdict rows: the exact hit set travels to
            # the host at N/8 bytes per constraint (exact-totals mode)
            return packed, jnp.packbits(grid.astype(jnp.uint8), axis=1)
        return packed


def _reduced_fold(raw, mask, budget, k: int, complete: bool, hit_cap: int,
                  pad_n: int, use_pallas: bool, totals=None):
    """Reduced-lane epilogue of a sweep, host and resident alike: the
    raw grid and the match mask -> ONE small int32 array
    ``[counts(C) | occ(C) | nsel | hits(hit_cap)]`` (counts and occ
    share a u16|u16 word while pad_n fits and ``counts`` are violating
    objects).  ``budget`` is unused (may be None) when ``complete``;
    ``totals`` (complete only, :func:`result_totals`) stands for
    ``counts`` on the wire, which then travel in words of their own: a
    result total may pass pad_n, and -1 marks an uncertified row."""
    with jax.named_scope("fold"):
        c_total = raw.shape[0]
        sentinel = c_total * pad_n
        if use_pallas:
            # Pallas fused fold: mask -> violation totals -> first-k
            # -> occupancy in ONE VMEM pass over the raw grid (the
            # masked grid never materializes as an XLA intermediate);
            # the else-branch is the fallback + differential reference
            from gatekeeper_tpu.ops.pallas_topk import fused_fold_pallas

            idx, valid, counts, occ = fused_fold_pallas(raw, mask, k)
        else:
            grid = raw & mask
            counts = jnp.sum(grid, axis=1, dtype=jnp.int32)
            occ = jnp.sum(mask, axis=1, dtype=jnp.int32)
        if complete:
            nsel = jnp.sum(counts)
            if hit_cap:
                # row-major nonzero == canonical (constraint,
                # ascending index) order; fill coords sort last so
                # the real hits are the nsel-prefix
                (hits,) = jnp.nonzero(grid.reshape(-1), size=hit_cap,
                                      fill_value=sentinel)
                hits = hits.astype(jnp.int32)
            else:
                hits = jnp.zeros((0,), jnp.int32)
        else:
            if not use_pallas:
                idx, valid = topk_violations(grid, k)
            k_eff = idx.shape[1]
            want = jnp.minimum(counts, budget)
            sel = valid & (jnp.arange(k_eff, dtype=jnp.int32)[None, :]
                           < want[:, None])
            nsel = jnp.sum(sel, dtype=jnp.int32)
            if hit_cap:
                (pos,) = jnp.nonzero(sel.reshape(-1), size=hit_cap,
                                     fill_value=c_total * k_eff)
                safe = jnp.minimum(pos, c_total * k_eff - 1)
                oi = jnp.take(idx.reshape(-1), safe)
                hits = jnp.where(
                    pos < c_total * k_eff,
                    (pos // k_eff).astype(jnp.int32) * pad_n + oi,
                    sentinel).astype(jnp.int32)
            else:
                hits = jnp.zeros((0,), jnp.int32)
        if totals is not None:
            head = [totals, occ]
        elif pad_n <= 0xFFFF:
            # counts and occupancy are both <= pad_n: one u16|u16
            # word per constraint halves the per-chunk floor (the
            # D2H twin of the H2D wire-dtype narrowing)
            head = [jax.lax.bitcast_convert_type(
                counts.astype(jnp.uint32)
                | (occ.astype(jnp.uint32) << 16), jnp.int32)]
        else:
            head = [counts, occ]
        return jnp.concatenate(
            head + [jnp.reshape(nsel, (1,)).astype(jnp.int32), hits])


def relevant_template_kinds(constraints) -> dict:
    """template (constraint) kind -> frozenset of object kinds its
    constraints' ``spec.match.kinds`` can match, or None for wildcard
    (any entry with kinds ``*``/absent, or no kinds matcher at all).

    This is the reference's --audit-match-kind-only prefilter semantics
    (pkg/audit/manager.go:427-483) applied per template: a SUPERSET by
    construction (apiGroups and the other 7 matchers still gate on
    device), so routing by it never changes verdicts."""
    rel: dict = {}
    for con in constraints:
        ks: set = set()
        wild = False
        entries = (con.match or {}).get("kinds") or []
        if not entries:
            wild = True
        for e in entries:
            kk = e.get("kinds") or []
            if not kk or "*" in kk:
                wild = True
            ks.update(k for k in kk if k != "*")
        prev = rel.get(con.kind)
        if wild or prev is None and con.kind in rel:
            rel[con.kind] = None
        elif prev is None and con.kind not in rel:
            rel[con.kind] = frozenset(ks)
        elif prev is not None:
            rel[con.kind] = prev | frozenset(ks)
    return rel


def make_kind_router(constraints):
    """obj kind -> frozenset of template kinds that could match it — the
    kind-bucketed sweep router.  Objects whose group is empty cannot
    violate anything (no template's match reaches their kind): the audit
    skips them entirely, and grouped chunks only flatten/ship/evaluate
    the group's schemas (a Service chunk never pays for container
    columns)."""
    rel = relevant_template_kinds(constraints)
    wild = frozenset(t for t, ks in rel.items() if ks is None)
    cache: dict = {}

    def group_of(obj_kind: str) -> frozenset:
        g = cache.get(obj_kind)
        if g is None:
            g = wild | frozenset(
                t for t, ks in rel.items()
                if ks is not None and obj_kind in ks)
            cache[obj_kind] = g
        return g

    return group_of


class _PendingSweep:
    __slots__ = ("result", "kinds", "offsets", "by_kind", "n",
                 "return_bits", "attr_weights", "attr_rows",
                 "lane", "pad_n", "hit_cap", "flat", "ref",
                 "dispatch_wall", "host_occ", "budget_np", "counted")

    def __init__(self, result, kinds, offsets, by_kind, n, return_bits,
                 attr_weights=None, attr_rows=None, lane="masks",
                 pad_n=0, hit_cap=0, flat=None):
        self.result = result
        self.kinds = kinds
        self.offsets = offsets
        self.by_kind = by_kind
        self.n = n
        self.return_bits = return_bits
        # per-template dispatch-share weights (mask row occupancy),
        # computed only while cost attribution is installed
        self.attr_weights = attr_weights
        self.attr_rows = attr_rows
        # collect lane this dispatch ran ('masks'|'reduced'|'differential')
        self.lane = lane
        self.pad_n = pad_n
        # reduced lane: static hit-buffer size of the fused program; the
        # retained _FlatChunk backs the masks-lane fallback re-dispatch
        # when a chunk's true hit count overflows it (dropped at collect)
        self.hit_cap = hit_cap
        self.flat = flat
        # differential lane: the masks-lane reference dispatch
        self.ref = None
        # reduced lane: dispatch wall seconds, attributed at collect time
        # once the DEVICE occupancy counts arrive (masks lane attributes
        # at dispatch from the host-visible mask rows)
        self.dispatch_wall = 0.0
        # differential lane: host-side per-constraint mask occupancy, the
        # reference the device counts are asserted against
        self.host_occ = None
        # budgeted reduced dispatch: the per-constraint kept budgets the
        # device selection was clipped to (None = complete variant)
        self.budget_np = None
        # exact lane: the kinds whose count column holds result totals
        self.counted = frozenset()


class _FlatChunk:
    """A host-flattened (not yet dispatched) sweep chunk — the hand-off
    unit between the pipeline's flatten stage (the C columnizer, GIL
    released in its three phases and held around them)
    and the dispatch stage (masks + wire pack + device_put + jit call)."""

    __slots__ = ("by_kind", "kinds", "cols", "batch", "objects", "any_gen",
                 "n", "pad_n", "return_bits", "source", "budget",
                 "programs")

    def __init__(self, by_kind, kinds, cols, batch, objects, any_gen, n,
                 pad_n, return_bits, source="", budget=None,
                 programs=None):
        self.by_kind = by_kind
        self.kinds = kinds
        self.cols = cols
        self.batch = batch
        self.objects = objects
        self.any_gen = any_gen
        self.n = n
        self.pad_n = pad_n
        self.return_bits = return_bits
        # review source ("Original"/"Generated") the chunk evaluates
        # under — expansion-stage chunks carry Generated so source-scoped
        # constraint matches see shift-left resultants correctly; ""
        # keeps the legacy mask behavior byte-for-byte
        self.source = source
        # reduced lane, budgeted variant: con -> remaining run-level kept
        # slots (evaluated at dispatch — always >= the fold-time budget,
        # so the device selection is a superset of what the fold keeps);
        # None = full render cap for every constraint
        self.budget = budget
        # the generation this chunk was flattened under ({kind ->
        # CompiledProgram}, captured once at flatten): dispatch MUST use
        # these programs — a generation swap between flatten and dispatch
        # would otherwise evaluate old columns with new kernels
        self.programs = programs


class _ResidentChunk:
    """A sweep chunk whose columns already LIVE on device — the
    device-resident snapshot lane's twin of :class:`_FlatChunk`.  No
    host batch, no host columns, no host masks: just the
    :class:`ResidentGroup` (snapshot/device_residency.py) plus the row
    positions to gather, so a clean-row dispatch ships only the gather
    index vector (cached per chunk shape — a warm tick ships NOTHING)."""

    __slots__ = ("rg", "by_kind", "kinds", "positions", "n", "pad_n",
                 "return_bits", "source", "budget", "programs")

    def __init__(self, rg, positions, n, pad_n, return_bits,
                 budget=None, programs=None):
        self.rg = rg
        self.by_kind = rg.by_kind
        self.kinds = rg.kinds
        self.positions = tuple(positions)
        self.n = n
        self.pad_n = pad_n
        self.return_bits = return_bits
        # the snapshot lane always evaluates under the default source
        # (audit relist semantics) — matches the host snapshot path
        self.source = ""
        self.budget = budget
        self.programs = programs


class ShardedEvaluator:
    """Runs a TpuDriver's compiled programs over a device mesh.

    One instance per (driver, mesh); reuses the driver's vocab so interned
    ids agree with single-chip evaluation.
    """

    def __init__(self, driver, mesh: Mesh, violations_limit: int = 20,
                 flatten_lane: str = "auto", metrics=None,
                 collect: str = "reduced", flatten_workers: int = 0):
        self.driver = driver
        self.mesh = mesh
        self.violations_limit = violations_limit
        # name -> Namespace object or None: where the match masks find a
        # swept object's Namespace for ``namespaceSelector``.  The audit
        # manager sets it to its target's NamespaceCache (what
        # ``Client.add_data`` fills and the interpreter's matcher falls
        # back to)
        self.namespace_of = None
        # --flatten-lane: how sweep chunks columnize (ops/flatten.py
        # FLATTEN_LANES) — auto takes the raw-bytes threaded C lane when
        # the lister hands over bytes and the native module built
        self.flatten_lane = flatten_lane
        # --flatten-workers: raw-lane sweep chunks fan byte spans across
        # N flatten worker processes (ops/flatten.FlattenWorkerPool),
        # merged bit-identically on the dispatch thread; 0 = in-process
        self.flatten_workers = max(0, int(flatten_workers))
        self.metrics = metrics
        # --collect: what a sweep chunk transfers device->host.
        # 'reduced' folds the verdict grid ON DEVICE (per-constraint
        # totals, top-k kept selection under the render cap, mask-row
        # occupancy) and ships one small packed array — O(kept) bytes,
        # not O(objects x constraints); exact/snapshot chunks
        # (return_bits) ship the complete hit-coordinate list instead of
        # the bit grid, with an adaptive buffer that falls back to the
        # masks lane per chunk on overflow (and pins dense corpora to
        # masks when coordinates would outweigh the bits).  'masks' is
        # the host-fold reference lane (the bit-identity oracle);
        # 'differential' runs BOTH per chunk and asserts totals, kept
        # selections and occupancy identical.
        if collect not in COLLECT_LANES:
            raise ValueError(f"unknown collect lane {collect!r}")
        self.collect = collect
        self._sweep_fns: dict = {}
        # fused-sweep trace counter: each jit TRACE of a sweep fn body
        # (first call per input-shape signature) bumps it — the
        # "zero retraces after a warm restart" pin reads the delta
        self.trace_count = 0
        # device-dispatch counter: every real sweep dispatch (incl. the
        # reduced lane's masks fallback re-dispatch) bumps it — the
        # fleet packing win (K clusters' chunks collapsing into one
        # dispatch) reads the delta
        self.dispatch_count = 0
        # warm-state record (drivers/generation.WarmStateCache): every
        # NEW fused executable's serializable descriptor + the input
        # avals its first dispatch traced at, so a restarted process can
        # replay the traces with zero-filled buffers before serving
        self.warm_record: dict = {}
        # per-generation merged-schema cache: (plan epoch, lowered set)
        # -> union Schema (see sweep_schema)
        self._schema_cache: dict = {}
        # reduced lane adaptive state per (kinds, pad_n): hit-buffer size
        # for complete-hits chunks, masks-lane pinning, low-water streak
        self._hit_state: dict = {}
        self._table_dev_cache: dict = {}  # key -> (host_array, dev_array)
        self._param_dev_cache: dict = {}  # digest -> dev uint8 buffer
        # corpus-wide per-column (min, max, const) from warm_pass: drives
        # wire-dtype narrowing + constant elision in pack_transfer_cols
        self._col_stats: dict = {}
        # corpus-stable ragged widths from warm_pass (ops/flatten
        # width_targets): sweep chunks pad to the corpus max on a bucket-2
        # grid instead of 8-wide minimums
        self._width_targets: dict = {}
        self._bucket = 2
        # per-phase wall-clock totals (seconds), reset via perf_reset():
        # flatten / masks / wire_pack / dispatch (device_put + jit call) /
        # collect (device->host).  The lock makes
        # accumulation safe under the staged pipeline, where flatten /
        # dispatch / collect run on different stage threads.
        self.perf: dict = {}
        self._perf_lock = threading.Lock()

    def _perf_add(self, phase: str, dt: float) -> None:
        with self._perf_lock:
            self.perf[phase] = self.perf.get(phase, 0.0) + dt

    def perf_reset(self) -> None:
        self.perf = {}

    @contextmanager
    def _timed(self, phase: str, span_cm):
        """One part of a dispatch: its seconds into ``perf[phase]``, and
        on the timeline as the span ``span_cm`` opens."""
        t0 = time.perf_counter()
        with span_cm:
            yield
        self._perf_add(phase, time.perf_counter() - t0)

    # --- warm-state persistence (drivers/generation.WarmStateCache) ------
    def _record_warm(self, desc: tuple, cols_bufs: dict,
                     tables_bufs: dict, table_cols: dict, mask,
                     budget) -> None:
        """Record a NEW fused executable's trace signature: the
        serializable key descriptor (lane, kinds, k, flags, layouts,
        pad_n) plus the host-side input avals its first dispatch carried
        — everything :meth:`replay_warm` needs to re-land the trace with
        zero-filled buffers after a restart.  Called only when the
        executable cache missed, so steady-state dispatches never pay
        this."""
        if len(self.warm_record) >= 64:
            return
        self.warm_record[desc] = {
            "cols": {dt: (b.shape, b.dtype.str)
                     for dt, b in cols_bufs.items()},
            "tables": {dt: (b.shape, b.dtype.str)
                       for dt, b in tables_bufs.items()},
            "table_cols": {name: (np.asarray(a).shape,
                                  np.asarray(a).dtype.str)
                           for name, a in table_cols.items()},
            "mask": tuple(mask.shape),
            "budget": None if budget is None else tuple(budget.shape),
        }

    def warm_state(self) -> dict:
        """The persistable warm execution state: recorded executable
        descriptors + the adaptive inputs that make post-restart
        dispatches compute IDENTICAL jit keys — corpus column stats and
        ragged width targets (they decide the wire layout, which is part
        of the key) and the reduced lane's hit-buffer state (cap sizing
        is part of the key too)."""
        return {
            "record": dict(self.warm_record),
            "col_stats": dict(self._col_stats),
            "width_targets": dict(self._width_targets),
            "hit_state": {k: dict(v)
                          for k, v in self._hit_state.items()},
        }

    def restore_warm_state(self, state: dict) -> None:
        self._col_stats = dict(state.get("col_stats") or {})
        self._width_targets = dict(state.get("width_targets") or {})
        self._hit_state = {k: dict(v) for k, v in
                           (state.get("hit_state") or {}).items()}
        self.warm_record = dict(state.get("record") or {})

    def replay_warm(self) -> int:
        """Re-land every recorded fused-sweep trace: zero-filled buffers
        at the recorded avals drive one trace per entry off the serving
        path (the persistent XLA cache answers the compile), so the
        first real tick after a restart reuses the traces instead of
        retracing once per layout.  Best-effort per entry: a descriptor
        the current program set cannot satisfy is skipped and simply
        retraces lazily later.  Returns the number of traces landed."""
        progs = self.driver._programs
        landed = 0
        for desc, avals in list(self.warm_record.items()):
            kinds = desc[1]
            if any(kd not in progs for kd in kinds):
                continue
            try:
                tables_dev = {
                    dt: jax.device_put(
                        np.zeros(shape, np.dtype(ds)),
                        NamedSharding(self.mesh, P(None)))
                    for dt, (shape, ds) in avals["tables"].items()}
                cols_dev = {
                    dt: jax.device_put(
                        np.zeros(shape, np.dtype(ds)),
                        NamedSharding(self.mesh, P("data", None)))
                    for dt, (shape, ds) in avals["cols"].items()}
                tcols = {name: np.zeros(shape, np.dtype(ds))
                         for name, (shape, ds)
                         in avals["table_cols"].items()}
                tcols_dev = shard_batch_arrays(tcols, self.mesh, {})
                mask_dev = jax.device_put(
                    np.zeros(avals["mask"], np.uint8),
                    NamedSharding(self.mesh, P(None, "data")))
                if desc[0] == "reduced":
                    (_lane, kinds, k, complete, hit_cap, cols_layout,
                     tables_layout, pad_n) = desc
                    budget_dev = jax.device_put(
                        np.zeros(avals["budget"] or (0,), np.int32),
                        NamedSharding(self.mesh, P(None)))
                    fn = self._sweep_fn_reduced(
                        kinds, k, complete, hit_cap, cols_layout,
                        tables_layout, pad_n)
                    jax.block_until_ready(fn(tables_dev, cols_dev,
                                             tcols_dev, mask_dev,
                                             budget_dev))
                else:
                    (_lane, kinds, k, return_bits, cols_layout,
                     tables_layout, pad_n) = desc
                    fn = self._sweep_fn(kinds, k, return_bits,
                                        cols_layout, tables_layout,
                                        pad_n)
                    jax.block_until_ready(fn(tables_dev, cols_dev,
                                             tcols_dev, mask_dev))
                landed += 1
            except Exception:  # noqa: PERF203
                continue
        return landed

    def _use_pallas(self) -> bool:
        """Whether the fused sweeps end in the Pallas epilogue
        (ops/pallas_topk.py) instead of the XLA ``top_k`` twin — decided
        from the mesh alone.  A pallas call cannot consume a sharded
        operand, so any multi-device mesh (data-sharded N or
        model-sharded C) keeps the XLA path, whose top-k all-gathers
        across shards; off the TPU there is no Mosaic compiler.  On a
        1-device TPU mesh the kernel is compiled, never interpreted: a
        Mosaic compile error propagates out of the dispatch."""
        return (self.mesh.size == 1
                and self.mesh.devices.flat[0].platform == "tpu")

    def _flattener(self, schema: Schema, label_keys=()) -> Flattener:
        return Flattener(schema, self.driver.vocab, bucket=self._bucket,
                         width_targets=self._width_targets or None,
                         lane=self.flatten_lane,
                         workers=self.flatten_workers,
                         label_keys=label_keys)

    def _needs_union(self, kinds, alias: Optional[dict] = None,
                     programs=None, counting: bool = False) -> dict:
        """Union of array fields any lowered program reads — the
        transfer-slimming key shared by warm_pass (col stats) and
        sweep_submit (packing); one definition so the stats keys always
        match the packed columns.  ``alias`` (orig spec -> exec spec from
        the Flattener's prefix-axis dedup) extends each aliased key's
        needs onto its exec column so slimming keeps exactly the fields
        some consumer reads through either name.  ``counting`` (the exact
        lane's host chunks): the result counts' fields too."""
        progs = programs if programs is not None \
            else self.driver._programs
        needs: dict = {}
        for kind in sorted(kinds):
            for ck, fields in needed_fields(
                    progs[kind].program, counting).items():
                needs.setdefault(ck, set()).update(fields)
        if alias:
            for orig, new in alias.items():
                ok, nk = col_key(orig), col_key(new)
                if ok in needs or nk in needs:
                    u = needs.get(ok, set()) | needs.get(nk, set())
                    needs[ok] = u
                    needs[nk] = u
        return needs

    def _sweep_fn(self, kinds: tuple, k: int, return_bits: bool,
                  cols_layout: tuple, tables_layout: tuple, pad_n: int,
                  progs=None):
        """One fused jitted program for the whole sweep: every template's
        verdict grid + mask + top-k + totals, returning ONE packed int32
        array [C_total, 2k+1] = [idx(k) | valid(k) | count].

        Each transfer command has a fixed cost, so BOTH directions are
        single buffers: the batch columns and parameter tables arrive
        byte-packed (unpacked here under jit, where the slices/bitcasts
        fuse to nothing), and the chunk result leaves in one packed
        transfer.

        Executables cache per program SET (the uid tuple): a generation
        swap that replaces one kind's program misses cleanly, while
        groups whose programs carried over keep their compiled fns.

        The jitted function is named for its lane (as its three twins
        are), so a device trace reads ``jit_sweep_masks/...``.
        """
        progs = progs if progs is not None else self.driver._programs
        uids = tuple(progs[kind].uid for kind in kinds)
        key = (kinds, uids, k, return_bits, cols_layout, tables_layout,
               pad_n)
        fn = self._sweep_fns.get(key)
        if fn is not None:
            return fn
        builders = [progs[kind]._build() for kind in kinds]
        counters = _counters(progs, kinds, return_bits)
        use_pallas = self._use_pallas()

        def sweep_masks(tables_buf, cols_buf, table_cols: dict, mask_bits):
            self.trace_count += 1  # runs at TRACE time only
            cols = unpack_transfer_cols(cols_buf, cols_layout, pad_n)
            cols.update(table_cols)
            tables = unpack_flat_tables(tables_buf, tables_layout,
                                        len(kinds))
            mask = jnp.unpackbits(mask_bits, axis=1,
                                  count=pad_n).astype(jnp.bool_)
            grids = template_grids(kinds, builders, tables, cols)
            grid = jnp.concatenate(grids, axis=0) & mask
            totals = None if counters is None else \
                result_totals(counters, tables, cols, grid)
            return _masks_fold(grid, k, return_bits, use_pallas, totals)

        fn = jax.jit(sweep_masks)
        self._sweep_fns[key] = fn
        return fn

    def _sweep_fn_reduced(self, kinds: tuple, k: int, complete: bool,
                          hit_cap: int, cols_layout: tuple,
                          tables_layout: tuple, pad_n: int, progs=None):
        """The device-side verdict REDUCTION twin of :meth:`_sweep_fn`:
        the fused grid never leaves the chip — per-constraint violation
        totals (segmented sum over the masked grid), the kept selection
        (``jax.lax.top_k`` under the render cap and the canonical
        lowest-index-first ordering key, clipped to the caller's
        remaining kept budget), and the mask-row occupancy counts cost
        attribution apportions by, all compacted into ONE small int32
        array ``[counts(C) | occ(C) | nsel | hits(hit_cap)]``.

        ``complete`` (exact-totals / snapshot chunks): ``hits`` carries
        EVERY violating ``ci*pad_n+oi`` coordinate instead of the kept
        selection — the verdict-store / exact-render consumers need the
        full hit set, just never the O(C x N) grid.  ``nsel`` is the true
        selected count; a value above ``hit_cap`` means the buffer
        truncated and the collect side must fall back to the masks lane
        for this chunk."""
        progs = progs if progs is not None else self.driver._programs
        uids = tuple(progs[kind].uid for kind in kinds)
        key = ("reduced", kinds, uids, k, complete, hit_cap, cols_layout,
               tables_layout, pad_n)
        fn = self._sweep_fns.get(key)
        if fn is not None:
            return fn
        builders = [progs[kind]._build() for kind in kinds]
        counters = _counters(progs, kinds, complete)
        use_pallas = not complete and self._use_pallas()

        def sweep_reduced(tables_buf, cols_buf, table_cols: dict,
                          mask_bits, budget):
            self.trace_count += 1  # runs at TRACE time only
            cols = unpack_transfer_cols(cols_buf, cols_layout, pad_n)
            cols.update(table_cols)
            tables = unpack_flat_tables(tables_buf, tables_layout,
                                        len(kinds))
            mask = jnp.unpackbits(mask_bits, axis=1,
                                  count=pad_n).astype(jnp.bool_)
            raw = jnp.concatenate(
                template_grids(kinds, builders, tables, cols), axis=0)
            totals = None if counters is None else \
                result_totals(counters, tables, cols, raw & mask)
            return _reduced_fold(raw, mask, budget, k, complete, hit_cap,
                                 pad_n, use_pallas, totals)

        fn = jax.jit(sweep_reduced)
        self._sweep_fns[key] = fn
        return fn

    def _gather_resident(self, idx, res_cols: dict, res_mask,
                         cols_layout: tuple, pad_n: int):
        """Device-side chunk materialization from the resident tall
        buffers: gather the packed column rows and the mask columns by
        ``idx`` (int32 [pad_n], -1 = pad slot).  Pad slots gather row 0
        — always in-bounds, and their mask column is forced False, so
        they contribute exactly what a host chunk's fill-padded rows
        under a False mask contribute: nothing.  Gather commutes with
        ``unpack_transfer_cols`` (both are row-wise), so the unpacked
        columns are bit-identical to packing a host-gathered sliver."""
        safe = jnp.maximum(idx, 0)
        gathered = {dt: jnp.take(b, safe, axis=0)
                    for dt, b in res_cols.items()}
        cols = unpack_transfer_cols(gathered, cols_layout, pad_n)
        mask = jnp.take(res_mask, safe, axis=1) & (idx >= 0)[None, :]
        return cols, mask

    def _resident_grids(self, kinds: tuple, builders: list, tables_buf,
                        idx, res_cols: dict, res_mask, table_cols: dict,
                        cols_layout: tuple, tables_layout: tuple,
                        pad_n: int) -> tuple:
        """(raw grid [C_total, pad_n], mask) of a resident chunk."""
        self.trace_count += 1  # runs at TRACE time only
        cols, mask = self._gather_resident(idx, res_cols, res_mask,
                                           cols_layout, pad_n)
        cols.update(table_cols)
        tables = unpack_flat_tables(tables_buf, tables_layout, len(kinds))
        grids = template_grids(kinds, builders, tables, cols)
        return jnp.concatenate(grids, axis=0), mask

    def _sweep_fn_resident(self, kinds: tuple, k: int, return_bits: bool,
                           cols_layout: tuple, tables_layout: tuple,
                           pad_n: int, progs=None):
        """Masks-lane twin of :meth:`_sweep_fn` over DEVICE-RESIDENT
        columns: instead of a packed host chunk + bit-packed host mask,
        the jitted program takes the resident tall buffers + tall mask
        and a gather index vector — the only per-chunk H2D operand (and
        it caches).  Epilogue identical to the host twin, so verdicts
        are bit-identical by construction."""
        progs = progs if progs is not None else self.driver._programs
        uids = tuple(progs[kind].uid for kind in kinds)
        key = ("resident", kinds, uids, k, return_bits, cols_layout,
               tables_layout, pad_n)
        fn = self._sweep_fns.get(key)
        if fn is not None:
            return fn
        builders = [progs[kind]._build() for kind in kinds]
        use_pallas = self._use_pallas()

        def sweep_resident(tables_buf, idx, res_cols: dict, res_mask,
                           table_cols: dict):
            raw, mask = self._resident_grids(
                kinds, builders, tables_buf, idx, res_cols, res_mask,
                table_cols, cols_layout, tables_layout, pad_n)
            return _masks_fold(raw & mask, k, return_bits, use_pallas)

        fn = jax.jit(sweep_resident)
        self._sweep_fns[key] = fn
        return fn

    def _sweep_fn_resident_reduced(self, kinds: tuple, k: int,
                                   complete: bool, hit_cap: int,
                                   cols_layout: tuple,
                                   tables_layout: tuple, pad_n: int,
                                   progs=None):
        """Reduced-lane twin of :meth:`_sweep_fn_reduced` over resident
        columns.  The COMPLETE variant (snapshot/exact-totals chunks —
        the audit tick's shape) takes NO budget operand: the host twin
        uploads an unused zeros budget every dispatch, and dropping it
        here is what makes a warm clean-rows tick's H2D genuinely zero.
        The non-complete variant routes the epilogue through the Pallas
        fused fold (ops/pallas_topk.fused_fold_pallas) on single-chip
        TPU meshes: mask -> totals -> first-k -> occupancy in one VMEM
        pass over the raw grid."""
        progs = progs if progs is not None else self.driver._programs
        uids = tuple(progs[kind].uid for kind in kinds)
        key = ("resident_reduced", kinds, uids, k, complete, hit_cap,
               cols_layout, tables_layout, pad_n)
        fn = self._sweep_fns.get(key)
        if fn is not None:
            return fn
        builders = [progs[kind]._build() for kind in kinds]
        use_pallas = not complete and self._use_pallas()

        def sweep_resident_reduced(tables_buf, idx, res_cols: dict,
                                   res_mask, table_cols: dict, budget=None):
            raw, mask = self._resident_grids(
                kinds, builders, tables_buf, idx, res_cols, res_mask,
                table_cols, cols_layout, tables_layout, pad_n)
            return _reduced_fold(raw, mask, budget, k, complete,
                                      hit_cap, pad_n, use_pallas)

        fn = jax.jit(sweep_resident_reduced)
        self._sweep_fns[key] = fn
        return fn

    def warm_pass(self, constraints: Sequence, objects,
                  chunk_size: int, return_bits: bool = False,
                  route: bool = True) -> None:
        """Full warmup with ZERO device->host fetches: intern the whole
        corpus's vocabulary host-side (so no chunk of the real run
        crosses a vocab bucket and recompiles mid-sweep), then compile +
        execute one sweep per distinct (kind group, pad bucket) via
        :meth:`sweep_warm`.  The timed run that follows measures the
        steady state.

        ``objects`` may be any iterable (including a one-shot generator):
        chunks are scanned AS THEY FILL and released, so a streaming 1M
        corpus warms at O(chunk) memory; only one representative chunk
        per (group, pad bucket) is retained for the compile sweeps.

        ``route`` mirrors the audit manager's kind-bucketed routing
        (make_kind_router): objects stream into per-group chunks so each
        group warms its own (slimmer) schema/layout/sweep fn."""
        from gatekeeper_tpu.ops.listroute import route_chunks

        # per-group compile state, built lazily on each group's first chunk
        state: dict = {}  # g -> (cons_g, flattener, needs) or None
        buckets: dict = {}  # (g, pad) -> (cons_g, representative chunk)

        def group_state(g):
            if g in state:
                return state[g]
            cons_g = [c for c in constraints if c.kind in g]
            by_kind: dict[str, list] = {}
            for con in cons_g:
                by_kind.setdefault(con.kind, []).append(con)
            lowered = [k for k in by_kind
                       if k in self.driver._programs
                       and self.driver.inventory_exact(k)
                       and self.driver.extdata_ready(k)]
            if not lowered:
                state[g] = None
                return None
            # register the group's param-table needles/strings BEFORE any
            # compile: string-pred matrices are [T, V] with T = needles
            # registered so far — a group compiled before a later group's
            # build_param_table would bake a smaller T and recompile on
            # the first timed pass
            for kind in lowered:
                build_param_table(
                    self.driver._programs[kind].program,
                    by_kind[kind], self.driver.vocab)
            schema = Schema()
            for kind in lowered:
                schema.merge(self.driver._programs[kind].program.schema)
            fl = Flattener(schema, self.driver.vocab,
                           bucket=self._bucket,
                           lane=self.flatten_lane,
                           workers=self.flatten_workers,
                           label_keys=selector_label_keys(cons_g))
            st = (cons_g, fl, self._needs_union(lowered, fl.alias,
                                                counting=return_bits))
            state[g] = st
            return st

        def scan_chunk(g, ch):
            st = group_state(g)
            if st is None:
                return
            cons_g, fl, needs = st
            # EVERY chunk interns (the compile below must see the final
            # vocab, or the timed run's first chunk crosses a vocab
            # bucket and retraces mid-sweep), feeds the corpus column
            # stats (stable narrowed/elided wire layout — layout is part
            # of the jit key; per-chunk layouts would retrace the fused
            # sweep mid-run) AND records corpus ragged-width maxes (the
            # timed run pads every chunk to these targets)
            batch = fl.flatten(ch, pad_n=self._pad(len(ch)))
            fl.record_widths(batch, self._width_targets)
            col_stats_update(
                self._col_stats,
                slim_cols(pack_batch_cols(batch), needs))
            buckets.setdefault((g, self._pad(len(ch))), (cons_g, ch))

        if route:
            # the audit's own chunking: the warmed chunks are the
            # measured ones
            for g, buf in route_chunks(objects, make_kind_router(constraints),
                                       chunk_size, [0], [0, 0, 0]):
                scan_chunk(g, buf)
        else:
            g_all = frozenset(c.kind for c in constraints)
            buf = []
            for obj in objects:
                buf.append(obj)
                if len(buf) >= chunk_size:
                    scan_chunk(g_all, buf)
                    buf = []
            if buf:
                scan_chunk(g_all, buf)
        # the scan flattened at chunk-local widths; the timed run pads to
        # the corpus targets — fold pad values in so the layout holds
        merge_pad_stats(self._col_stats)
        for cons_g, ch in buckets.values():
            self.sweep_warm(cons_g, ch, return_bits)

    def sweep_warm(self, constraints: Sequence, objects: Sequence[dict],
                   return_bits: bool = False) -> None:
        """Compile + execute a sweep WITHOUT any device->host fetch.

        ``block_until_ready`` waits for execution but transfers nothing:
        a warm-up needs the compile and the run, not the result."""
        pending = self.sweep_submit(constraints, objects, return_bits)
        if not isinstance(pending, _PendingSweep):
            return
        jax.block_until_ready(pending.result)
        if pending.ref is not None:
            jax.block_until_ready(pending.ref.result)
        if self.collect in ("reduced", "differential") and not return_bits:
            # pre-compile the budgeted hit-buffer ladder (hit_bucket):
            # the timed run's chunks move DOWN the ladder as run-level
            # kept budgets drain, and a mid-sweep retrace would poison
            # the steady state the warm pass exists to protect
            def warm_budget(total):
                left = [total]

                def b(_con):
                    v = min(self.violations_limit, left[0])
                    left[0] -= v
                    return v

                return b

            for total in _HIT_STEPS:
                p = self.sweep_submit(constraints, objects, return_bits,
                                      budget=warm_budget(total))
                if isinstance(p, _PendingSweep):
                    jax.block_until_ready(p.result)
                    if p.ref is not None:
                        jax.block_until_ready(p.ref.result)

    def sweep(self, constraints: Sequence, objects: Sequence[dict],
              return_bits: bool = False):
        """One audit sweep chunk: {kind: (cons, idx, valid, counts, bits)}.

        idx/valid [C, k]: top-k violating object indices per constraint;
        counts [C]: violating-object totals; bits: bit-packed verdict rows
        [C, ceil(pad_n/8)] when ``return_bits`` (exact audit totals), else
        None.  Fallback (non-lowered) kinds are handled by the caller via
        driver.query_batch; this path is the mass-scan for lowered kinds.
        """
        return self.sweep_collect(
            self.sweep_submit(constraints, objects, return_bits))

    def sweep_submit(self, constraints: Sequence, objects: Sequence[dict],
                     return_bits: bool = False, budget=None):
        """Flatten + dispatch without fetching: jit dispatch is async, so
        the caller can flatten/submit the NEXT chunk while the device works
        (the pipeline-parallel fix for the reference's fully-sequential
        spill-review loop, SURVEY.md §2.9).

        Composed of the two pipeline stages — :meth:`sweep_flatten` (host
        columnize) then :meth:`sweep_dispatch` (masks/wire/device) — so
        the serial schedule and the staged pipeline run the exact same
        code."""
        return self.sweep_dispatch(
            self.sweep_flatten(constraints, objects, return_bits,
                               budget=budget))

    def sweep_schema(self, constraints: Sequence, programs=None) -> tuple:
        """(by_kind, lowered_kinds, merged_schema) — the columnize plan
        :meth:`sweep_flatten` runs; exposed so the resident-snapshot
        store (gatekeeper_tpu/snapshot/) flattens patches with EXACTLY
        the schema a fresh sweep of the same constraint group would use
        (the bit-identity precondition of the resync differential).
        ``lowered_kinds`` is empty when nothing is device-eligible.

        The merged union schema is cached per (generation epoch, lowered
        set): 46-template groups re-merge ~150 column specs per chunk
        otherwise, and the epoch key makes a generation swap a clean
        miss while chunks of one generation share one schema object."""
        progs = programs if programs is not None \
            else self.driver._programs
        by_kind: dict[str, list] = {}
        for con in constraints:
            by_kind.setdefault(con.kind, []).append(con)
        lowered = [k for k in by_kind
                   if k in progs
                   and self.driver.inventory_exact(k, programs=progs)
                   and self.driver.extdata_ready(k, programs=progs)]
        key = (getattr(self.driver, "plan_epoch", 0),
               tuple(sorted(lowered)))
        schema = self._schema_cache.get(key)
        if schema is None:
            schema = Schema()
            for kind in lowered:
                schema.merge(progs[kind].program.schema)
            if len(self._schema_cache) > 64:
                self._schema_cache.clear()
            self._schema_cache[key] = schema
        return by_kind, lowered, schema

    def sweep_flatten_from_batch(self, constraints: Sequence, batch,
                                 objects: Sequence[dict],
                                 return_bits: bool = False,
                                 alias: Optional[dict] = None,
                                 source: str = "", budget=None):
        """Pipeline stage 1 over a PRE-FLATTENED :class:`ColumnBatch` —
        the resident-snapshot lane: the columns were flattened when the
        watch patched them in, so a sweep over the snapshot pays only
        pack/slim here (no list, no columnize).  ``alias`` is the
        producing Flattener's prefix-axis alias map (slimming must keep
        fields read through either name).  Returns the same
        :class:`_FlatChunk` the columnizing lane produces."""
        programs = self.driver._programs  # capture the generation once
        by_kind, lowered, _schema = self.sweep_schema(constraints,
                                                      programs=programs)
        if not lowered:
            return {}
        cols = slim_cols(pack_batch_cols(batch),
                         self._needs_union(lowered, alias or {},
                                           programs=programs,
                                           counting=return_bits))
        n = len(objects)
        if batch.has_generate_name is not None:
            any_gen = bool(batch.has_generate_name[:n].any())
        else:
            any_gen = any(
                "generateName" in (o.get("metadata") or {})
                for o in objects)
        return _FlatChunk(by_kind, tuple(sorted(lowered)), cols, batch,
                          objects, any_gen, n, batch.n, return_bits,
                          source=source, budget=budget, programs=programs)

    def sweep_flatten_resident(self, rg, positions,
                               return_bits: bool = False, budget=None):
        """Stage-1 twin for DEVICE-RESIDENT snapshot rows: no flatten,
        no host gather, no column pack — the chunk is just the resident
        group + row positions.  Returns a :class:`_ResidentChunk` for
        :meth:`sweep_dispatch`, or None when the resident mirror went
        stale against the live generation (a swap landed between
        ``prepare`` and here) — the caller falls back to the host
        column path, which handles generations via _FlatChunk.programs."""
        programs = self.driver._programs  # capture the generation once
        if tuple(programs[k].uid for k in rg.kinds
                 if k in programs) != rg.uids:
            return None
        n = len(positions)
        if n == 0:
            return {}
        return _ResidentChunk(rg, positions, n, self._pad(n),
                              return_bits, budget=budget,
                              programs=programs)

    def sweep_flatten(self, constraints: Sequence, objects: Sequence[dict],
                      return_bits: bool = False, source: str = "",
                      budget=None):
        """Pipeline stage 1 (host; the C columnizer's three phases run
        with the GIL released, the items loop, the arrays' allocation,
        the intern merge and the assembly with it held): schema
        union + flatten + column pack/slim.  Returns a :class:`_FlatChunk`
        for :meth:`sweep_dispatch`, or {} when no kind is lowered (the
        caller's fallback lane handles everything)."""
        programs = self.driver._programs  # capture the generation once
        by_kind, lowered, schema = self.sweep_schema(constraints,
                                                     programs=programs)
        if not lowered:
            return {}
        n = len(objects)
        pad_n = self._pad(n)
        from gatekeeper_tpu.observability import tracing

        t0 = time.perf_counter()
        # the labels the group's selectors read ride beside the identity
        # columns, so the masks load no object to match it
        fl = self._flattener(schema, selector_label_keys(constraints))
        with tracing.span("ops.flatten.columnize", n=n,
                          lane=self.flatten_lane) as sp:
            batch = fl.flatten(objects, pad_n=pad_n)
            sp.set_attribute("lane_used", fl.lane_used)
        dt = time.perf_counter() - t0
        self._perf_add("flatten", dt)
        for k, v in fl.perf.items():  # sub-phases of the flatten above
            self._perf_add("fl_" + k, v)
        from gatekeeper_tpu.observability import costattr

        attr = costattr.active()
        if attr is not None:
            # flatten/columnize time splits across the templates whose
            # union schema the flatten served, by constraint count (the
            # rows are shared; the columns are schema-driven)
            attr.attribute(
                dt, {k: float(len(by_kind[k])) for k in lowered},
                costattr.EP_AUDIT, costattr.PHASE_FLATTEN)
        if self.metrics is not None:
            from gatekeeper_tpu.metrics import registry as M

            self.metrics.inc_counter(M.FLATTEN_LANE,
                                     {"lane": fl.lane_used or "unknown"})
            if dt > 0:
                self.metrics.set_gauge(M.FLATTEN_OBJECTS_PER_SECOND,
                                       n / dt)
            wu = getattr(fl, "last_workers_used", 0)
            if wu:
                self.metrics.set_gauge(M.FLATTEN_WORKER_COUNT, wu)
                busy = fl.perf.get("worker_busy", 0.0)
                if busy > 0:
                    # aggregate objects per worker-second: the number a
                    # perfectly-parallel pool would serve per worker
                    self.metrics.set_gauge(
                        M.FLATTEN_WORKER_OBJECTS_PER_SECOND, n / busy)
                self.metrics.set_gauge(M.FLATTEN_WORKER_MERGE_SECONDS,
                                       fl.perf.get("worker_merge", 0.0))
            fb = fl.perf.get("worker_fallbacks", 0.0)
            if fb:
                self.metrics.inc_counter(M.FLATTEN_WORKER_FALLBACKS,
                                         value=float(fb))

        cols = pack_batch_cols(batch)
        # transfer slimming: ship only the array fields some program reads
        cols = slim_cols(cols, self._needs_union(lowered, fl.alias,
                                                 programs=programs,
                                                 counting=return_bits))

        if batch.has_generate_name is not None:
            # native JSON lane: presence came back as a column — avoids
            # materializing RawJSON objects just for this scan
            any_gen = bool(batch.has_generate_name[:n].any())
        else:
            any_gen = any(
                "generateName" in (o.get("metadata") or {})
                for o in objects)
        return _FlatChunk(by_kind, tuple(sorted(lowered)), cols, batch,
                          objects, any_gen, n, pad_n, return_bits,
                          source=source, budget=budget, programs=programs)

    def sweep_dispatch(self, flat):
        """Pipeline stage 2 (host->device): match masks + param tables +
        wire packing + sharded device_put + async jit dispatch.  Accepts
        :meth:`sweep_flatten`'s output; {} passes through (empty submit).

        The collect lane is resolved here (``self.collect``): the
        differential lane dispatches the chunk through BOTH the reduced
        and the masks program so collect can assert them identical."""
        if not isinstance(flat, (_FlatChunk, _ResidentChunk)):
            return flat if isinstance(flat, dict) else {}
        from gatekeeper_tpu.observability import costattr, tracing

        lane = self.collect
        t0 = time.perf_counter()
        with tracing.span("device.sweep_dispatch", n=flat.n,
                          kinds=len(flat.kinds), collect=lane):
            if lane == "differential":
                pending = self._sweep_dispatch_impl(flat, lane="reduced",
                                                    host_occ=True)
                if pending.lane == "reduced":
                    pending.ref = self._sweep_dispatch_impl(
                        flat, lane="masks", host_occ=True)
                    pending.lane = "differential"
            else:
                pending = self._sweep_dispatch_impl(flat, lane=lane)
        wall = time.perf_counter() - t0
        if isinstance(pending, _PendingSweep):
            pending.dispatch_wall = wall
        attr = costattr.active()
        if attr is not None and isinstance(pending, _PendingSweep) \
                and pending.attr_weights:
            # the whole fused pass's wall time apportioned by mask row
            # occupancy — per-template shares sum back to the parent
            # span's wall time (the closure the tests assert).  The
            # reduced lane has no host-visible masks: its attr_weights
            # are None here and the attribution happens at collect, from
            # the device occupancy counts, over the same wall.
            attr.attribute(wall, pending.attr_weights,
                           costattr.EP_AUDIT, costattr.PHASE_DISPATCH,
                           rows=pending.attr_rows)
        return pending

    def _hit_state_for(self, kinds: tuple, pad_n: int) -> dict:
        key = (kinds, pad_n)
        st = self._hit_state.get(key)
        if st is None:
            st = self._hit_state[key] = {"cap": 256, "low": 0,
                                         "pinned": False, "blast": None}
        return st

    def _budget_hit_cap(self, flat, c_off: int, k_eff: int) -> tuple:
        """(per-constraint kept budgets [C] i32, static hit-buffer size)
        of a budgeted (non-complete) reduced dispatch.

        Buffer sizing: sum(budgets) bounds the selection, but
        constraints that never reach the run cap keep their budget
        forever — sizing by the PREVIOUS chunk's observed selection (2x
        margin) ships near-empty buffers in steady state; a chunk that
        suddenly selects more overflows into the masks-lane fallback
        once and resizes.  Inside a pass the budgets only shrink, so a
        budget sum that GREW means a new pass began with its kept slots
        reset: the previous pass's drained tail says nothing about it
        (sizing from it overflowed the head chunks of every group in
        every pass after the first), so the observation is dropped until
        this pass's first collect replaces it."""
        if flat.budget is None:
            budget_np = np.full(c_off, k_eff, np.int32)
        else:
            budget_np = np.fromiter(
                (min(k_eff, max(0, int(flat.budget(con))))
                 for kind in flat.kinds for con in flat.by_kind[kind]),
                np.int32, count=c_off)
        need = int(budget_np.sum())
        st = self._hit_state_for(flat.kinds, flat.pad_n)
        if need > st.get("need", need):
            st["blast"] = None
        st["need"] = need
        blast = st["blast"]
        guess = need if blast is None else \
            min(need, max(_HIT_STEPS[1], 2 * blast))
        return budget_np, hit_bucket(guess, c_off * k_eff)

    def _sweep_dispatch_impl(self, flat, lane: str = "masks",
                             host_occ: bool = False):
        if isinstance(flat, _ResidentChunk):
            # the resident lane shares every downstream convention
            # (lane resolution, differential pairing, the reduced
            # collect's masks-lane overflow fallback re-enters here)
            return self._dispatch_resident_impl(flat, lane=lane,
                                                host_occ=host_occ)
        from gatekeeper_tpu.observability import tracing
        from gatekeeper_tpu.resilience.faults import fault_point

        fault_point("device.dispatch", lane="sweep", n=flat.n)
        self.dispatch_count += 1
        from gatekeeper_tpu.ir import masks as masks_mod

        by_kind = flat.by_kind
        kinds = flat.kinds
        batch = flat.batch
        objects = flat.objects
        cols = flat.cols
        any_gen = flat.any_gen
        n, pad_n, return_bits = flat.n, flat.pad_n, flat.return_bits
        # the generation this chunk flattened under (its columns match
        # THESE programs' schemas; a swap between flatten and dispatch
        # must not retarget the chunk)
        progs = flat.programs if flat.programs is not None \
            else self.driver._programs
        k = self.violations_limit
        tables = []
        offsets = {}
        c_off = 0
        # constraint rows the tables answered / the per-object predicate,
        # and what the selector tables were evaluated on
        mask_counts: dict = {}
        # the masks run no C of ours that lets the GIL go, so their
        # thread's CPU seconds are what they held it for (an upper
        # bound), and ``masks`` less ``masks_cpu`` their own wait for it
        c0 = time.thread_time()
        with self._timed("masks",
                         tracing.span("device.sweep_dispatch.masks")):
            for kind in kinds:
                prog = progs[kind]
                cons = by_kind[kind]
                # param tables FIRST: they register StrPred needle rows
                # that the vocab tables below must include
                tables.append(build_param_table(prog.program, cons,
                                                self.driver.vocab))
                offsets[kind] = (c_off, c_off + len(cons))
                c_off += len(cons)
            # one call for the group: the chunk's columns are coded once
            # for all its constraints
            try:
                mask_all = masks_mod.constraint_masks(
                    [con for kind in kinds for con in by_kind[kind]],
                    batch, self.driver.vocab, objects,
                    sources=([flat.source] * len(objects)
                             if flat.source else None),
                    any_generate_name=any_gen, counts=mask_counts,
                    namespace_of=self.namespace_of,
                )
            finally:
                # written on every dispatch, a 0 too, and where the
                # oracle raised (a Namespace that was never synced)
                for key, counted in (("mask_rows_fast", "rows_vectorized"),
                                     ("mask_rows_slow", "rows_predicate"),
                                     ("mask_rows_selector", "rows_selector"),
                                     ("mask_ns_missing", "ns_missing"),
                                     ("masks_selector", "selector_s")):
                    self._perf_add(key, mask_counts.get(counted, 0))
            mask_rows = [mask_all[lo:hi] for lo, hi in offsets.values()]
            tracing.set_attribute("constraints", c_off)
            for key in ("rows_vectorized", "rows_predicate"):
                tracing.set_attribute(key, mask_counts[key])
        self._perf_add("masks_cpu", time.thread_time() - c0)
        # constraint rows of this dispatch, those whose program
        # ir/lower_cel.py produced, and those whose total the device
        # gives: every row of the top-k lane, the rows of the kinds whose
        # program counts its results in the exact lane (less, at collect,
        # a row whose count a hit left uncertified): written on every
        # dispatch, a 0 too
        cel_kinds = self.driver._cel_kinds
        counted = counted_kinds(progs, kinds, return_bits)
        for key, rows in (
                ("sweep_rows", c_off),
                ("sweep_rows_cel", sum(len(by_kind[kind]) for kind in kinds
                                       if kind in cel_kinds)),
                ("sweep_rows_counted",
                 sum(len(by_kind[kind]) for kind in counted)
                 if return_bits else c_off)):
            self._perf_add(key, rows)
            tracing.set_attribute(key, rows)
        from gatekeeper_tpu.observability import costattr

        complete = bool(return_bits)
        if lane == "reduced" and complete \
                and self._hit_state_for(kinds, pad_n)["pinned"]:
            # dense corpus: complete hit coordinates would outweigh the
            # bit grid — this (kinds, pad) shape ships masks from now on
            lane = "masks"
        attr_weights = attr_rows = None
        if lane != "reduced" and costattr.active() is not None:
            # row occupancy per template: live (constraint, object) mask
            # cells — the dispatch-share weight.  +1 keeps an all-masked
            # template visible (it still pays fixed per-template cost).
            # The reduced lane reads the SAME counts off the device
            # result at collect instead (no host mask walk).
            attr_rows = {k: int(np.asarray(m).sum())
                         for k, m in zip(kinds, mask_rows)}
            attr_weights = {k: 1.0 + r for k, r in attr_rows.items()}
        host_occ_np = None
        if host_occ:
            # differential reference: per-constraint live mask cells in
            # constraint-grid order, asserted equal to the device occ
            host_occ_np = np.concatenate(
                [np.asarray(m).sum(axis=1, dtype=np.int64)
                 for m in mask_rows]).astype(np.int32)
        table_cols: dict = {}
        # external-data join tables FIRST: the lane's bulk fetch lands
        # this chunk's deduped keys and the table build interns value
        # strings — the vocab tables built below must cover those sids
        for kind in kinds:
            ext_cols, _ok = self.driver.extdata_cols(kind, batch,
                                                     programs=progs)
            table_cols.update(ext_cols)
        for kind in kinds:
            for tk, tv in vocab_tables(
                progs[kind].program, self.driver.vocab,
                counting=kind in counted
            ).items():
                table_cols[tk] = tv
            for tk, tv in self.driver.inventory_cols(
                    kind, programs=progs)[0].items():
                table_cols[tk] = tv
        # ONE transfer per input: packed batch columns (data-sharded),
        # packed param tables (replicated, device-cached on content — the
        # constraint set rarely changes chunk-over-chunk), shared vocab/
        # inventory tables (device-cached on content), and the mask.
        with self._timed("wire_pack",
                         tracing.span("device.sweep_dispatch.pack")):
            pack_counts: dict = {}
            cols_bufs, cols_layout = pack_transfer_cols(
                cols, pad_n, stats=self._col_stats or None,
                counts=pack_counts)
            # written on every dispatch, a 0 too
            for path, shipped in pack_counts.items():
                self._perf_add("wire_cols_" + path, shipped)
                tracing.set_attribute("wire_cols_" + path, shipped)
        # the bit-packed match mask crosses beside the columns
        mask_bytes = c_off * pad_n // 8
        self._perf_add("mask_wire_bytes", mask_bytes)
        self._perf_add(
            "wire_bytes",
            sum(b.nbytes for b in cols_bufs.values()) + mask_bytes)
        hit_cap = 0
        budget_np = None
        with self._timed("dispatch",
                         tracing.span("device.sweep_dispatch.launch")):
            cols_bufs_dev = {
                dt: jax.device_put(b, NamedSharding(self.mesh,
                                                    P("data", None)))
                for dt, b in cols_bufs.items()}
            tables_bufs, tables_layout = pack_flat_tables(tables)
            pkey = (tables_layout,
                    tuple(sorted((dt, b.tobytes())
                                 for dt, b in tables_bufs.items())))
            tables_bufs_dev = self._param_dev_cache.pop(pkey, None)
            if tables_bufs_dev is None:
                tables_bufs_dev = {
                    dt: jax.device_put(b,
                                       NamedSharding(self.mesh, P(None)))
                    for dt, b in tables_bufs.items()}
            # bounded LRU (re-insert = recent): kind-bucketed sweeps cycle
            # one entry per group; a clear-on-miss would evict every other
            # group on each rotation
            self._param_dev_cache[pkey] = tables_bufs_dev
            while len(self._param_dev_cache) > 32:
                self._param_dev_cache.pop(
                    next(iter(self._param_dev_cache)))
            table_cols_dev = shard_batch_arrays(table_cols, self.mesh,
                                                self._table_dev_cache)
            # bit-packed match mask: [C, pad_n/8] uint8 on the wire (8x
            # fewer bytes than bool [C, N]); unpacked to bool inside the
            # jitted sweep where the expansion fuses into the grid AND
            mask = np.packbits(mask_all, axis=1)
            mask_dev = jax.device_put(
                mask, NamedSharding(self.mesh, P(None, "data"))
            )
            nfns0 = len(self._sweep_fns)
            if lane == "reduced":
                k_eff = min(k, pad_n)
                if complete:
                    budget_np = np.zeros(c_off, np.int32)  # unused there
                    st = self._hit_state_for(kinds, pad_n)
                    hit_cap = min(st["cap"], c_off * pad_n)
                else:
                    budget_np, hit_cap = self._budget_hit_cap(flat, c_off,
                                                              k_eff)
                budget_dev = jax.device_put(
                    budget_np, NamedSharding(self.mesh, P(None)))
                fn = self._sweep_fn_reduced(
                    kinds, k, complete, hit_cap, cols_layout,
                    tables_layout, pad_n, progs=progs)
                if len(self._sweep_fns) != nfns0:
                    self._record_warm(
                        ("reduced", kinds, k, complete, hit_cap,
                         cols_layout, tables_layout, pad_n),
                        cols_bufs, tables_bufs, table_cols, mask,
                        budget_np)
                result = fn(tables_bufs_dev, cols_bufs_dev, table_cols_dev,
                            mask_dev, budget_dev)
            else:
                fn = self._sweep_fn(kinds, k, return_bits, cols_layout,
                                    tables_layout, pad_n, progs=progs)
                if len(self._sweep_fns) != nfns0:
                    self._record_warm(
                        ("masks", kinds, k, return_bits, cols_layout,
                         tables_layout, pad_n),
                        cols_bufs, tables_bufs, table_cols, mask, None)
                result = fn(tables_bufs_dev, cols_bufs_dev, table_cols_dev,
                            mask_dev)
        # the masks lane attributes at dispatch and has no fallback to
        # keep the chunk for; the reduced lane the other way round
        pending = _PendingSweep(
            result, kinds, offsets, by_kind, n, return_bits,
            attr_weights=attr_weights, attr_rows=attr_rows, lane=lane,
            pad_n=pad_n, hit_cap=hit_cap,
            flat=flat if lane == "reduced" else None)
        pending.host_occ = host_occ_np
        pending.budget_np = None if complete else budget_np
        pending.counted = counted
        return pending

    def _table_upload_bytes(self, table_cols: dict) -> int:
        """Bytes ``shard_batch_arrays`` is ABOUT to upload given the
        current content cache — the resident lane's honest H2D meter
        (cache hits are free; a vocab bucket crossing pays once)."""
        total = 0
        for key, val in table_cols.items():
            if key.startswith(("fn:", "st:", "inv:", "ext:")):
                hit = self._table_dev_cache.get(key)
                if hit is not None and (
                        hit[0] is val
                        or (hit[0].shape == val.shape
                            and hit[0].dtype == val.dtype
                            and np.array_equal(hit[0], val))):
                    continue
            total += val.nbytes
        return total

    def _dispatch_resident_impl(self, flat, lane: str = "masks",
                                host_occ: bool = False):
        """Resident twin of :meth:`_sweep_dispatch_impl`: no host masks
        (they live in the resident mirror), no column wire pack, no
        batch upload.  What still crosses the wire — and only on cache
        miss — is the param-table pack (content-keyed LRU), vocab/
        inventory tables (content cache), and the gather index vector
        (per-position-tuple cache); every byte lands in
        ``perf['resident_h2d_bytes']`` so the warm clean-tick zero is
        measured, not asserted."""
        from gatekeeper_tpu.observability import tracing
        from gatekeeper_tpu.resilience.faults import fault_point

        fault_point("device.dispatch", lane="sweep_resident", n=flat.n)
        self.dispatch_count += 1
        rg = flat.rg
        by_kind, kinds = flat.by_kind, flat.kinds
        n, pad_n, return_bits = flat.n, flat.pad_n, flat.return_bits
        progs = flat.programs if flat.programs is not None \
            else self.driver._programs
        k = self.violations_limit
        h2d = 0
        tables = []
        offsets = {}
        c_off = 0
        for kind in kinds:
            cons = by_kind[kind]
            tables.append(build_param_table(progs[kind].program, cons,
                                            self.driver.vocab))
            offsets[kind] = (c_off, c_off + len(cons))
            c_off += len(cons)
        complete = bool(return_bits)
        if lane == "reduced" and complete \
                and self._hit_state_for(kinds, pad_n)["pinned"]:
            lane = "masks"
        host_occ_np = None
        if host_occ:
            # differential reference: the HOST mirror's per-constraint
            # occupancy over these rows — asserting it against the
            # device counts proves the resident mask never drifted
            pos = np.asarray(flat.positions, np.intp)
            host_occ_np = rg.mask_host[:, pos].sum(
                axis=1, dtype=np.int64).astype(np.int32)
        table_cols: dict = {}
        for kind in kinds:
            for tk, tv in vocab_tables(
                    progs[kind].program, self.driver.vocab).items():
                table_cols[tk] = tv
            for tk, tv in self.driver.inventory_cols(
                    kind, programs=progs)[0].items():
                table_cols[tk] = tv
        hit_cap = 0
        budget_np = None
        with self._timed("dispatch",
                         tracing.span("device.sweep_dispatch.launch")):
            tables_bufs, tables_layout = pack_flat_tables(tables)
            pkey = (tables_layout,
                    tuple(sorted((dt, b.tobytes())
                                 for dt, b in tables_bufs.items())))
            tables_bufs_dev = self._param_dev_cache.pop(pkey, None)
            if tables_bufs_dev is None:
                tables_bufs_dev = {
                    dt: jax.device_put(b,
                                       NamedSharding(self.mesh, P(None)))
                    for dt, b in tables_bufs.items()}
                h2d += sum(b.nbytes for b in tables_bufs.values())
            self._param_dev_cache[pkey] = tables_bufs_dev
            while len(self._param_dev_cache) > 32:
                self._param_dev_cache.pop(
                    next(iter(self._param_dev_cache)))
            h2d += self._table_upload_bytes(table_cols)
            table_cols_dev = shard_batch_arrays(table_cols, self.mesh,
                                                self._table_dev_cache)
            idx_dev, idx_bytes = rg.chunk_idx(flat.positions, pad_n)
            h2d += idx_bytes
            cols_layout = rg.cols_layout
            operands = (tables_bufs_dev, idx_dev, rg.cols_dev, rg.mask_dev,
                        table_cols_dev)
            if lane == "reduced":
                k_eff = min(k, pad_n)
                if complete:
                    # NO budget operand: the warm clean tick's only
                    # inputs are already device-resident
                    st = self._hit_state_for(kinds, pad_n)
                    hit_cap = min(st["cap"], c_off * pad_n)
                else:
                    budget_np, hit_cap = self._budget_hit_cap(flat, c_off,
                                                              k_eff)
                    operands += (jax.device_put(
                        budget_np, NamedSharding(self.mesh, P(None))),)
                    h2d += budget_np.nbytes
                fn = self._sweep_fn_resident_reduced(
                    kinds, k, complete, hit_cap, cols_layout,
                    tables_layout, pad_n, progs=progs)
            else:
                fn = self._sweep_fn_resident(
                    kinds, k, return_bits, cols_layout, tables_layout,
                    pad_n, progs=progs)
            result = fn(*operands)
        self._perf_add("resident_h2d_bytes", float(h2d))
        pending = _PendingSweep(
            result, kinds, offsets, by_kind, n, return_bits, lane=lane,
            pad_n=pad_n, hit_cap=hit_cap,
            flat=flat if lane == "reduced" else None)
        pending.host_occ = host_occ_np
        pending.budget_np = budget_np
        return pending

    def sweep_collect(self, pending):
        """Fetch + unpack a submitted sweep (the single device->host
        transfer)."""
        if pending is None:
            return {}
        if isinstance(pending, dict):  # empty submit
            return pending
        from gatekeeper_tpu.observability import tracing

        with tracing.span("device.sweep_collect", n=pending.n):
            return self._sweep_collect_impl(pending)

    def _sweep_collect_impl(self, pending):
        if pending.lane == "differential":
            return self._collect_differential(pending)
        if pending.lane == "reduced":
            return self._collect_reduced(pending)
        return self._collect_masks(pending)

    def _collect_masks(self, pending):
        t0 = time.perf_counter()
        if pending.return_bits:
            packed_np = np.asarray(pending.result[0])
            bits_np = np.asarray(pending.result[1])
            self._perf_add("d2h_bytes", packed_np.nbytes + bits_np.nbytes)
        else:
            packed_np = np.asarray(pending.result)
            bits_np = None
            self._perf_add("d2h_bytes", packed_np.nbytes)

        # top_k clamps k to the padded batch width; recover the effective k
        # from the packed layout [idx(k') | valid(k') | count]
        k_eff = (packed_np.shape[1] - 1) // 2
        n = pending.n
        out = {}
        for kind in pending.kinds:
            lo, hi = pending.offsets[kind]
            idx_np = packed_np[lo:hi, :k_eff]
            valid_np = (packed_np[lo:hi, k_eff: 2 * k_eff] != 0) & (idx_np < n)
            counts_np = packed_np[lo:hi, 2 * k_eff]
            kb = bits_np[lo:hi] if bits_np is not None else None
            out[kind] = self._swept_rows(
                pending, kind, (pending.by_kind[kind], idx_np, valid_np,
                                counts_np, kb))
        self._perf_add("collect", time.perf_counter() - t0)
        return out

    def _swept_rows(self, pending, kind: str, entry: tuple) -> SweptRows:
        """A collected kind's rows, marked where its counts are result
        totals; a row a hit left uncertified leaves ``sweep_rows_counted``
        here, since its total comes from the renders."""
        results = kind in pending.counted
        if results:
            self._perf_add("sweep_rows_counted",
                           -int(np.count_nonzero(entry[3] < 0)))
        return SweptRows(entry, results)

    @staticmethod
    def _kept_from_hits(sub: np.ndarray, ck: int, pad_n: int, k_eff: int,
                        n: int) -> tuple:
        """(idx [ck, k_eff], valid) rebuilt from a kind's sorted local
        hit coords — the same layout the masks-lane packed result
        carries, so every downstream fold runs unchanged."""
        idx = np.zeros((ck, k_eff), np.int32)
        valid = np.zeros((ck, k_eff), bool)
        if sub.size:
            ci = (sub // pad_n).astype(np.intp)
            oi = (sub % pad_n).astype(np.int32)
            starts = np.searchsorted(ci, np.arange(ck))
            j = np.arange(sub.size) - starts[ci]
            ok = (j < k_eff) & (oi < n)
            idx[ci[ok], j[ok]] = oi[ok]
            valid[ci[ok], j[ok]] = True
        return idx, valid

    def _collect_reduced(self, pending, _aux: bool = False):
        """Unpack one device-reduced chunk result: O(kept/violations)
        bytes off the wire, occupancy-weighted cost attribution from the
        on-device counts, masks-lane fallback when a complete-hits
        buffer overflowed (dense chunk), adaptive buffer sizing for the
        chunks after it."""
        from gatekeeper_tpu.observability import costattr

        t0 = time.perf_counter()
        arr = np.asarray(pending.result)
        self._perf_add("d2h_bytes", arr.nbytes)
        c_total = max(hi for _lo, hi in pending.offsets.values())
        pad_n, n = pending.pad_n, pending.n
        if pad_n <= 0xFFFF and not pending.counted:
            co = arr[:c_total].view(np.uint32)
            counts_all = (co & 0xFFFF).astype(np.int32)
            occ_all = (co >> 16).astype(np.int32)
            base = c_total
        else:
            counts_all = arr[:c_total]
            occ_all = arr[c_total: 2 * c_total]
            base = 2 * c_total
        nsel = int(arr[base])
        hits = arr[base + 1:]
        complete = pending.return_bits
        st = self._hit_state_for(pending.kinds, pad_n)
        if not complete:
            # budgeted buffer sizing feedback for the NEXT chunk
            st["blast"] = nsel
        if nsel > pending.hit_cap:
            # the chunk's true hit count overflowed the static buffer:
            # re-dispatch THIS chunk through the masks lane (bit grid,
            # always complete), and grow — or, past the point where
            # coordinates outweigh the grid, pin — the shape's buffer
            self._perf_add("collect_fallbacks", 1.0)
            if complete:
                cap = 256
                while cap < 2 * nsel:
                    cap *= 2
                if 4 * cap > (c_total * pad_n) // 8:
                    st["pinned"] = True
                else:
                    st["cap"] = cap
                st["low"] = 0
            flat, pending.flat = pending.flat, None
            fb = self._sweep_dispatch_impl(flat, lane="masks")
            attr = costattr.active()
            if attr is not None and fb.attr_weights:
                attr.attribute(pending.dispatch_wall, fb.attr_weights,
                               costattr.EP_AUDIT, costattr.PHASE_DISPATCH,
                               rows=fb.attr_rows)
            out = self._collect_masks(fb)
            return (out, None) if _aux else out
        if complete and not st["pinned"]:
            # de-escalate a buffer the corpus stopped filling (16-chunk
            # hysteresis; compiled variants stay cached either way)
            if st["cap"] > 256 and 4 * nsel < st["cap"]:
                st["low"] += 1
                if st["low"] >= 16:
                    st["cap"] //= 2
                    st["low"] = 0
            else:
                st["low"] = 0
        hits = hits[: min(nsel, hits.size)]
        k_eff = min(self.violations_limit, pad_n)
        out = {}
        for kind in pending.kinds:
            lo, hi = pending.offsets[kind]
            ck = hi - lo
            sub = (hits[(hits >= lo * pad_n) & (hits < hi * pad_n)]
                   .astype(np.int64) - lo * pad_n)
            idx_np, valid_np = self._kept_from_hits(sub, ck, pad_n,
                                                    k_eff, n)
            kb = HitRows(sub, pad_n, n, ck) if complete else None
            out[kind] = self._swept_rows(
                pending, kind, (pending.by_kind[kind], idx_np, valid_np,
                                counts_all[lo:hi], kb))
        attr = costattr.active()
        if attr is not None and pending.dispatch_wall > 0:
            # satellite of the reduced lane: occupancy weights come from
            # the DEVICE counts (host never saw the masks), apportioning
            # the dispatch wall exactly as the masks lane does
            rows = {kind: int(occ_all[lo:hi].sum())
                    for kind, (lo, hi) in pending.offsets.items()}
            attr.attribute(pending.dispatch_wall,
                           {kind: 1.0 + r for kind, r in rows.items()},
                           costattr.EP_AUDIT, costattr.PHASE_DISPATCH,
                           rows=rows)
        pending.flat = None
        self._perf_add("collect", time.perf_counter() - t0)
        if _aux:
            return out, {"counts": counts_all, "occ": occ_all,
                         "nsel": nsel, "hits": hits}
        return out

    def _collect_differential(self, pending):
        """``--collect=differential``: the reduced result must match the
        masks-lane host fold bit-for-bit — violation totals, canonical
        kept selections (the device top-k under the same budget), the
        complete hit sets of exact/snapshot chunks, and per-constraint
        mask occupancy.  Raises on the first divergence."""
        ref = self._collect_masks(pending.ref)
        red = self._collect_reduced(pending, _aux=True)
        out, aux = red
        # aux None = complete-hits overflow inside the differential: the
        # reduced side already fell back to a second masks pass, so the
        # two masks folds are compared (still a real assertion of
        # dispatch determinism)
        if pending.host_occ is not None and aux is not None:
            if not np.array_equal(aux["occ"], pending.host_occ):
                raise RuntimeError(
                    "collect differential: device occupancy != host mask "
                    f"occupancy ({aux['occ'].tolist()[:8]} vs "
                    f"{pending.host_occ.tolist()[:8]})")
        n = pending.n
        for kind, (cons, idx_m, valid_m, counts_m, bits_m) in ref.items():
            cons_r, idx_r, valid_r, counts_r, kb_r = out[kind]
            if not np.array_equal(np.asarray(counts_m),
                                  np.asarray(counts_r)):
                raise RuntimeError(
                    f"collect differential: totals differ for {kind}")
            for ci in range(len(cons)):
                if bits_m is not None:
                    ref_rows = violation_rows(bits_m, ci, n)
                    if kb_r is not None and not np.array_equal(
                            ref_rows, violation_rows(kb_r, ci, n)):
                        raise RuntimeError(
                            "collect differential: hit rows differ for "
                            f"{kind}[{ci}]")
                else:
                    ref_rows = np.asarray(idx_m[ci])[
                        np.asarray(valid_m[ci])]
                # kept selection: the reduced lane keeps the FIRST
                # min(count, budget, k) canonical hits; the masks lane's
                # selection clipped the same way must agree exactly
                want = int(np.asarray(counts_m)[ci])
                bud = pending.budget_np
                if bud is not None:
                    lo = pending.offsets[kind][0]
                    want = min(want, int(bud[lo + ci]))
                want = min(want, idx_r.shape[1])
                kept_ref = np.sort(ref_rows[:want]) if want else \
                    np.zeros(0, np.int64)
                kept_red = np.sort(idx_r[ci][valid_r[ci]])
                if not np.array_equal(kept_ref,
                                      kept_red.astype(np.int64)):
                    raise RuntimeError(
                        "collect differential: kept selection differs "
                        f"for {kind}[{ci}]")
        self._perf_add("collect_differential_ok", 1.0)
        return ref

    def _pad(self, n: int) -> int:
        base = self.mesh.shape["data"] * 8
        p = base
        while p < n:
            p *= 2
        return p

"""Measure the Pallas verdict-epilogue kernels against their XLA twins
on the live device, at the sweep's real shapes.

    python tools/bench_pallas.py [C] [N] [k]

Two lanes, both under one jit (as the fused sweep calls them), timed
over repeated dispatches with block_until_ready:

- **topk** — ``topk_violations_pallas`` vs the XLA ``top_k`` fold over
  an already-masked grid (the classic epilogue);
- **fused_fold** — ``fused_fold_pallas(grid_raw, mask, k)`` vs the XLA
  reference fold (mask apply -> violation totals -> top-k -> occupancy
  as separate XLA ops): the resident-tick epilogue, where the raw
  verdict block and the match mask meet in one VMEM pass.

Writes PALLAS_BENCH.json (created when missing): every run appends to
``history`` with its device + date.  TPU only: the kernels are compiled
by Mosaic, never interpreted, so without a chip this exits non-zero.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

_OUT = os.path.join(os.path.dirname(__file__), "..", "PALLAS_BENCH.json")


def _timed(run, arg, iters):
    r = run(*arg)
    jax.block_until_ready(r)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        r = run(*arg)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e6


def _topk_lane(c, n, k, iters):
    from gatekeeper_tpu.ops.pallas_topk import topk_violations_pallas
    from gatekeeper_tpu.parallel.sharded import topk_violations

    rng = np.random.default_rng(0)
    grid = jnp.asarray(rng.random((c, n)) < 0.05)

    def packed(fn):
        @jax.jit
        def run(g):
            idx, valid = fn(g, k)
            counts = jnp.sum(g, axis=1, dtype=jnp.int32)
            return jnp.concatenate(
                [idx, valid.astype(jnp.int32), counts[:, None]], axis=1)
        return run

    results = {}
    for name, fn in (("xla_topk", topk_violations),
                     ("pallas", topk_violations_pallas)):
        results[name] = _timed(packed(fn), (grid,), iters)
        print(f"topk/{name}: {results[name]:.0f} us/call",
              file=sys.stderr)
    return results


def _fused_fold_lane(c, n, k, iters):
    from gatekeeper_tpu.ops.pallas_topk import fused_fold_pallas
    from gatekeeper_tpu.parallel.sharded import topk_violations

    rng = np.random.default_rng(1)
    grid = jnp.asarray(rng.random((c, n)) < 0.05)
    mask = jnp.asarray(rng.random((c, n)) < 0.7)

    @jax.jit
    def xla_ref(g, m):
        masked = g & m
        idx, valid = topk_violations(masked, k)
        return jnp.concatenate(
            [idx, valid.astype(jnp.int32),
             jnp.sum(masked, axis=1, dtype=jnp.int32)[:, None],
             jnp.sum(m, axis=1, dtype=jnp.int32)[:, None]], axis=1)

    @jax.jit
    def fused(g, m):
        idx, valid, cnt, occ = fused_fold_pallas(g, m, k)
        return jnp.concatenate(
            [idx, valid.astype(jnp.int32), cnt[:, None], occ[:, None]],
            axis=1)

    results = {}
    for name, fn in (("xla_fold", xla_ref), ("pallas_fused", fused)):
        results[name] = _timed(fn, (grid, mask), iters)
        print(f"fused_fold/{name}: {results[name]:.0f} us/call",
              file=sys.stderr)
    return results


def _history_append(entry: dict) -> None:
    """Append to PALLAS_BENCH.json's history; the headline is the
    newest run."""
    try:
        with open(_OUT) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    history = doc.pop("history", [])
    entry = dict(entry)
    entry["date"] = time.strftime("%Y-%m-%d")
    history.append(entry)
    headline = {k: v for k, v in entry.items() if k != "date"}
    out_doc = dict(headline)
    out_doc["history"] = history
    with open(_OUT, "w") as f:
        json.dump(out_doc, f, indent=1)
        f.write("\n")


def main(c=46, n=32768, k=20, iters=50):
    dev = jax.devices()[0]
    print(f"devices: {jax.devices()}", file=sys.stderr)
    if dev.platform != "tpu":
        sys.exit(f"bench_pallas: platform is {dev.platform!r}; the Pallas "
                 "kernels only compile for the TPU")
    out = {"C": c, "N": n, "k": k, "iters": iters,
           "platform": dev.platform, "device_kind": dev.device_kind}
    out["topk"] = _topk_lane(c, n, k, iters)
    out["speedup_pallas_vs_xla"] = round(
        out["topk"]["xla_topk"] / out["topk"]["pallas"], 3)
    out["fused_fold"] = _fused_fold_lane(c, n, k, iters)
    out["speedup_fused_vs_xla_fold"] = round(
        out["fused_fold"]["xla_fold"] / out["fused_fold"]["pallas_fused"],
        3)
    _history_append(out)
    print(json.dumps(out))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:5]))

"""Phase-by-phase profile of one sweep chunk on the live device.

Usage: python tools/profile_sweep.py [n_objects] [chunk]
Times flatten / table build / H2D / dispatch+device / D2H separately so
a per-transfer latency pathology is attributable to its direction.
"""

import sys
import time

sys.path.insert(0, ".")


def main(n=32768, chunk=32768):
    from bench import build_client, log
    import jax
    import numpy as np

    log(f"devices: {jax.devices()}")
    client, tpu, nt, nc = build_client()
    from gatekeeper_tpu.parallel.sharded import (ShardedEvaluator,
                                                 make_mesh,
                                                 shard_batch_arrays,
                                                 shard_param_table)
    from gatekeeper_tpu.utils.synthetic import make_cluster_objects
    from gatekeeper_tpu.ops.flatten import Flattener, Schema
    from gatekeeper_tpu.ir import masks as masks_mod
    from gatekeeper_tpu.ir.program import (build_param_table, needed_fields,
                                           pack_batch_cols, slim_cols,
                                           vocab_tables)
    from jax.sharding import NamedSharding, PartitionSpec as P

    objects = make_cluster_objects(n)
    for o in objects:
        if o.get("kind") == "Ingress":
            client.add_data(o)
    cons = client.constraints()
    ev = ShardedEvaluator(tpu, make_mesh(), violations_limit=20)

    # warm: the production path (interning + corpus col stats + compile,
    # fetch-free), then one timed warm sweep
    t0 = time.perf_counter()
    ev.warm_pass(cons, objects[:chunk], chunk)
    log(f"warm_pass (compile): {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    ev.sweep(cons, objects[:chunk])
    log(f"warm sweep: {time.perf_counter()-t0:.3f}s")

    # now phase by phase (mirrors sweep_submit)
    objs = objects[:chunk]
    by_kind = {}
    for con in cons:
        by_kind.setdefault(con.kind, []).append(con)
    lowered = [k for k in by_kind
               if k in tpu._programs and tpu.inventory_exact(k)]
    t0 = time.perf_counter()
    schema = Schema()
    for kind in lowered:
        schema.merge(tpu._programs[kind].program.schema)
    pad_n = ev._pad(len(objs))
    batch = Flattener(schema, tpu.vocab).flatten(objs, pad_n=pad_n)
    t_flatten = time.perf_counter() - t0

    from gatekeeper_tpu.parallel.sharded import (pack_flat_tables,
                                                 pack_transfer_cols)

    t0 = time.perf_counter()
    cols = pack_batch_cols(batch)
    cols = slim_cols(cols, ev._needs_union(lowered))
    any_gen = (bool(batch.has_generate_name[:len(objs)].any())
               if batch.has_generate_name is not None else False)
    kinds = tuple(sorted(lowered))
    tables = []
    mask_rows = []
    for kind in kinds:
        prog = tpu._programs[kind]
        kcons = by_kind[kind]
        tables.append(build_param_table(prog.program, kcons, tpu.vocab))
        mask_rows.append(masks_mod.constraint_masks(
            kcons, batch, tpu.vocab, objs, any_generate_name=any_gen))
    table_cols = {}
    for kind in kinds:
        for tk, tv in vocab_tables(tpu._programs[kind].program,
                                   tpu.vocab).items():
            table_cols[tk] = tv
        for tk, tv in tpu.inventory_cols(kind)[0].items():
            table_cols[tk] = tv
    cols_bufs, cols_layout = pack_transfer_cols(
        cols, pad_n, stats=ev._col_stats or None)
    tables_bufs, tables_layout = pack_flat_tables(tables)
    t_tables = time.perf_counter() - t0

    n_arrays = len(cols_bufs) + len(tables_bufs) + len(table_cols) + 1
    total_mb = sum(b.nbytes for b in cols_bufs.values()) / 1e6
    t0 = time.perf_counter()
    cols_bufs_dev = {
        dt: jax.device_put(b, NamedSharding(ev.mesh, P("data", None)))
        for dt, b in cols_bufs.items()}
    tables_bufs_dev = {
        dt: jax.device_put(b, NamedSharding(ev.mesh, P(None)))
        for dt, b in tables_bufs.items()}
    table_cols_dev = shard_batch_arrays(table_cols, ev.mesh,
                                        ev._table_dev_cache)
    mask = np.concatenate(mask_rows, axis=0)
    mask_dev = jax.device_put(mask, NamedSharding(ev.mesh, P(None, "data")))
    jax.block_until_ready(cols_bufs_dev)
    jax.block_until_ready(tables_bufs_dev)
    jax.block_until_ready(table_cols_dev)
    jax.block_until_ready(mask_dev)
    t_h2d = time.perf_counter() - t0

    fn = ev._sweep_fn(kinds, 20, False, cols_layout, tables_layout, pad_n)
    t0 = time.perf_counter()
    result = fn(tables_bufs_dev, cols_bufs_dev, table_cols_dev, mask_dev)
    jax.block_until_ready(result)
    t_device = time.perf_counter() - t0

    t0 = time.perf_counter()
    packed_np = np.asarray(result)
    t_d2h = time.perf_counter() - t0

    log(f"phases for chunk={chunk} ({len(kinds)} kinds, "
        f"{n_arrays} device transfers, {total_mb:.1f} MB H2D):")
    log(f"  flatten:       {t_flatten*1000:8.1f} ms")
    log(f"  tables+masks:  {t_tables*1000:8.1f} ms")
    log(f"  H2D:           {t_h2d*1000:8.1f} ms")
    log(f"  device+disp:   {t_device*1000:8.1f} ms")
    log(f"  D2H (packed):  {t_d2h*1000:8.1f} ms  ({packed_np.nbytes/1e3:.0f} KB)")
    tot = t_flatten + t_tables + t_h2d + t_device + t_d2h
    log(f"  TOTAL:         {tot*1000:8.1f} ms -> "
        f"{chunk/tot:,.0f} reviews/s extrapolated")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 32768,
         int(sys.argv[2]) if len(sys.argv) > 2 else 32768)

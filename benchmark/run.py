"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python benchmark/run.py --check
    python benchmark/run.py --workload <cell> --rehearse [...]
    python benchmark/run.py --workload <cell> --traffic <mix> [...]

One process that holds the chip: it loads the cell's configuration and
traffic mix, sets up, measures for ``--seconds``, prints one line of JSON
last and exits.  Without a TPU (or with fewer chips than the cell asks for)
it prints no result and exits non-zero; it never falls back.  ``--rehearse``
is the only CPU mode: toy sizes, for the sandbox, and its line says so.
``--traffic`` runs the cell's configuration, held to the cell's metrics,
under another mix (``full.audit-sweep`` under ``traffic/audit-churn.json``
reads that mix in the top-k lane): a reading by hand, never a cell's run.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="toy sizes on whatever JAX finds; never a result")
    p.add_argument("--traffic", default=None,
                   help="a mix of benchmark/traffic/ in place of the cell's")
    p.add_argument("--check", action="store_true",
                   help="check BENCHMARK.json against the files it names")
    args = p.parse_args(argv)

    from benchmark import manifest

    if args.check:
        faults = manifest.check()
        for fault in faults:
            print(f"benchmark: {fault}", file=sys.stderr)
        print(f"BENCHMARK.json: {len(faults)} faults")
        return 1 if faults else 0
    if not args.workload:
        p.error("--workload is required")
    cell = manifest.Cell(args.workload, rehearse=args.rehearse,
                         traffic=args.traffic)
    seconds = args.seconds
    if seconds is None:
        seconds = manifest.read_json(manifest.MANIFEST)["run_seconds"]

    from benchmark import admit, audit, harness

    run = harness.Run(cell, args.seed, seconds, bool(args.trace),
                      args.rehearse, T0)
    drivers = {"audit": audit.run, "open": admit.run, "closed": admit.run}
    try:
        result = drivers[cell.traffic["loop"]](run)
    except harness.NoDevice as e:
        print(f"benchmark: {e}; refusing to run (the only CPU mode is "
              "--rehearse)", file=sys.stderr)
        return 1
    finally:
        run.reap()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

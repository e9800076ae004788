"""Find the knee of an open-loop admission mix, by hand, on the chip.

    python benchmark/find_knee.py --config library-full --traffic admit-steady \
        --rates 5,10,20,40,80,160 [--seconds 30] [--seed 0]

Not run by the driver, and run before the pair is a cell: a cell needs the
rate this finds.  One process serves the configuration's webhook once and
offers the mix at each rate in turn, for ``--seconds`` after a short
warm-up.  The knee is the highest rate the system sustained: p99 from due
time under ``P99_LIMIT_MS``, every request answered as the interpreter
answers, nothing compiled, and no growing backlog (the last third of the
window no slower than twice the first).  Write 0.8 of it into the traffic
file as ``rate_per_s``, and the table, with the date and the commit, into
PERF.md.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P99_LIMIT_MS = 1000.0  # ISSUE 22: sustained means p99 under 1 s


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests per second, rising")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    from benchmark import admit, harness, manifest, stats

    cell = manifest.Cell.unlisted(args.config, args.traffic,
                                  rehearse=args.rehearse)
    if cell.traffic["loop"] != "open":
        p.error("only an open loop has a knee to find")
    run = harness.Run(cell, args.seed, args.seconds, False, args.rehearse,
                      T0)
    table, knee = [], None
    try:
        with admit.Served(run) as served:
            for rate in (float(r) for r in args.rates.split(",")):
                traffic = dict(cell.traffic, rate_per_s=rate, warmup_s=3)
                d = served.drive(traffic, args.seconds, False)
                sc = served.score(d)
                third = max(1, len(sc["lat_ms"]) // 3)
                first = stats.median(sc["lat_ms"][:third])
                last = stats.median(sc["lat_ms"][-third:])
                row = {"rate_per_s": rate, "requests": sc["requests"],
                       "p50_ms": sc["p50_ms"], "p99_ms": sc["p99_ms"],
                       "late_ms_p99": sc["late_ms_p99"], "shed": sc["shed"],
                       "unanswered": sc["unanswered"],
                       "mismatched": sc["mismatched"],
                       "compiles": run.compiles_between(d["w0"], d["w1"]),
                       "inflight_limit": served.program.metrics.get_gauge(
                           "overload_inflight_limit"),
                       "first_third_p50_ms": first,
                       "last_third_p50_ms": last}
                row["sustained"] = (
                    sc["p99_ms"] < P99_LIMIT_MS
                    and sc["ok"] == sc["requests"]
                    and row["compiles"] == 0
                    and last <= 2 * max(first, 1.0))
                if row["sustained"]:
                    knee = rate
                table.append(row)
                print(json.dumps(row), flush=True)
    except harness.NoDevice as e:
        print(f"find_knee: {e}", file=sys.stderr)
        return 1
    finally:
        run.reap()
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "device": run.device,
                      "seconds": args.seconds, "seed": args.seed,
                      "knee_per_s": knee,
                      "rate_per_s_at_0.8": knee and 0.8 * knee,
                      "table": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Traffic of kinds ``open`` and ``closed``: AdmissionReviews over HTTP from
the load generator, a JAX-free child, to the webhook this process serves
as ``python -m gatekeeper_tpu`` serves it.

Set-up: the body pool and its schedule from the seed, the reference
children (the interpreter's answer to every body of the pool), the
program, the inventory, the served port, the grid warmed as ``__main__``
warms it and then with the pool, and ``warmup_s`` of the cell's own load.
Window: ``--seconds`` of that load.

No cell of BENCHMARK.json has such traffic yet (PERF.md section 7 says
why); ``find_knee.py`` and the tests drive this module.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

from benchmark import answers, cluster, loadgen, reference, stats, wiring
from benchmark.harness import Run

HISTOGRAMS = ("webhook_batch_size", "webhook_batch_queue_wait_seconds")
LEAD_S = 2.0  # from the plan's writing to the generator's first request


def make_pool(cl: cluster.Cluster, traffic: dict, seed: int) -> list:
    """The pool of distinct AdmissionReviews, in the order they are sent,
    round and round: the configuration's own cluster generator (its kind
    mix and namespace skew) under the mix's operations.  With ``rollouts``,
    that share of the pool comes in runs of near-identical Pod CREATEs (one
    Pod, copied under other names), and the base mix is strewn between
    them."""
    rng = random.Random(f"{seed}:pool")
    ops, op_cum = list(traffic["operations"]), []
    for w in traffic["operations"].values():
        op_cum.append((op_cum[-1] if op_cum else 0.0) + w)
    base = cl.stream()
    roll = traffic.get("rollouts")
    pool: list = []
    left, template, r = 0, None, 0
    while len(pool) < traffic["pool"]:
        uid = f"u{len(pool)}"
        if roll and rng.random() < roll["share"]:
            if not left:
                left = rng.randint(*roll["run"])
                r += 1
                template = cl._pod(rng, 0, cl.namespace(rng))
            left -= 1
            pod = json.loads(json.dumps(template))
            pod["metadata"]["name"] = f"rollout-{r}-{left}"
            pool.append(cluster.admission_review(pod, uid, "CREATE"))
        else:
            op = cl._pick(rng, ops, op_cum)
            pool.append(cluster.admission_review(next(base), uid, op))
    return pool


def make_plan(traffic: dict, seed: int, seconds: float) -> dict:
    """What the generator is to send, and when, relative to its start.  An
    open loop is a Poisson process at ``rate_per_s``, through the warm-up
    and the window alike, walking the pool in order."""
    warmup = float(traffic["warmup_s"])
    plan = {"loop": traffic["loop"], "connections": traffic["connections"],
            "timeout_s": traffic["timeout_s"], "warmup_s": warmup,
            "seconds": seconds}
    if traffic["loop"] == "closed":
        plan["sequence"] = list(range(traffic["pool"]))
        return plan
    rng = random.Random(f"{seed}:arrivals")
    rate = float(traffic["rate_per_s"])
    times = stats.arrival_times(rng, round(rate * warmup), warmup) + [
        warmup + t
        for t in stats.arrival_times(rng, round(rate * seconds), seconds)]
    plan["schedule"] = [[t, i % traffic["pool"]]
                        for i, t in enumerate(times)]
    return plan


def warm_grid(program, pool: list, own_step: int) -> None:
    """The grid lane warmed as ``__main__`` warms it before it serves
    (:1171-1186): a dummy Pod in batches of 9, 18, 36 and 64.  Then the
    pool itself in batches of ``own_step`` (the mix's ``warm_own_step``,
    listed under its ``assumed``): the shapes the grid compiles come from
    the data (which kinds a batch holds, which optional fields), and a
    dummy has none of them.  What a batch of the window still compiles is
    counted (``entry.compiles_in_window``) and its wait is the requests'."""
    from gatekeeper_tpu.apis.constraints import WEBHOOK_EP
    from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
    from gatekeeper_tpu.target.review import (AugmentedReview,
                                              AugmentedUnstructured)
    from gatekeeper_tpu.webhook.policy import parse_admission_review

    batcher = program.batcher
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "warmup", "namespace": "default"},
           "spec": {"containers": [{"name": "c", "image": "warmup"}]}}
    warm = [AugmentedUnstructured(object=dict(pod), source=SOURCE_ORIGINAL)
            for _ in range(batcher.max_batch)]
    n = max(1, batcher.small_batch + 1)
    while n <= batcher.max_batch:
        program.client.review_batch(warm[:n])
        n *= 2
    program.client.review_batch(warm)
    reviews = [AugmentedReview(admission_request=parse_admission_review(body),
                               namespace=None, source=SOURCE_ORIGINAL,
                               is_admission=True) for body in pool]
    for i in range(0, len(reviews), own_step):
        program.client.review_batch(reviews[i:i + own_step],
                                    enforcement_point=WEBHOOK_EP)


def hist_state(metrics) -> dict:
    out = {}
    for name in HISTOGRAMS:
        h = metrics.get_histogram(name)
        out[name] = (h["count"], h["sum"]) if h else (0, 0.0)
    return out


def sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


class Served:
    """The webhook, served and warm, with the interpreter's answer to every
    body of the pool: everything of a run but the load."""

    def __init__(self, run: Run):
        self.run = run
        cfg, traffic = run.cell.config, run.cell.traffic
        # first of all, so that a host without the chip is refused at once
        run.require_device()
        run.mark("native_jax_devices")
        cl = cluster.Cluster(cfg["cluster"], cfg["objects"], run.seed)
        pool = make_pool(cl, traffic, run.seed)
        self.bodies_path = os.path.join(run.work, "bodies.jsonl")
        with open(self.bodies_path, "wb") as f:
            for body in pool:
                f.write(cluster.dumps(body) + b"\n")
        inv_path = os.path.join(run.work, "inventory.jsonl")
        with open(inv_path, "wb") as f:
            for kind in cfg["referential_kinds"]:
                for obj in cl.inventory(kind):
                    f.write(cluster.dumps(obj) + b"\n")
        ref = reference.Children(run.spawn, cfg, "admit", run.seed,
                                 [inv_path], self.bodies_path, run.work,
                                 traffic["reference_children"])
        run.mark("pool_inventory_reference_children")
        self.program = wiring.Program(cfg, run.traced, run.seed)
        run.mark("program_library")
        try:
            with open(inv_path, "rb") as f:
                self.n_inv = self.program.sync_inventory(
                    json.loads(ln) for ln in f)
            self.port = self.program.build_serving(
                cl.namespace_objects()).port
            run.mark("inventory_serving")
            warm_grid(self.program, pool, traffic["warm_own_step"])
            run.mark("warm_grid")
            self.program.begin_background_compile()
            parts = ref.join()
            run.mark("reference_join")
        except BaseException:
            self.program.close()
            raise
        self.want = [None] * len(pool)  # digest owed to each body
        for k, part in enumerate(parts):
            self.want[k::len(parts)] = part

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.program.close()

    def drive(self, traffic: dict, seconds: float, traced: bool) -> dict:
        """Start the generator on ``traffic`` and sit out its warm-up and
        its window.  Returns the generator's rows and the window's ends."""
        run, metrics = self.run, self.program.metrics
        plan = make_plan(traffic, run.seed, seconds)
        out_path = os.path.join(run.work, "loadgen.out.json")
        plan_path = os.path.join(run.work, "loadgen.plan.json")
        start = time.monotonic() + LEAD_S
        w0 = start + plan["warmup_s"]
        w1 = w0 + seconds
        plan.update(port=self.port, bodies=self.bodies_path,
                    output=out_path, start=start)
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        child = run.spawn([loadgen.__file__, plan_path])
        sleep_until(w0)
        out = {"w0": w0, "w1": w1, "w0_wall": time.time(),
               "before": hist_state(metrics), "plan": plan}
        if traced:
            sleep_until(w0 + traffic["trace_offset_s"])
            with run.device_trace():
                time.sleep(traffic["trace_seconds"])
        sleep_until(w1)
        out.update(after=hist_state(metrics), w1_wall=time.time())
        if child.wait(timeout=seconds + 120) != 0:
            raise RuntimeError(f"the load generator exited "
                               f"{child.returncode}")
        with open(out_path) as f:
            out.update(json.load(f))
        return out

    def score(self, d: dict) -> dict:
        """The window's requests against the reference and the clock.  A
        request has failed when it was not answered 200 within
        ``timeout_s`` of the time it was due, was shed, or was answered
        otherwise than the interpreter answers."""
        plan, w0, w1 = d["plan"], d["w0"], d["w1"]
        timeout = plan["timeout_s"]
        open_loop = plan["loop"] == "open"
        ok = completed = mismatched = shed = unanswered = 0
        due_at: list = []
        sent_at: list = []
        end_at: list = []
        for body, due, sent, done, status, digest, code in d["rows"]:
            answered = done is not None and status == 200
            right = (answered and code != answers.SHED_CODE
                     and digest == self.want[body])
            # the rate counts what was COMPLETED inside the window, whenever
            # it was due: above the knee an open loop answers late
            completed += right and w0 <= done < w1
            # a request that failed has missed every latency limit: it
            # stands in the sample at the timeout, it does not drop out
            end = done if answered else due + timeout
            if not w0 <= (due if open_loop else end) < w1:
                continue
            due_at.append(due)
            sent_at.append(sent)
            end_at.append(end)
            ok += right
            shed += code == answers.SHED_CODE
            unanswered += not answered
            if answered and code != answers.SHED_CODE and not right:
                mismatched += 1
                if mismatched <= 3:
                    print(f"benchmark: body {body} was answered {digest}, "
                          f"the interpreter answers {self.want[body]}",
                          file=sys.stderr)
        lat_ms = [1e3 * x for x in stats.open_loop_latencies(due_at, end_at)]
        late_ms = [1e3 * x for x in stats.lateness(due_at, sent_at)]
        out = {"requests": len(due_at), "ok": ok, "mismatched": mismatched,
               "shed": shed, "unanswered": unanswered, "lat_ms": lat_ms,
               "connections_peak": d["connections_peak"]}
        if due_at:
            out.update(reviews_per_s=completed / (w1 - w0),
                       p50_ms=stats.median(lat_ms),
                       p99_ms=stats.percentile(lat_ms, 99),
                       late_ms_p99=stats.percentile(late_ms, 99))
        return out


def run(run: Run) -> dict:
    traffic = run.cell.traffic
    with Served(run) as served:
        program = served.program
        d = served.drive(traffic, run.seconds, run.traced)
        setup_s = d["w0"] - run.t0
        run.marks["generator_lead_and_warmup"] = \
            LEAD_S + float(traffic["warmup_s"])
        sc = served.score(d)
        sc.pop("lat_ms")
        e2e = {"setup_s": setup_s}
        if sc["requests"]:
            e2e["admit_reviews_per_s"] = sc["reviews_per_s"]
            if traffic["loop"] == "open":
                e2e["admit_p50_ms"] = sc["p50_ms"]
                e2e["admit_p99_ms"] = sc["p99_ms"]
        compiles = run.compiles_between(d["w0"], d["w1"])
        spans, reduced = [], None
        if run.traced:
            spans = program.spans(d["w0_wall"], d["w1_wall"])
            reduced = run.reduce_trace(spans)
        hist = {name: {"count": d["after"][name][0] - d["before"][name][0],
                       "sum": d["after"][name][1] - d["before"][name][1]}
                for name in HISTOGRAMS}
        obs = {"perf": {"driver": dict(program.tpu.perf)}, "spans": spans,
               "hist": hist, "counts": {"compiles_in_window": compiles},
               "loadgen": sc, "trace": reduced}
        notes = {
            "loop": traffic["loop"], "connections": traffic["connections"],
            "rate_per_s": traffic.get("rate_per_s"), "loadgen": sc,
            "inventory_synced": served.n_inv, "pool": len(served.want),
            "compiles_in_window": compiles,
            "compiles_in_setup": run.compiles_between(run.t0, d["w0"]),
            "inflight_limit": program.metrics.get_gauge(
                "overload_inflight_limit"),
            "batch_size_mean": (hist["webhook_batch_size"]["sum"]
                                / max(1, hist["webhook_batch_size"][
                                    "count"])),
            "xla_cache_dir": program.xla_cache_dir,
        }
        correct = sc["mismatched"] == 0 and sc["ok"] > 0
        return run.result(correct, sc["requests"],
                          sc["requests"] - sc["ok"], e2e, obs, notes)

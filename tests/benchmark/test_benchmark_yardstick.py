"""The benchmark's yardstick, checked on the CPU: the arithmetic that turns
clocks, spans and traces into metrics, the generator's distributions, and
the manifest.  Nothing here times the system under test."""

from __future__ import annotations

import collections
import http.server
import json
import os
import random
import subprocess
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (admit, answers, audit, cluster,  # noqa: E402
                       manifest, readers, roofline, stats, xplane)

TESTDATA = os.path.join(ROOT, "benchmark", "testdata")


# --- percentiles, latency from due time, lateness ----------------------------

@pytest.mark.parametrize("q,want", [(50, 50), (99, 99), (100, 100), (1, 1),
                                    (99.5, 100)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(range(1, 101), q) == want


def test_percentile_of_few_samples_is_one_of_them():
    assert stats.percentile([7.0, 3.0, 5.0], 99) == 7.0
    assert stats.percentile([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_median_and_spread():
    assert stats.median([4, 1, 3]) == 3
    assert stats.median([4, 1, 3, 2]) == 2.5
    # quartiles 2 and 6 of 1..7 around the median 4
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)
    assert stats.spread([5, 5, 5, 5]) == 0.0


@pytest.mark.parametrize("slow", [3, 4])
def test_mean_moves_by_a_share_of_the_gap_between_two_modes(slow):
    # seven passes in two modes, 3.75 s and 4.10 s: one pass changing its
    # mode moves the median by the whole gap and the mean by a seventh
    passes = [4.10] * slow + [3.75] * (7 - slow)
    assert stats.median(passes) == (4.10 if slow == 4 else 3.75)
    assert stats.mean(passes) == pytest.approx(3.75 + 0.35 * slow / 7)
    with pytest.raises(ValueError):
        stats.mean([])


def test_latency_runs_from_due_time_not_from_send_time():
    # one connection, 100 ms of service, four requests due at once: the
    # fourth is answered 400 ms after it was due although it was in flight
    # for 100 ms only; a request never answered drops out here (the caller
    # stands it in at the timeout)
    due = [0.0, 0.0, 0.0, 0.0, 1.0]
    sent = [0.0, 0.1, 0.2, 0.3, 1.0]
    done = [0.1, 0.2, 0.3, 0.4, None]
    lat = stats.open_loop_latencies(due, done)
    assert lat == pytest.approx([0.1, 0.2, 0.3, 0.4])
    assert stats.lateness(due, sent) == pytest.approx(
        [0.0, 0.1, 0.2, 0.3, 0.0])


def test_lateness_never_negative():
    assert stats.lateness([1.0, 2.0], [0.9, None]) == [0.0]


# --- arrivals -----------------------------------------------------------------

def test_poisson_arrivals_offer_the_same_work_on_every_seed():
    for seed in range(4):
        times = stats.arrival_times(random.Random(seed), 1200, 30.0)
        assert len(times) == 1200 and times == sorted(times)
        assert 0.0 <= times[0] and times[-1] < 30.0
        # exponential gaps: their mean is 1/rate, and about 1/e of them
        # are longer than that
        gaps = [b - a for a, b in zip(times, times[1:])]
        assert sum(gaps) / len(gaps) == pytest.approx(1 / 40, rel=0.05)
        long = sum(1 for g in gaps if g > 1 / 40) / len(gaps)
        assert long == pytest.approx(0.368, abs=0.05)
    a = stats.arrival_times(random.Random(1), 100, 5.0)
    assert a == stats.arrival_times(random.Random(1), 100, 5.0)
    assert a != stats.arrival_times(random.Random(2), 100, 5.0)


def test_open_plan_is_poisson_singles_through_the_pool_in_order():
    traffic = {"loop": "open", "rate_per_s": 80, "pool": 128, "warmup_s": 2,
               "connections": 32, "timeout_s": 3.0}
    plan = admit.make_plan(traffic, 5, 4.0)
    rows = plan["schedule"]
    assert len(rows) == 80 * 2 + 80 * 4
    assert [i for _, i in rows] == [j % 128 for j in range(len(rows))]
    assert all(t < 2.0 for t, _ in rows[:160])
    assert all(2.0 <= t < 6.0 for t, _ in rows[160:])
    assert rows == sorted(rows, key=lambda r: r[0])
    assert len({t for t, _ in rows}) == len(rows)  # no two at one instant
    assert plan == admit.make_plan(traffic, 5, 4.0)
    assert plan != admit.make_plan(traffic, 6, 4.0)


def test_closed_plan_walks_the_pool():
    traffic = {"loop": "closed", "pool": 64, "warmup_s": 2,
               "connections": 64, "timeout_s": 3.0}
    plan = admit.make_plan(traffic, 5, 4.0)
    assert plan["sequence"] == list(range(64)) and "schedule" not in plan


# --- the trace reduction --------------------------------------------------------

def test_union_gaps_and_busy_on_intervals_written_down():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 110)]
    assert xplane.union(iv) == [[0, 20], [30, 45], [100, 110]]
    assert xplane.busy_ns(iv, 0, 120) == 45
    assert xplane.busy_ns(iv, 15, 35) == 10  # clipped at both ends
    assert xplane.gaps(iv, 0, 120) == [(20, 30), (45, 100), (110, 120)]
    assert xplane.gaps([], 5, 9) == [(5, 9)]


def _tpu_like_planes():
    ops = [("fusion.1", 100.0, 50.0, {}), ("copy.2", 150.0, 25.0, {}),
           ("fusion.1", 400.0, 50.0, {}), ("fusion.9", 900.0, 100.0, {})]
    modules = [("jit_fused(123)", 90.0, 100.0, {}),
               ("jit_fused(123)", 390.0, 70.0, {}),
               ("jit_other(7)", 890.0, 120.0, {})]
    host = [(xplane.WINDOW, 0.0, 1000.0, {"wall_ns": 5_000_000_000})]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": modules},
                       {"name": "XLA Ops", "events": ops},
                       {"name": "Steps", "events": [("0", 0.0, 1e3, {})]}]},
            {"name": "/host:CPU", "lines": [{"name": "main",
                                             "events": host}]}]


def test_reduce_reads_device_planes_and_labels_gaps_with_host_spans():
    spans = [{"name": "audit.sweep", "start_ts": 5.0, "duration_s": 1e-6,
              "thread_id": 1},
             {"name": "ops.flatten.columnize", "start_ts": 5.0000002,
              "duration_s": 2e-7, "thread_id": 2},
             {"name": "pipeline.stage.flatten", "start_ts": 5.0000001,
              "duration_s": 4e-7, "thread_id": 2}]
    r = xplane.reduce(_tpu_like_planes(), spans)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(225e-9)
    assert r["device_ops"][0] == ["jit_fused/fusion.1", pytest.approx(1e-7)]
    assert ["jit_other/fusion.9", pytest.approx(1e-7)] in r["device_ops"]
    # the longest gap is [450, 900); at its middle, 675 ns, thread 1 is in
    # audit.sweep and thread 2 is in nothing
    assert r["idle_gaps"][0] == ["audit.sweep", pytest.approx(450e-9)]
    # the next, [175, 400): thread 2's innermost open span wins
    assert r["idle_gaps"][1] == ["audit.sweep+ops.flatten.columnize",
                                 pytest.approx(225e-9)]


def test_reduce_averages_busy_time_over_devices():
    planes = _tpu_like_planes()
    second = json.loads(json.dumps(planes[0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["fusion.1", 100.0, 25.0, {}]]
    r = xplane.reduce(planes + [second], [])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((225e-9 + 25e-9) / 2)


def test_reduce_without_a_window_annotation_is_an_error():
    planes = _tpu_like_planes()[:1]
    with pytest.raises(ValueError):
        xplane.reduce(planes, [])


def test_reduce_on_the_recorded_trace():
    """A trace recorded by the profiler (three matmuls 20 ms apart inside
    the WINDOW annotation), read back through jax.profiler.ProfileData."""
    planes = xplane.load(os.path.join(TESTDATA,
                                      "cpu_three_matmuls.xplane.pb"))
    lo, hi, offset = xplane.window(planes)
    assert hi > lo and offset > 1e18  # wall clock: ns since 1970
    r = xplane.reduce(planes, [])
    assert r["devices"] == 1
    assert 0.06 < r["window_s"] < 0.2
    assert 0 < r["busy_s"] < 0.02
    assert r["device_ops"][0][0] == "jit__lambda/dot_general.1"
    assert sum(s for _, s in r["device_ops"]) >= r["busy_s"] * 0.999
    long_gaps = [s for _, s in r["idle_gaps"] if s > 0.019]
    assert len(long_gaps) == 3  # the three sleeps
    # busy and idle make up the window
    ops = xplane.device_ops(planes)["CPU:0"]
    iv = [(s, e) for s, e, _ in ops]
    idle = sum(e - s for s, e in xplane.gaps(iv, lo, hi))
    assert idle + xplane.busy_ns(iv, lo, hi) == pytest.approx(hi - lo)


def test_reduce_on_the_trace_recorded_on_a_v5e():
    """The same three matmuls recorded on one v5e chip (PR 22): the device
    is a plane of its own there, and its operations carry whole HLO text
    as their names."""
    planes = xplane.load(os.path.join(TESTDATA,
                                      "tpu_three_matmuls.xplane.pb"))
    assert "/device:TPU:0" in [p["name"] for p in planes]
    ops = xplane.device_ops(planes)
    assert list(ops) == ["TPU:0"] and len(ops["TPU:0"]) == 9
    assert {name for _, _, name in ops["TPU:0"]} == {
        "jit__lambda/convolution_reduce_fusion", "jit__lambda/copy-start",
        "jit__lambda/copy-done"}
    r = xplane.reduce(planes, [])
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.065070088)
    # the first matmul shows ~1 ms before the annotation opened (the
    # device's clock against the host's), so two of the three are inside
    assert r["busy_s"] == pytest.approx(1.315e-6)
    assert r["device_ops"][0] == ["jit__lambda/convolution_reduce_fusion",
                                  pytest.approx(1.284e-6)]
    assert len([s for _, s in r["idle_gaps"] if s > 0.02]) == 3


# --- the generator -----------------------------------------------------------

def _config(name):
    return manifest.read_json(os.path.join(ROOT, "benchmark", "configs",
                                           f"{name}.json"))


@pytest.mark.parametrize("name", ["library-full", "psp-pods"])
def test_generator_is_a_function_of_the_seed(name):
    cfg = _config(name)
    n = 2 * cluster.SHARD
    a = cluster.Cluster(cfg["cluster"], n, 7)
    b = cluster.Cluster(cfg["cluster"], n, 7)
    c = cluster.Cluster(cfg["cluster"], n, 8)
    objs = [cluster.dumps(o) for o in a.objects(1)]
    assert len(objs) == cluster.SHARD
    assert objs == [cluster.dumps(o) for o in b.objects(1)]
    other = [cluster.dumps(o) for o in c.objects(1)]
    assert objs != other
    assert a.namespace_objects() == b.namespace_objects()
    stream = a.stream()
    assert [next(stream) for _ in range(50)] != [
        o for o, _ in zip(c.stream(), range(50))]


@pytest.mark.parametrize("name", ["library-full", "psp-pods"])
def test_the_seed_leaves_the_cluster_vocabulary_alone(name):
    """Another seed is another draw of every field, over the same names,
    kinds and images, with the same first shard: the program's compiled
    sweep programs depend on the order it first sees strings in."""
    cfg = _config(name)
    n = 2 * cluster.SHARD
    a = cluster.Cluster(cfg["cluster"], n, 1)
    b = cluster.Cluster(cfg["cluster"], n, 2)
    assert [cluster.dumps(o) for o in a.objects(0)] == \
        [cluster.dumps(o) for o in b.objects(0)]
    second_a, second_b = list(a.objects(1)), list(b.objects(1))
    assert [(o["kind"], o["metadata"]["name"]) for o in second_a] == \
        [(o["kind"], o["metadata"]["name"]) for o in second_b]
    assert second_a != second_b
    assert a._images == b._images
    # the first two Pods are the widest there can be
    pods = [o for o in a.objects(0) if o["kind"] == "Pod"][:2]
    widest = cfg["cluster"]["pod"]["containers"]["values"][-1]
    assert [len(p["spec"]["containers"]) for p in pods] == [widest, widest]
    assert "volumes" in pods[1]["spec"] and "volumes" not in pods[0]["spec"]


def test_namespaces_follow_the_configured_zipf():
    cfg = _config("library-full")
    cl = cluster.Cluster(cfg["cluster"], 1, 0)
    rng = random.Random(0)
    n = 100_000
    seen = collections.Counter(cl.namespace(rng) for _ in range(n))
    s = cfg["cluster"]["namespaces"]["zipf_s"]
    norm = sum(1 / (r + 1) ** s for r in range(40))
    for rank in (0, 1, 4, 19):
        want = (1 / (rank + 1) ** s) / norm
        assert seen[f"ns-{rank}"] / n == pytest.approx(want, rel=0.1)
    assert len(seen) == 40


def test_kind_mix_follows_the_configuration():
    cfg = _config("library-full")
    cl = cluster.Cluster(cfg["cluster"], 32768, 3)
    seen = collections.Counter(o["kind"] for o in cl.objects(0))
    assert seen["Pod"] / 32768 == pytest.approx(0.70, abs=0.01)
    assert seen["Ingress"] / 32768 == pytest.approx(0.08, abs=0.005)
    assert seen["RoleBinding"] / 32768 == pytest.approx(0.024, abs=0.004)
    assert seen["ClusterRoleBinding"] / 32768 == \
        pytest.approx(0.016, abs=0.004)


def test_psp_pods_carry_the_container_tail():
    cfg = _config("psp-pods")
    cl = cluster.Cluster(cfg["cluster"], 32768, 5)
    pods = list(cl.objects(0))
    assert {o["kind"] for o in pods} == {"Pod"}
    counts = [len(o["spec"]["containers"]) for o in pods]
    assert sum(1 for c in counts if c <= 3) / len(counts) == \
        pytest.approx(0.90, abs=0.01)
    assert stats.percentile(counts, 99) == 8
    assert max(counts) > 12 and max(counts) <= 16
    init = sum(1 for o in pods if "initContainers" in o["spec"])
    assert init / len(pods) == pytest.approx(0.15, abs=0.01)
    vols = sum(1 for o in pods if "volumes" in o["spec"])
    assert vols / len(pods) == pytest.approx(0.12, abs=0.01)


def test_admission_review_update_carries_the_old_object():
    obj = {"apiVersion": "apps/v1", "kind": "Deployment",
           "metadata": {"name": "d", "namespace": "ns-1"}, "spec": {}}
    r = cluster.admission_review(obj, "u1", "UPDATE")["request"]
    assert r["kind"] == {"group": "apps", "version": "v1",
                         "kind": "Deployment"}
    assert r["oldObject"]["metadata"]["labels"] == {"revision": "previous"}
    assert "labels" not in r["object"]["metadata"]
    c = cluster.admission_review(obj, "u2", "CREATE")["request"]
    assert "oldObject" not in c and c["namespace"] == "ns-1"


def test_storm_pool_comes_in_rollout_runs():
    cfg = _config("library-full")
    traffic = manifest.read_json(manifest.traffic_path("admit-storm"))
    cl = cluster.Cluster(cfg["cluster"], cfg["objects"], 1)
    pool = admit.make_pool(cl, traffic, 1)
    assert len(pool) == traffic["pool"]
    assert len({b["request"]["uid"] for b in pool}) == len(pool)
    names = [b["request"]["name"] for b in pool]
    rollout = [n for n in names if n.startswith("rollout-")]
    assert len(rollout) / len(pool) == pytest.approx(0.8, abs=0.05)
    runs = collections.Counter(n.rsplit("-", 1)[0] for n in rollout)
    assert all(n <= 500 for n in runs.values())
    assert pool == admit.make_pool(
        cluster.Cluster(cfg["cluster"], cfg["objects"], 1), traffic, 1)


# --- answers ------------------------------------------------------------------

def test_answer_digest_ignores_order_and_uid_but_not_content():
    a = {"response": {"uid": "u1", "allowed": False, "status": {
        "code": 403, "message": "[a] x\n[b] y"}, "warnings": ["w2", "w1"]}}
    b = {"response": {"uid": "u2", "allowed": False, "status": {
        "code": 403, "message": "[b] y\n[a] x"}, "warnings": ["w1", "w2"]}}
    c = {"response": {"uid": "u1", "allowed": False, "status": {
        "code": 403, "message": "[a] x"}}}
    assert answers.of_response(a) == answers.of_response(b)
    assert answers.of_response(a)[0] != answers.of_response(c)[0]
    shed = {"response": {"uid": "u", "allowed": False,
                         "status": {"code": 429, "message": "shed"}}}
    assert answers.of_response(shed)[1] == answers.SHED_CODE


def test_answer_digest_of_a_validation_response_matches_the_wire_form():
    V = collections.namedtuple("V", "allowed message code warnings")
    wire = {"response": {"uid": "u", "allowed": True}}
    assert answers.of_validation(V(True, "", 200, [])) == \
        answers.of_response(wire)[0]
    deny = {"response": {"uid": "u", "allowed": False, "status": {
        "code": 403, "message": "[c] no"}, "warnings": ["w"]}}
    assert answers.of_validation(V(False, "[c] no", 403, ["w"])) == \
        answers.of_response(deny)[0]


# --- readers --------------------------------------------------------------------

def _obs():
    spans = [
        {"name": "webhook.request", "span_id": "r1", "parent_id": None,
         "duration_s": 0.010, "attributes": {}},
        {"name": "webhook.review", "span_id": "v1", "parent_id": "r1",
         "duration_s": 0.008, "attributes": {}},
        {"name": "webhook.batcher.enqueue", "span_id": "e1",
         "parent_id": "v1", "duration_s": 0.007,
         "attributes": {"lane": "grid"}},
        {"name": "webhook.batcher.enqueue", "span_id": "e2",
         "parent_id": "v2", "duration_s": 0.003,
         "attributes": {"lane": "interp"}},
        {"name": "webhook.batcher.flush", "span_id": "f1", "parent_id": "e1",
         "duration_s": 0.006, "attributes": {"batch_size": 12}},
        {"name": "webhook.batcher.flush", "span_id": "f2", "parent_id": "e2",
         "duration_s": 0.002, "attributes": {"batch_size": 2}}]
    return {"perf": {"evaluator": {"flatten": 6.0, "masks": 1.0,
                                   "wire_pack": 2.0, "wire_bytes": 8000.0},
                     "manager": {"pipe_device_wait": 1.0, "pipe_wall": 4.0}},
            "passes": 2, "objects": 100, "constraints": 4, "spans": spans,
            "hist": {"webhook_batch_queue_wait_seconds":
                     {"count": 4, "sum": 0.02}},
            "counts": {"compiles_in_window": 0}, "full_gc_s": 0.7,
            "loadgen": {"late_ms_p99": 0.4},
            "trace": {"busy_s": 0.5, "window_s": 2.0, "passes": 2},
            "peaks": {"hbm_bytes_per_s": 819e9, "int8_op_per_s": 393e12}}


@pytest.mark.parametrize("spec,want", [
    ({"from": "perf", "of": "evaluator", "keys": ["flatten"],
      "per": "pass"}, 3.0),
    ({"from": "perf", "of": "evaluator", "keys": ["masks", "wire_pack"],
      "per": "pass"}, 1.5),
    ({"from": "perf", "of": "evaluator", "keys": ["wire_bytes"],
      "per": "object"}, 40.0),
    ({"from": "perf", "of": "manager", "keys": ["pipe_device_wait"],
      "over": {"of": "manager", "keys": ["pipe_wall"]}}, 0.25),
    ({"from": "perf", "of": "manager", "keys": ["absent"]}, None),
    ({"from": "spans", "name": "webhook.request", "value": "self",
      "agg": "p50", "scale": 1000}, 2.0),
    ({"from": "spans", "name": "webhook.batcher.enqueue", "agg": "share",
      "where": {"lane": "grid"}}, 0.5),
    ({"from": "spans", "name": "webhook.batcher.flush",
      "value": "attr:batch_size", "agg": "mean"}, 7.0),
    ({"from": "spans", "name": "device.query_batch", "value": "duration",
      "agg": "p50"}, None),
    ({"from": "hist", "name": "webhook_batch_queue_wait_seconds",
      "scale": 1000}, 5.0),
    ({"from": "hist", "name": "webhook_batch_size"}, None),
    ({"from": "counts", "key": "compiles_in_window"}, 0),
    ({"from": "loadgen", "key": "late_ms_p99"}, 0.4),
    ({"from": "gc", "per": "pass"}, 0.35),
    ({"from": "gc"}, 0.7),
    ({"from": "trace", "key": "busy_s", "per": "pass"}, 0.25),
    ({"from": "trace", "key": "idle_share"}, 0.75),
])
def test_reader(spec, want):
    got = readers.READERS[spec["from"]](_obs(), spec)
    assert got == (pytest.approx(want) if want is not None else None)


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    metrics = [{"name": "a", "unit": "s", "read": {
        "from": "perf", "of": "evaluator", "keys": ["flatten"]}},
        {"name": "b", "unit": "s", "read": {
            "from": "perf", "of": "evaluator", "keys": ["nowhere"]}}]
    assert readers.read_all(metrics, _obs()) == {
        "a": {"value": 6.0, "unit": "s"}}


def test_gc_reader_finds_nothing_in_a_run_that_did_not_watch():
    obs = dict(_obs(), full_gc_s=None)
    assert readers.read_gc(obs, {"from": "gc", "per": "pass"}) is None


def test_watch_gc_times_full_collections_only(tmp_path, monkeypatch):
    import gc

    from benchmark import harness

    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    cell = collections.namedtuple("Cell", "name")("a-cell")
    run = harness.Run(cell, 0, 1.0, True, True, time.monotonic())
    before = list(gc.callbacks)
    try:
        run.watch_gc()
        t0 = time.monotonic()
        gc.collect(0)
        gc.collect(1)
        assert run.full_gcs == []
        gc.collect()
        gc.collect(2)
        t1 = time.monotonic()
    finally:
        gc.callbacks[:] = before
    assert len(run.full_gcs) == 2
    assert all(t0 <= t <= t1 and 0 <= s <= t1 - t0 for t, s in run.full_gcs)
    total = run.full_gc_s_between(t0, t1 + 1)
    assert total == pytest.approx(sum(s for _, s in run.full_gcs))
    assert run.full_gc_s_between(t1 + 1, t1 + 2) == 0


def test_roofline_reader_and_arithmetic():
    obs = _obs()
    # per pass: 4000 B in, 0 out, over 819 GB/s, against 0.25 s busy
    want = 100 * (4000 / 819e9) / 0.25
    got = readers.read_python(obs, {"file": "sweep_device_roofline.py"})
    assert got == pytest.approx(want)
    share, bound = roofline.roofline_share(819e9, 1.0, 2.0, obs["peaks"])
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = roofline.roofline_share(1.0, 393e12, 4.0, obs["peaks"])
    assert (share, bound) == (pytest.approx(25.0), "compute")
    obs["peaks"] = None  # a rehearsal has no chip and so no roofline
    assert readers.read_python(
        obs, {"file": "sweep_device_roofline.py"}) is None


def test_peaks_table_raises_on_a_device_it_does_not_list():
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")


# --- the manifest -----------------------------------------------------------------

def test_manifest_self_check_passes():
    assert manifest.check() == []


def test_run_py_check_exits_zero():
    p = subprocess.run([sys.executable, os.path.join(
        ROOT, "benchmark", "run.py"), "--check"], capture_output=True,
        text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    assert "0 faults" in p.stdout


@pytest.mark.parametrize("name", [w["name"] for w in manifest.read_json(
    manifest.MANIFEST)["workloads"]])
def test_every_cell_resolves_to_its_files(name):
    cell = manifest.Cell(name)
    reported = {e["name"] for e in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert reported - {"setup_s"} <= set(cell.traffic["yields"])
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert m["read"]["from"] in readers.READERS
    assert cell.chips == 1
    for key in ("source", "assumed", "reduced", "guarantees"):
        assert key in cell.config


def test_ingress_always_has_a_host_of_its_own():
    cfg = _config("library-full")
    cl = cluster.Cluster(cfg["cluster"], 32768, 2)
    ings = [o for o in cl.objects(0) if o["kind"] == "Ingress"]
    own = [o["spec"]["rules"][0]["host"] for o in ings]
    assert len(set(own)) == len(ings) > 2000
    shared = sum(1 for o in ings if len(o["spec"]["rules"]) > 1)
    assert 0.03 < shared / len(ings) < 0.09


def test_rehearsal_sizes_replace_the_files_own_keys():
    cell = manifest.Cell.unlisted("library-full", "admit-storm",
                                  rehearse=True)
    full = manifest.Cell.unlisted("library-full", "admit-storm")
    assert cell.name == full.name == "library-full.admit-storm"
    assert cell.config["objects"] < full.config["objects"] == 262144
    assert cell.traffic["rollouts"]["run"] == [5, 50]
    assert full.traffic["rollouts"]["run"] == [50, 500]
    assert full.end_to_end == [] and full.per_layer == []
    audit_cell = manifest.Cell("psp.audit-sweep", rehearse=True)
    assert audit_cell.config["audit"]["chunk_size"] == 1024


def test_without_a_tpu_the_benchmark_prints_no_result():
    """On this CPU-only host the command must exit non-zero and print no
    result line (the only CPU mode is --rehearse)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "full.audit-sweep", "--seed", "0", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=env)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "refusing to run" in p.stderr


# --- the audit cells' comparison with the interpreter --------------------------

A, B, C = ("Pod", "ns-0", "a"), ("Pod", "ns-1", "b"), ("Pod", "ns-0", "c")


@pytest.mark.parametrize("got,want,agrees", [
    # every result of every violating object, in listing order
    ([(A, "x"), (B, "y")], [(A, ["x"]), (B, ["y"])], True),
    # the order of one object's results is not part of the answer
    ([(A, "x2"), (A, "x1"), (B, "y")], [(A, ["x1", "x2"]), (B, ["y"])], True),
    # ... the order of objects is
    ([(B, "y"), (A, "x")], [(A, ["x"]), (B, ["y"])], False),
    ([(A, "x")], [(A, ["x"]), (B, ["y"])], False),            # one is missing
    ([(A, "x"), (B, "y")], [(A, ["x"])], False),             # one too many
    ([(A, "x"), (B, "other")], [(A, ["x"]), (B, ["y"])], False),
    ([], [], True),
    # the limit (3) cuts through B: any one of B's own results may be kept
    ([(A, "x1"), (A, "x2"), (B, "y2")],
     [(A, ["x1", "x2"]), (B, ["y1", "y2"]), (C, ["z"])], True),
    ([(A, "x1"), (A, "x2"), (C, "z")],
     [(A, ["x1", "x2"]), (B, ["y1", "y2"]), (C, ["z"])], False),
    # short of the limit nothing may be cut
    ([(A, "x1"), (B, "y1")], [(A, ["x1"]), (B, ["y1", "y2"])], False),
    # the limit reached exactly at an object's end: the next is not kept
    ([(A, "x1"), (A, "x2"), (B, "y")],
     [(A, ["x1", "x2"]), (B, ["y"]), (C, ["z"])], True),
])
def test_kept_violations_against_the_interpreters(got, want, agrees):
    assert audit.kept_agrees(got, want, 3) is agrees


def test_sample_corpus_fills_each_groups_first_chunk(tmp_path):
    """Each kind group's sample is listed over and over to as many rows as
    that group's first real chunk has, so the audit sweeps it on the
    programs of the measured passes."""
    pods, svcs = frozenset({"P"}), frozenset({"S"})
    groups = {pods: [(10, b"p10"), (11, b"p11"), (12, b"p12")],
              svcs: [(20, b"s20"), (21, b"s21")]}
    path = tmp_path / "sample.corpus.jsonl"
    order = audit.write_sample_corpus(groups, {pods: 100, svcs: 5}, 8,
                                      str(path))
    assert order == [10, 11, 12, 10, 11, 12, 10, 11, 20, 21, 20, 21, 20]
    assert path.read_bytes().split(b"\n")[:-1] == [
        b"p10", b"p11", b"p12", b"p10", b"p11", b"p12", b"p10", b"p11",
        b"s20", b"s21", b"s20", b"s21", b"s20"]


def _audited(totals, kept, n, incomplete=False):
    V = collections.namedtuple("V", "kind namespace name message")
    Run = collections.namedtuple(
        "Run", "total_violations kept total_objects incomplete")
    return Run(totals, {k: [V(*obj, msg) for obj, msg in vs]
                        for k, vs in kept.items()}, n, incomplete)


def test_sample_audit_is_held_to_the_interpreters_totals_and_kept():
    k1, k2 = ("K8sA", "a"), ("K8sB", "b")
    ident = {0: A, 1: B, 2: C}
    results = {0: {k1: ["x"]}, 2: {k1: ["z1", "z2"], k2: ["w"]}}
    order = [0, 1, 2, 0, 1, 2]  # the sample listed twice
    good = _audited({k1: 4, k2: 2},
                    {k1: [(A, "x"), (C, "z2"), (C, "z1")],
                     k2: [(C, "w"), (C, "w")]}, 6)
    assert audit.sample_audit_problems(good, order, results, ident, 3) == []
    miscounted = good._replace(total_violations={k1: 3, k2: 2})
    assert len(audit.sample_audit_problems(
        miscounted, order, results, ident, 3)) == 1
    dropped_tail = _audited({k1: 4, k2: 2},
                            {k1: [(A, "x"), (C, "z2")],
                             k2: [(C, "w"), (C, "w")]}, 6)
    assert len(audit.sample_audit_problems(
        dropped_tail, order, results, ident, 3)) == 1
    short = good._replace(total_objects=5)
    assert len(audit.sample_audit_problems(
        short, order, results, ident, 3)) == 1
    unknown = _audited({k1: 4}, {k1: [(A, "x"), (C, "z2"), (C, "z1")]}, 6)
    assert len(audit.sample_audit_problems(
        unknown, order, results, ident, 3)) == 1


# --- what counts as a failed admission request ---------------------------------

def test_a_request_is_scored_from_the_time_it_was_due():
    """Window [10, 20), timeout 3 s.  Rows: body, due, sent, done, HTTP
    status, digest, code."""
    served = admit.Served.__new__(admit.Served)
    served.want = ["d0", "d1"]
    rows = [
        [0, 10.0, 10.0, 10.1, 200, "d0", None],    # right, 100 ms
        [1, 11.0, 11.5, 11.7, 200, "d1", None],    # right, left 500 ms late
        [0, 12.0, 12.0, 12.2, 200, "other", 403],  # answered otherwise
        [1, 13.0, 13.0, 13.1, 200, None, 429],     # shed
        [0, 14.0, 14.0, None, None, None, None],   # never answered
        [1, 15.0, 15.0, 15.4, 500, None, None],    # an HTTP error
        [0, 9.0, 9.0, 10.5, 200, "d0", None],      # due before the window
        [1, 19.9, 19.9, 20.3, 200, "d1", None],    # due inside, done after
    ]
    d = {"plan": {"loop": "open", "timeout_s": 3.0}, "w0": 10.0, "w1": 20.0,
         "rows": rows, "connections_peak": 3}
    sc = served.score(d)
    assert sc["requests"] == 7 and sc["ok"] == 3
    assert (sc["mismatched"], sc["shed"], sc["unanswered"]) == (1, 1, 2)
    # completed inside the window and right: rows 0, 1 and the one due at 9
    assert sc["reviews_per_s"] == pytest.approx(3 / 10)
    assert sorted(sc["lat_ms"]) == pytest.approx(
        [100, 100, 200, 400, 700, 3000, 3000])
    assert sc["late_ms_p99"] == pytest.approx(500)
    # a closed loop has no due time: a request belongs where it ended
    d["plan"]["loop"] = "closed"
    assert served.score(d)["requests"] == 7


# --- cells that are planned: new files and new entries only -------------------

PLANNED = {
    "workloads": [
        {"name": "full.admit-steady", "config": "library-full",
         "traffic": "admit-steady", "chips": 1, "why": "PERF.md section 7"},
        {"name": "full.admit-storm", "config": "library-full",
         "traffic": "admit-storm", "chips": 1, "why": "PERF.md section 7"}],
    "end_to_end": [
        {"name": "admit_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["full.admit-steady"]},
        {"name": "admit_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["full.admit-steady"]},
        {"name": "admit_reviews_per_s", "unit": "1/s", "better": "higher",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["full.admit-storm"]}],
    "per_layer": [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": name.split(".")[0], "moves": moves, "workloads": [cell]}
        for name, unit, better, source, moves, cell in [
            ("http.self_ms_p50", "ms", "lower", "program_span",
             "admit_p50_ms", "full.admit-steady"),
            ("batcher.enqueue_to_answer_ms_p50", "ms", "lower",
             "program_span", "admit_p50_ms", "full.admit-steady"),
            ("batcher.queue_wait_ms_mean", "ms", "lower", "program_counter",
             "admit_p99_ms", "full.admit-steady"),
            ("loadgen.late_ms_p99", "ms", "lower", "host_clock",
             "admit_p99_ms", "full.admit-steady"),
            ("batcher.grid_share", "1", "higher", "program_span",
             "admit_reviews_per_s", "full.admit-storm"),
            ("batcher.batch_size_mean", "1", "higher", "program_span",
             "admit_reviews_per_s", "full.admit-storm"),
            ("admit_device.flush_ms_p50", "ms", "lower", "program_span",
             "admit_reviews_per_s", "full.admit-storm"),
            ("loadgen.p99_ms", "ms", "lower", "host_clock",
             "admit_reviews_per_s", "full.admit-storm"),
            ("device.idle_share_admit", "1", "lower", "device_trace",
             "admit_reviews_per_s", "full.admit-storm")]],
}


@pytest.fixture
def planned_manifest(tmp_path, monkeypatch):
    """BENCHMARK.json with the admission cells PERF.md section 7 plans
    appended, as the PR that lands them will append them: entries only,
    over files that are there.  (The bounds are placeholders.)"""
    m = manifest.read_json(manifest.MANIFEST)
    for section, entries in PLANNED.items():
        m[section] = m[section] + entries
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    monkeypatch.setattr(manifest, "MANIFEST", str(path))
    return m


def test_the_planned_admission_cells_need_entries_only(planned_manifest):
    assert manifest.check() == []
    steady = manifest.Cell("full.admit-steady")
    assert {e["name"] for e in steady.end_to_end} == {
        "admit_p50_ms", "admit_p99_ms", "setup_s"}
    assert "loadgen.late_ms_p99" in {p["name"] for p in steady.per_layer}
    assert steady.traffic["loop"] == "open"
    assert steady.traffic["rate_per_s"] is None  # the knee is not found yet
    storm = manifest.Cell("full.admit-storm", rehearse=True)
    assert storm.traffic["loop"] == "closed"
    assert {"entry.compiles_in_window", "batcher.grid_share"} <= {
        p["name"] for p in storm.per_layer}


def test_every_layer_metric_file_is_listed_or_planned(planned_manifest):
    listed = {p["name"] for p in planned_manifest["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.dirname(
        manifest.metric_path("x"))) if f.endswith(".json")}
    assert files == listed


@pytest.mark.slow
@pytest.mark.parametrize("cell,trace", [("full.admit-steady", 0),
                                        ("full.admit-storm", 1)])
def test_rehearse_a_planned_admission_cell(planned_manifest, capsys, cell,
                                           trace):
    """The whole admission path at toy sizes on whatever JAX finds: pool,
    reference children, served webhook, generator child, scoring,
    readers.  Half a minute each."""
    from benchmark import run as run_py

    assert run_py.main(["--workload", cell, "--rehearse", "--seed", "4",
                        "--seconds", "6", "--trace", str(trace)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    assert "rehearsal" in line
    if trace:
        assert "batcher.grid_share" in line["metrics"]
        # 0 where every flush went to the interpreter lane: the storm has
        # two regimes (PERF.md section 6)
        assert line["device"]["busy_s"] >= 0 < line["device"]["window_s"]
    else:
        assert set(line["metrics"]) == {e["name"] for e in manifest.Cell(
            cell).end_to_end}


# --- the load generator, against a stub ---------------------------------------------

class _Stub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    service_s = 0.05

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        time.sleep(self.service_s)
        data = json.dumps({"response": {
            "uid": body["request"]["uid"], "allowed": True}}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


def _generate(tmp_path, n_bodies, service_s=0.05, **plan):
    """Run loadgen.py on ``plan`` against the stub; its output."""
    stub = type("Stub", (_Stub,), {"service_s": service_s})
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), stub)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        bodies = tmp_path / "bodies.jsonl"
        bodies.write_bytes(b"".join(
            json.dumps({"request": {"uid": f"u{i}"}}).encode() + b"\n"
            for i in range(n_bodies)))
        out = tmp_path / "out.json"
        plan = dict({"timeout_s": 3.0, "warmup_s": 0.0, "seconds": 1.0,
                     "port": srv.server_address[1], "bodies": str(bodies),
                     "output": str(out), "start": time.monotonic() + 1.5},
                    **plan)
        plan_path = tmp_path / "plan.json"
        plan_path.write_text(json.dumps(plan))
        p = subprocess.run([sys.executable, os.path.join(
            ROOT, "benchmark", "loadgen.py"), str(plan_path)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr
    finally:
        srv.shutdown()
        srv.server_close()
    return json.loads(out.read_text())


def test_open_loop_never_holds_a_departure_back(tmp_path):
    """Four requests due at one instant, ONE connection kept open, a server
    that takes 50 ms each: the generator opens three more connections
    rather than queue behind the one, so all four leave on time and are
    answered ~50 ms after they were due."""
    got = _generate(tmp_path, 4, loop="open", connections=1,
                    schedule=[[0.0, i] for i in range(4)])
    rows = got["rows"]
    assert got["connections_peak"] == 4
    assert [r[0] for r in rows] == [0, 1, 2, 3]
    want = answers.of_response({"response": {"uid": "x", "allowed": True}})
    assert all(r[4] == 200 and r[5] == want[0] for r in rows)
    due = [r[1] for r in rows]
    assert max(due) - min(due) < 1e-9
    lat = stats.open_loop_latencies(due, [r[3] for r in rows])
    late = stats.lateness(due, [r[2] for r in rows])
    assert all(0.045 <= x < 0.3 for x in lat)
    assert all(0.0 <= x < 0.05 for x in late)


def test_open_loop_reuses_a_connection_that_is_idle(tmp_path):
    got = _generate(tmp_path, 3, loop="open", connections=1,
                    schedule=[[0.3 * i, i] for i in range(3)])
    assert got["connections_peak"] == 1
    assert all(r[4] == 200 for r in got["rows"])


def test_a_request_fails_at_the_timeout_counted_from_its_due_time(tmp_path):
    """The server takes 0.5 s, the webhook's timeout is 0.2 s: every request
    is abandoned 0.2 s after it was due and reported unanswered."""
    t0 = time.monotonic()
    got = _generate(tmp_path, 2, service_s=0.5, loop="open", connections=2,
                    timeout_s=0.2, schedule=[[0.0, 0], [0.1, 1]])
    assert time.monotonic() - t0 < 10
    assert [r[3:] for r in got["rows"]] == [[None] * 4] * 2


def test_closed_loop_sends_the_next_request_on_the_reply(tmp_path):
    """Two connections, 50 ms of service, 0.6 s: about 12 requests each,
    never more than two in flight, latency from the send."""
    got = _generate(tmp_path, 8, loop="closed", connections=2,
                    seconds=0.6, sequence=list(range(8)))
    rows = sorted(got["rows"], key=lambda r: r[2])
    assert 12 <= len(rows) <= 26
    assert [r[0] for r in rows[:8]] == list(range(8))
    assert all(r[1] == r[2] and r[4] == 200 for r in rows)
    for i, r in enumerate(rows):  # at most two in flight at any send
        assert sum(1 for q in rows[:i] if q[3] > r[2]) <= 1

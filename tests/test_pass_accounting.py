"""The audit pass accounts for itself from inside the program: the lister
is a timed, spanned layer, every pipeline stage says what it waited for
and what CPU it burnt, a dispatch has parts, full garbage collections are
on the tracer's timeline, and a span lives on one clock."""

import gc
import threading
import time

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.audit.render_memo import RenderMemo
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.metrics import registry as M
from gatekeeper_tpu.observability import tracing
from gatekeeper_tpu.ops import native
from gatekeeper_tpu.parallel import sharded
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.pipeline import PipelineError, Stage, StagedPipeline
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.unstructured import load_yaml_file

LIB = "/root/repo/library/general"


def _sleepy(seconds):
    return lambda x: (time.sleep(seconds), x)[1]


def _spin(seconds):
    def fn(x):
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            pass
        return x
    return fn


def _slow_source(n, seconds):
    for i in range(n):
        time.sleep(seconds)
        yield i


# --- the executor's account ------------------------------------------------

def test_slow_source_shows_in_source_busy():
    run = StagedPipeline([Stage("sink", lambda x: None)]).run(
        _slow_source(10, 0.01))
    assert run.source_items == 10
    assert run.source_busy_s >= 0.1
    assert run.source_busy_s > 0.8 * run.wall_s
    # asleep in the lister is busy, not CPU
    assert run.source_cpu_s < 0.5 * run.source_busy_s


def test_slow_last_stage_shows_in_drain():
    # an unbounded-enough queue: the source is done at once, and the pass
    # then waits for the sink to work through what is queued
    run = StagedPipeline([
        Stage("sink", lambda x: (time.sleep(0.01), None)[1], queue_cap=64),
    ]).run(range(10))
    assert run.drain_s >= 0.08
    assert run.drain_s > 0.8 * run.wall_s
    assert run.source_busy_s < 0.2 * run.wall_s


@pytest.mark.parametrize("source,stages", [
    (lambda: range(50), lambda: [Stage("sink", lambda x: None)]),
    (lambda: _slow_source(8, 0.005), lambda: [Stage("sink", lambda x: None)]),
    (lambda: range(8), lambda: [
        Stage("slow", _sleepy(0.005), queue_cap=1),
        Stage("sink", lambda x: None, queue_cap=1)]),
    (lambda: range(0), lambda: [Stage("sink", lambda x: None)]),
], ids=["fast", "slow-source", "backpressure", "empty"])
def test_calling_threads_account_closes(source, stages):
    run = StagedPipeline(stages(), source_cap=1).run(source())
    assert run.source_busy_s + run.source_stall_s + run.drain_s == \
        pytest.approx(run.wall_s, abs=1e-9)
    assert min(run.source_busy_s, run.source_stall_s, run.drain_s) >= 0.0


def test_account_closes_after_a_failed_source():
    def src():
        yield 1
        time.sleep(0.01)
        raise RuntimeError("lister died")

    # run() raises, so the account is read off the spans instead: the
    # failed next() is a pipeline.source span with the error on it
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer), tracing.span("root"):
        with pytest.raises(PipelineError):
            StagedPipeline([Stage("s", lambda x: None)]).run(src())
    spans = [s for s in tracer.traces()[0]["spans"]
             if s["name"] == "pipeline.source"]
    assert [s["attributes"]["chunk"] for s in spans] == [0, 1]
    assert spans[1]["status"] == "error" and spans[1]["duration_s"] >= 0.01


def test_stage_cpu_is_at_most_busy_and_near_zero_asleep():
    run = StagedPipeline([
        Stage("spin", _spin(0.01), queue_cap=2),
        Stage("sleep", _sleepy(0.01), workers=2, queue_cap=2),
        Stage("sink", lambda x: None),
    ]).run(range(12))
    spin, sleep = run.stage("spin"), run.stage("sleep")
    for st in run.stages:
        # thread_time and perf_counter tick apart by microseconds an item
        assert st.cpu_s <= st.busy_s + 1e-3 * st.items, st
    assert spin.busy_s >= 0.12 and sleep.busy_s >= 0.12
    assert sleep.cpu_s < 0.25 * sleep.busy_s  # asleep burns no CPU
    # a spinning thread runs whenever it holds the GIL; what it lacks of
    # its busy time it spent waiting for the interpreter
    assert spin.cpu_s > sleep.cpu_s


def test_summary_carries_the_account():
    run = StagedPipeline([Stage("a", _sleepy(0.001)),
                          Stage("sink", lambda x: None)]).run(range(5))
    doc = run.summary()
    for key in ("source_busy_s", "source_cpu_s", "source_stall_s",
                "drain_s", "wall_s"):
        assert key in doc, key
    assert set(doc["stages"]) == {"a", "sink"}
    for st in doc["stages"].values():
        assert {"busy_s", "cpu_s", "wait_s", "stall_s"} <= set(st)


def test_source_spans_sit_under_the_ambient_span():
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer), tracing.span("root") as root:
        StagedPipeline([Stage("sink", lambda x: None)]).run(range(3))
    spans = tracer.traces()[0]["spans"]
    src = [s for s in spans if s["name"] == "pipeline.source"]
    # one per next(): three items and the call that found the end
    assert [s["attributes"]["chunk"] for s in src] == [0, 1, 2, 3]
    assert {s["parent_id"] for s in src} == {root.span_id}
    assert {s["thread_id"] for s in src} == {threading.get_ident()}


# --- the audit pass's account ----------------------------------------------

def _objects(n):
    return [{"apiVersion": "v1", "kind": "Namespace",
             "metadata": {"name": f"ns-{i}",
                          "labels": {"gatekeeper": "x"} if i % 3 else {}}}
            for i in range(n)]


@pytest.fixture(scope="module")
def toy():
    tpu = TpuDriver()
    client = Client(target=K8sValidationTarget(), drivers=[tpu],
                    enforcement_points=[AUDIT_EP])
    client.add_template(load_yaml_file(
        f"{LIB}/requiredlabels/template.yaml")[0])
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels",
        "metadata": {"name": "ns-must-have-gk"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Namespace"]}]},
                 "parameters": {"labels": [{"key": "gatekeeper"}]}},
    })
    evaluator = ShardedEvaluator(tpu, make_mesh(), violations_limit=5)
    return client, evaluator


def _toy_mgr(toy, pipeline, metrics=None):
    client, evaluator = toy
    objects = _objects(40)
    return AuditManager(
        client, lister=lambda: iter(objects),
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline=pipeline),
        evaluator=evaluator, metrics=metrics)


def test_perf_keys_after_a_pipelined_pass(toy):
    metrics = M.MetricsRegistry()
    mgr = _toy_mgr(toy, "on", metrics)
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        run = mgr.audit()
    perf = mgr.perf
    assert perf["pipelined"] == 1.0 and run.total_objects == 40
    for key in ("list", "list_cpu", "pipe_source_stall", "pipe_drain",
                "pipe_wall", "pipe_device_wait", "report", "render",
                "fold_render", "n_renders"):
        assert key in perf, key
    for stage in ("flatten", "dispatch", "collect", "fold_render"):
        for what in ("busy", "wait", "stall", "cpu", "workers"):
            assert f"pipe_{stage}_{what}" in perf, (stage, what)
        assert perf[f"pipe_{stage}_workers"] >= 1.0
    # the calling thread's account closes on the pipeline's wall
    assert perf["list"] + perf["pipe_source_stall"] + perf["pipe_drain"] \
        == pytest.approx(perf["pipe_wall"], abs=1e-6)
    assert perf["pipe_device_wait"] == perf["pipe_collect_busy"]
    assert 0.0 < perf["render"] <= perf["fold_render"]
    assert perf["n_renders"] > 0
    # what misled is gone; what it was computed from stands under its name
    assert "device_idle_fraction" not in mgr.pipe_stats
    assert mgr.pipe_stats["device_wait_s"] >= 0.0
    assert metrics.get_gauge(M.PIPELINE_DEVICE_WAIT) is not None
    assert not hasattr(M, "PIPELINE_DEVICE_IDLE")
    # and the pass's parts are on the timeline
    spans = tracer.traces()[0]["spans"]
    root = next(s for s in spans if s["name"] == "audit.sweep")
    assert "device_idle_fraction" not in root["attributes"]
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert len(by_name["pipeline.source"]) == 4  # 3 chunks + the end
    assert [s["parent_id"] for s in by_name["audit.report"]] == \
        [root["span_id"]]
    dispatches = {s["span_id"] for s in by_name["device.sweep_dispatch"]}
    for part in ("masks", "pack", "launch"):
        parts = by_name[f"device.sweep_dispatch.{part}"]
        assert len(parts) == len(dispatches) == 3, part
        assert {s["parent_id"] for s in parts} == dispatches
    # a second pass adds to the account; workers is a reading, not a sum
    wall1, workers = perf["pipe_wall"], perf["pipe_flatten_workers"]
    mgr.audit()
    assert mgr.perf["pipe_wall"] > wall1
    assert mgr.perf["pipe_flatten_workers"] == workers


def test_perf_keys_after_a_serial_pass(toy):
    mgr = _toy_mgr(toy, "off")
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        run = mgr.audit()
    assert mgr.perf["pipelined"] == 0.0 and run.total_objects == 40
    assert mgr.pipe_stats is None
    for key in ("list", "report", "render", "fold_render"):
        assert mgr.perf[key] > 0.0, key
    assert not any(k.startswith("pipe_") for k in mgr.perf)
    spans = tracer.traces()[0]["spans"]
    listed = [s for s in spans if s["name"] == "audit.chunk.list"]
    assert [s["attributes"]["chunk"] for s in listed] == [0, 1, 2, 3]
    assert sum(s["duration_s"] for s in listed) <= mgr.perf["list"]
    assert sum(s["name"] == "audit.report" for s in spans) == 1


def test_dispatch_parts_keep_their_perf_keys(toy):
    _client, evaluator = toy
    evaluator.perf_reset()
    _toy_mgr(toy, "off").audit()
    for key in ("flatten", "masks", "wire_pack", "wire_bytes", "dispatch",
                "collect", "d2h_bytes"):
        assert evaluator.perf.get(key, 0.0) > 0.0, key


# --- the wire pack's two paths ----------------------------------------------

def _kept(run):
    return (dict(run.total_violations),
            {key: sorted((v.message, v.kind, v.namespace, v.name)
                         for v in vs) for key, vs in run.kept.items()})


def _pack_pass(toy, evaluator, monkeypatch):
    """One serial pass over the toy cluster: (what it kept, the evaluator's
    counters, the columns its layouts shipped, its pack spans)."""
    client, _ = toy
    layouts = []
    real = sharded.pack_transfer_cols

    def recording(*args, **kw):
        out = real(*args, **kw)
        layouts.append(out[1])
        return out

    monkeypatch.setattr(sharded, "pack_transfer_cols", recording)
    evaluator.perf_reset()
    objects = _objects(40)
    mgr = AuditManager(
        client, lister=lambda: iter(objects),
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline="off"),
        evaluator=evaluator)
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        run = mgr.audit()
    monkeypatch.setattr(sharded, "pack_transfer_cols", real)
    shipped = [sum(e[2] not in ("alias", "const") for e in layout)
               for layout in layouts]
    spans = [s["attributes"] for t in tracer.traces() for s in t["spans"]
             if s["name"] == "device.sweep_dispatch.pack"]
    return _kept(run), dict(evaluator.perf), shipped, spans


def test_wire_cols_are_counted_by_the_path_that_packed_them(
        toy, monkeypatch):
    client, plain = toy
    # no corpus stats: no plan, every column by the numpy form, and both
    # counters written all the same
    kept0, perf, shipped, spans = _pack_pass(toy, plain, monkeypatch)
    assert len(shipped) == 3 and all(shipped)
    assert perf["wire_cols_fused"] == 0
    assert perf["wire_cols_numpy"] == sum(shipped)
    assert [(a["wire_cols_fused"], a["wire_cols_numpy"]) for a in spans] \
        == [(0, n) for n in shipped]

    warmed = ShardedEvaluator(plain.driver, make_mesh(), violations_limit=5)
    warmed.warm_pass(client.constraints(), _objects(40), 16)
    assert warmed._col_stats
    kept1, perf, shipped, spans = _pack_pass(toy, warmed, monkeypatch)
    have = native.load_wirepack() is not None
    fused = sum(shipped) if have else 0
    assert len(shipped) == 3 and all(shipped)
    assert perf["wire_cols_fused"] == fused
    assert perf["wire_cols_numpy"] == sum(shipped) - fused
    assert [a["wire_cols_fused"] + a["wire_cols_numpy"] for a in spans] \
        == shipped

    # the module unloaded: share 0, the same columns, the same verdicts
    monkeypatch.setattr(native, "load_wirepack", lambda: None)
    kept2, perf, shipped2, spans = _pack_pass(toy, warmed, monkeypatch)
    assert shipped2 == shipped
    assert perf["wire_cols_fused"] == 0
    assert perf["wire_cols_numpy"] == sum(shipped)
    assert [(a["wire_cols_fused"], a["wire_cols_numpy"]) for a in spans] \
        == [(0, n) for n in shipped]
    assert kept0 == kept1 == kept2 and sum(kept0[0].values()) > 0


# --- full collections on the program's timeline -----------------------------

def test_full_collection_is_a_span_under_the_ambient_span():
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        with tracing.span("root"):
            with tracing.span("child") as child:
                gc.collect()
            gc.collect(0)  # a young collection: no span
    spans = tracer.traces()[0]["spans"]
    full = [s for s in spans if s["name"] == "runtime.gc.full"]
    assert len(full) == 1
    assert full[0]["parent_id"] == child.span_id
    assert full[0]["trace_id"] == child.trace_id
    assert full[0]["thread_id"] == threading.get_ident()
    assert set(full[0]["attributes"]) == {"collected", "uncollectable"}
    assert full[0]["duration_s"] > 0.0
    assert tracer.snapshot()["gc_full_unparented"] == 0


def test_full_collection_with_no_ambient_span_is_counted_not_traced():
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        gc.collect()
        gc.collect()
        with tracing.span("later"):
            pass
    snap = tracer.snapshot()
    assert snap["gc_full_unparented"] == 2
    assert snap["gc_full_unparented_s"] > 0.0
    assert [tr["root"] for tr in snap["traces"]] == ["later"]
    assert [s["name"] for s in snap["traces"][0]["spans"]] == ["later"]


def test_gc_hook_lives_only_while_a_tracer_does():
    def hooked():
        return tracing._gc_hook in gc.callbacks

    assert not hooked()
    tracer = tracing.Tracer(seed=0)
    tracing.install(tracer)
    try:
        assert hooked()
        with tracing.activate(tracing.Tracer(seed=1), process=False):
            assert hooked()
        assert hooked()  # the installed tracer still wants it
    finally:
        tracing.uninstall()
    assert not hooked()
    with tracing.activate(tracer):
        with tracing.activate(tracing.Tracer(seed=2)):
            assert gc.callbacks.count(tracing._gc_hook) == 1
        assert hooked()
    assert not hooked()


def test_collection_inside_the_tracers_lock_does_not_deadlock():
    """A collection can start on an allocation inside ``start_span`` or
    ``end_span``, where the thread holds ``Tracer._lock``.  The hook must
    not want that lock."""
    tracer = tracing.Tracer(seed=0)
    done = []

    def body():
        with tracing.activate(tracer, process=False):
            with tracing.span("root"):
                with tracer._lock:
                    gc.collect()
            done.append(True)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout=20)
    assert done, "the gc hook took Tracer._lock while the thread held it"
    names = [s["name"] for s in tracer.traces()[0]["spans"]]
    assert names == ["runtime.gc.full", "root"]


def test_collections_under_allocation_pressure_on_many_threads():
    """The same with real collections: thresholds of 1 start collections
    on allocations everywhere, end_span's included, on more threads than
    cores."""
    tracer = tracing.Tracer(seed=0, ring_capacity=4096)
    old = gc.get_threshold()
    errors = []

    def body():
        try:
            for _ in range(200):
                with tracing.span("root"):
                    with tracing.span("child", junk=[[] for _ in range(8)]):
                        pass
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=body, daemon=True)
               for _ in range(8)]
    with tracing.activate(tracer):
        gc.set_threshold(1, 1, 1)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            gc.set_threshold(*old)
    assert not any(t.is_alive() for t in threads), "deadlocked"
    assert not errors
    traces = tracer.traces()
    assert len(traces) == 8 * 200
    for tr in traces:
        ids = {s["span_id"] for s in tr["spans"]}
        for s in tr["spans"]:
            assert s["name"] in ("root", "child", "runtime.gc.full")
            # every collection was filed under a span of its own trace
            assert s["parent_id"] is None or s["parent_id"] in ids


def test_a_collection_does_not_shift_the_seeded_id_sequence():
    def ids(collect):
        tracer = tracing.Tracer(seed=3)
        with tracing.activate(tracer):
            with tracing.span("a"):
                if collect:
                    gc.collect()
                with tracing.span("b"):
                    pass
        return [(s["name"], s["span_id"])
                for s in tracer.traces()[0]["spans"]
                if s["name"] != "runtime.gc.full"]

    assert ids(True) == ids(False)


# --- one clock per span ----------------------------------------------------

def test_child_never_ends_past_its_parent():
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        for _ in range(200):
            with tracing.span("root"):
                with tracing.span("child"):
                    with tracing.span("leaf"):
                        pass
        with tracing.span("root"):
            gc.collect()
    for tr in tracer.traces():
        by_id = {s["span_id"]: s for s in tr["spans"]}
        for s in tr["spans"]:
            parent = by_id.get(s["parent_id"])
            if parent is None:
                continue
            # float seconds near 1.8e9 resolve to ~2.4e-7
            assert s["start_ts"] >= parent["start_ts"] - 1e-6
            assert s["start_ts"] + s["duration_s"] <= \
                parent["start_ts"] + parent["duration_s"] + 1e-6


def test_timestamps_ignore_a_wall_clock_that_steps():
    mono, wall = [100.0], [5000.0]
    tracer = tracing.Tracer(seed=0, clock=lambda: mono[0],
                            wall=lambda: wall[0])
    with tracing.activate(tracer):
        with tracing.span("root") as root:
            mono[0] += 1.0
            wall[0] -= 3600.0  # the wall clock is set back an hour
            with tracing.span("child"):
                mono[0] += 2.0
                root.add_event("tick")
            mono[0] += 0.5
    spans = {s["name"]: s for s in tracer.traces()[0]["spans"]}
    assert spans["root"]["start_ts"] == 5000.0
    assert spans["root"]["duration_s"] == 3.5
    assert spans["child"]["start_ts"] == 5001.0
    assert spans["child"]["duration_s"] == 2.0
    assert spans["root"]["events"][0]["ts"] == 5003.0


# --- the lister's routing in the account (native/listroutemod.c) ------------

@pytest.mark.parametrize("pipeline", ["on", "off"])
@pytest.mark.parametrize("raw", [True, False], ids=["rawjson", "dicts"])
def test_account_closes_and_counts_every_listed_object(toy, pipeline, raw):
    """With the native routing call on the calling thread the account
    still closes on the wall, and ``list_fast + list_slow`` is the
    listed objects of the pass: head-form RawJSON all fast, plain dicts
    all through ``peek_kind``; ``list_untracked`` is those the native call
    took off the cyclic collector's lists, a 0 where it took none."""
    from gatekeeper_tpu.ops import native
    from gatekeeper_tpu.utils.rawjson import as_raw

    client, evaluator = toy
    objects = _objects(40)
    lister = (lambda: (as_raw(o) for o in objects)) if raw \
        else (lambda: iter(objects))
    mgr = AuditManager(
        client, lister=lister,
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline=pipeline),
        evaluator=evaluator)
    mgr.audit()  # whatever compiles, compiles here
    mgr.perf = {}
    # the measured pass is one that renders, as the first after a boot
    mgr._render_memo = RenderMemo()
    tracer = tracing.Tracer(seed=0)
    t0 = time.perf_counter()
    with tracing.activate(tracer):
        run = mgr.audit()
    wall = time.perf_counter() - t0
    perf = mgr.perf
    assert run.total_objects == 40
    assert perf["n_renders"] > 0 and perf["render_memo_hits"] == 0
    assert perf["list_fast"] + perf["list_slow"] == 40
    fast = 40 if raw and native.load_listroute() is not None else 0
    assert (perf["list_fast"], perf["list_slow"]) == (fast, 40 - fast)
    assert perf["list_untracked"] == fast
    spans = tracer.traces()[0]["spans"]
    if pipeline == "on":
        account = (perf["list"] + perf["pipe_source_stall"]
                   + perf["pipe_drain"] + perf["report"])
        assert perf["list"] + perf["pipe_source_stall"] \
            + perf["pipe_drain"] == pytest.approx(perf["pipe_wall"],
                                                   abs=1e-6)
        # what is left is _audit_impl's preamble and the thread starts
        assert 0.8 * wall < account <= wall
        listed = [s for s in spans if s["name"] == "pipeline.source"]
    else:
        listed = [s for s in spans if s["name"] == "audit.chunk.list"]
    # the counts ride the listing's span, cumulative over the pass
    assert [s["attributes"].get("list_fast", 0)
            + s["attributes"].get("list_slow", 0)
            for s in listed[:3]] == [16, 32, 40]
    assert [s["attributes"]["list_untracked"] for s in listed[:3]] == \
        [16 * fast // 40, 32 * fast // 40, fast]
    # a second pass adds to all three
    mgr.audit()
    assert mgr.perf["list_fast"] + mgr.perf["list_slow"] == 80
    assert mgr.perf["list_untracked"] == 2 * fast


# --- the renderer's counters in the account (audit/render_memo.py) ----------

@pytest.mark.parametrize("pipeline", ["on", "off"])
@pytest.mark.parametrize("raw", [True, False], ids=["rawjson", "dicts"])
def test_renderer_counters_are_written_on_every_pass(toy, pipeline, raw):
    """``n_renders``, ``render``, ``render_memo_hits`` and
    ``render_memo_bypass`` are in ``perf`` after every pass, a 0 too, and
    hits plus interpreter renders (of which the bypasses are a part) is
    the renders the fold asked for: unloaded RawJSON all hit on the second
    pass, plain dicts bypass the memo on every pass."""
    from gatekeeper_tpu.utils.rawjson import as_raw

    client, evaluator = toy
    objects = _objects(40)
    lister = (lambda: (as_raw(o) for o in objects)) if raw \
        else (lambda: iter(objects))
    mgr = AuditManager(
        client, lister=lister,
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline=pipeline),
        evaluator=evaluator)
    asked = []
    render_fn = mgr._render_fn

    def counting(*args, **kw):
        render = render_fn(*args, **kw)

        def counted(con, obj, cache_key=None):
            asked.append(cache_key)
            return render(con, obj, cache_key)
        return counted

    mgr._render_fn = counting
    keys = ("render_memo_hits", "n_renders", "render_memo_bypass", "render")
    first = mgr.audit()
    n = len(asked)
    assert n > 0 and all(k in mgr.perf for k in keys)
    assert mgr.perf["render_memo_hits"] == 0
    assert mgr.perf["n_renders"] == n
    assert mgr.perf["render_memo_bypass"] == (0 if raw else n)
    mgr.perf = {}
    second = mgr.audit()
    assert len(asked) == 2 * n and all(k in mgr.perf for k in keys)
    hits, renders = mgr.perf["render_memo_hits"], mgr.perf["n_renders"]
    assert hits + renders == n
    assert (hits, renders, mgr.perf["render_memo_bypass"]) == \
        ((n, 0, 0) if raw else (0, n, n))
    assert (mgr.perf["render"] == 0.0) == raw
    assert [(v.message, v.name) for vs in second.kept.values() for v in vs] \
        == [(v.message, v.name) for vs in first.kept.values() for v in vs]


def test_a_pass_with_nothing_to_render_still_writes_the_counters(toy):
    client, evaluator = toy
    clean = [o for o in _objects(40) if o["metadata"]["labels"]]
    mgr = AuditManager(
        client, lister=lambda: iter(clean),
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline="on"),
        evaluator=evaluator)
    run = mgr.audit()
    assert sum(run.total_violations.values()) == 0
    assert {k: mgr.perf[k] for k in ("render_memo_hits", "n_renders",
                                     "render_memo_bypass", "render")} == \
        {"render_memo_hits": 0, "n_renders": 0, "render_memo_bypass": 0,
         "render": 0.0}


# --- the kept violations' identity in the account (utils/rawjson.peek_identity)

def _violations(run):
    return {key: [(v.message, v.details, v.enforcement_action, v.group,
                   v.version, v.kind, v.name, v.namespace) for v in vs]
            for key, vs in run.kept.items()}


@pytest.mark.parametrize("pipeline", ["on", "off"])
@pytest.mark.parametrize("raw", [True, False], ids=["rawjson", "dicts"])
def test_every_kept_violation_is_counted_peeked_or_loaded(toy, pipeline, raw):
    """``violation_peeked + violation_loaded`` is the kept violations of
    the pass, both written on every pass: unloaded RawJSON whose renders
    the memo answers are all peeked, objects a render loaded and plain
    dicts are all read through the object; the chunk's fold span carries
    the chunk's two counts."""
    from gatekeeper_tpu.utils.rawjson import as_raw

    client, evaluator = toy
    objects = _objects(40)
    lister = (lambda: (as_raw(o) for o in objects)) if raw \
        else (lambda: iter(objects))
    mgr = AuditManager(
        client, lister=lister,
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline=pipeline),
        evaluator=evaluator)
    first = mgr.audit()
    kept = sum(len(vs) for vs in first.kept.values())
    assert kept > 5  # more than one chunk's
    # the pass that rendered loaded what it rendered
    assert (mgr.perf["violation_peeked"], mgr.perf["violation_loaded"]) \
        == (0, kept)
    mgr.perf = {}
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        second = mgr.audit()
    have = raw and native.load_listroute() is not None
    assert (mgr.perf["violation_peeked"], mgr.perf["violation_loaded"]) \
        == ((kept, 0) if have else (0, kept))
    assert _violations(second) == _violations(first)
    name = "pipeline.stage.fold_render" if pipeline == "on" \
        else "audit.chunk.collect_fold"
    folds = [s["attributes"] for t in tracer.traces() for s in t["spans"]
             if s["name"] == name]
    assert len(folds) == 3
    assert all({"violation_peeked", "violation_loaded",
                "render_memo_hits"} <= set(a) for a in folds)
    assert sum(a["violation_peeked"] for a in folds) \
        == mgr.perf["violation_peeked"]
    assert sum(a["violation_loaded"] for a in folds) \
        == mgr.perf["violation_loaded"]
    # a third pass adds to both
    mgr.audit()
    assert mgr.perf["violation_peeked"] + mgr.perf["violation_loaded"] \
        == 2 * kept


def test_without_the_native_module_every_violation_is_loaded_and_the_same(
        toy, monkeypatch):
    from gatekeeper_tpu.utils.rawjson import as_raw

    client, evaluator = toy
    objects = _objects(40)
    mgr = AuditManager(
        client, lister=lambda: (as_raw(o) for o in objects),
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline="on"),
        evaluator=evaluator)
    mgr.audit()
    mgr.perf = {}
    with_module = mgr.audit()
    peeked = mgr.perf["violation_peeked"]
    total = peeked + mgr.perf["violation_loaded"]
    assert total == sum(len(vs) for vs in with_module.kept.values()) > 0
    if native.load_listroute() is not None:
        assert peeked == total
    monkeypatch.setattr(native, "load_listroute", lambda: None)
    mgr.perf = {}
    without = mgr.audit()
    assert (mgr.perf["violation_peeked"], mgr.perf["violation_loaded"]) \
        == (0, total)
    assert _violations(without) == _violations(with_module)
    assert dict(without.total_violations) == \
        dict(with_module.total_violations)


def test_a_pass_that_keeps_nothing_still_writes_both_counters(toy):
    client, evaluator = toy
    clean = [o for o in _objects(40) if o["metadata"]["labels"]]
    mgr = AuditManager(
        client, lister=lambda: iter(clean),
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline="on"),
        evaluator=evaluator)
    mgr.audit()
    assert (mgr.perf["violation_peeked"], mgr.perf["violation_loaded"]) \
        == (0, 0)


# --- the GIL account: held = cpu - released (PR 36) -------------------------

def _thread_clock_step() -> float:
    """The step ``time.thread_time()`` really advances by.  What the
    kernel calls the clock's resolution is a nanosecond everywhere, but
    where it accounts CPU by the scheduler's tick (the chip's host: 10
    ms, PERF.md section 6) the clock stands still between ticks: a
    sub-tick piece of work reads 0.0 and a thread that slept may be
    charged a whole tick."""
    steps = []
    last = time.thread_time()
    deadline = time.perf_counter() + 0.25
    while len(steps) < 5 and time.perf_counter() < deadline:
        now = time.thread_time()
        if now != last:
            steps.append(now - last)
            last = now
    return max([time.get_clock_info("thread_time").resolution]
               + ([min(steps)] if steps else []))


CLOCK_STEP = _thread_clock_step()
# thread_time, perf_counter and the C's clock are read a few instructions
# apart, an item; and each reading of a CPU clock is a step off at most
TICK = max(2e-3, 2 * CLOCK_STEP)

RELEASED_KEYS_PIPELINED = (
    ["list_released", "pipe_process_cpu"]
    + [f"pipe_{s}_released"
       for s in ("flatten", "dispatch", "collect", "fold_render")])
EVALUATOR_KEYS = ["masks_cpu"]
FLATTEN_RAW_KEYS = ["fl_items_cpu", "fl_columnize_cpu",
                    "fl_columnize_released", "fl_stabilize_cpu"]
# pairs no metric would read (the pipe_<stage>_* pairs carry them): not
# written
UNREAD = ["flatten_cpu", "flatten_released", "masks_released",
          "wire_pack_cpu", "wire_pack_released", "dispatch_cpu",
          "dispatch_released", "fl_assemble_cpu", "fl_canon_fill_cpu"]
GONE = ["fl_c_columnize", "fl_py_assemble", "fl_canon_fill", "fl_stabilize"]
# counts every lane of the flattener writes on every pass, a 0 too (PR 37):
# the columnizer's prefill bytes by who wrote them, the chunks re-padded
FILL_KEYS = ["fl_fill_released_bytes", "fl_fill_held_bytes",
             "fl_stabilize_repads"]


def _raw_mgr(toy, pipeline, n=40):
    from gatekeeper_tpu.utils.rawjson import as_raw

    client, evaluator = toy
    objects = _objects(n)
    return AuditManager(
        client, lister=lambda: (as_raw(o) for o in objects),
        config=AuditConfig(chunk_size=16, exact_totals=False,
                           pipeline=pipeline),
        evaluator=evaluator)


def _ordered(released, cpu, busy, what):
    assert -TICK <= released <= cpu + TICK, what
    assert cpu <= busy + TICK, what


def test_released_thread_time_sums_the_loaded_modules_and_loads_none(
        monkeypatch):
    monkeypatch.setattr(native, "_mods", {})
    monkeypatch.setattr(native, "_tried", set())
    assert native.released_thread_time() == 0.0
    assert native._mods == {} and native._tried == set()  # built nothing

    class Fake:
        def __init__(self, s):
            self.s = s

        def released_cpu(self):
            return self.s

    monkeypatch.setattr(native, "_mods", {
        "gtpu_flattenjson": Fake(0.25), "gtpu_wirepack": Fake(0.5),
        "gtpu_listroute": Fake(8.0),  # releases nothing: not asked
        "gtpu_flatten": None})
    assert native.released_thread_time() == 0.75
    monkeypatch.setattr(native, "_mods", {"gtpu_wirepack": None})
    assert native.released_thread_time() == 0.0


def test_the_released_clock_is_the_calling_threads_own():
    mod = native.load_wirepack()
    if mod is None:
        pytest.skip("native/wirepackmod.c did not build")
    seen = {}

    def other():
        seen["fresh"] = mod.released_cpu()

    import numpy as np

    rng = np.random.default_rng(36)
    n = 1 << 16
    cols = {"a": {"sid": rng.integers(-1, 40000, (n, 8)).astype(np.int32)}}
    stats: dict = {}
    sharded.col_stats_update(stats, cols)
    sharded.merge_pad_stats(stats)
    before = mod.released_cpu()
    # one pack of 2 MB is under a millisecond: on a clock that steps by
    # the scheduler's tick, pack until a few steps have gone by
    deadline = time.perf_counter() + 20.0
    while True:
        counts: dict = {}
        sharded.pack_transfer_cols(cols, n, stats=stats, counts=counts)
        assert counts["fused"] == 1
        if mod.released_cpu() - before >= 3 * CLOCK_STEP \
                or time.perf_counter() > deadline:
            break
    assert mod.released_cpu() > before
    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen["fresh"] == 0.0  # a thread that never called in


def test_a_spinning_stage_holds_the_lock_for_all_its_cpu():
    run = StagedPipeline([
        Stage("spin", _spin(0.01), queue_cap=2),
        Stage("sleep", _sleepy(0.01), queue_cap=2),
        Stage("sink", lambda x: None),
    ]).run(range(12))
    spin = run.stage("spin")
    assert spin.released_s == 0.0 and run.source_released_s == 0.0
    # held = cpu - released: all of what it ran, and it ran what it was
    # busy for (twelve items, each a step of the clock off at most)
    assert spin.cpu_s - spin.released_s == pytest.approx(spin.cpu_s,
                                                         rel=0.1)
    assert spin.cpu_s > 0.5 * spin.busy_s - TICK * spin.items
    for st in run.stages:
        _ordered(st.released_s, st.cpu_s, st.busy_s + TICK * st.items,
                 st.name)


def test_the_released_clock_is_the_callers_and_read_on_each_thread():
    # the executor knows no native code: whoever builds the pipeline
    # hands it the clock, and each thread books what the clock advanced
    # by around its own items; released may pass cpu, nothing is clipped
    local = threading.local()

    def clock():
        local.s = getattr(local, "s", 0.0) + 0.25
        return local.s

    run = StagedPipeline([
        Stage("a", lambda x: x, queue_cap=2),
        Stage("b", lambda x: None, queue_cap=2),
    ], released_clock=clock).run(range(4))
    for st in run.stages:
        assert st.released_s == pytest.approx(0.25 * 4)
        assert st.cpu_s - st.released_s < 0.0
    # five next() calls, the last the StopIteration
    assert run.source_released_s == pytest.approx(0.25 * 5)
    assert StagedPipeline([Stage("a", lambda x: None)]).run(
        range(4)).stage("a").released_s == 0.0


def test_a_stage_that_packs_books_its_released_cpu(monkeypatch):
    """A stage whose fn is the wire pack of a large chunk: the native
    call's CPU is booked released on the worker thread that ran it, and
    with the module unloaded the stage reads 0.0 and packs the same
    bytes."""
    import numpy as np

    rng = np.random.default_rng(36)
    n = 1 << 16
    cols = {"a": {"sid": rng.integers(-1, 40000, (n, 8)).astype(np.int32),
                  "kind": rng.integers(-1, 7, (n, 8)).astype(np.int8)},
            "b": {"count": rng.integers(0, 200, n).astype(np.int32)}}
    stats: dict = {}
    sharded.col_stats_update(stats, cols)
    sharded.merge_pad_stats(stats)
    packed = []

    def pack(_):
        counts: dict = {}
        packed.append((sharded.pack_transfer_cols(cols, n, stats=stats,
                                                  counts=counts), counts))

    def one_run(items=3):
        return StagedPipeline(
            [Stage("pack", pack)],
            released_clock=native.released_thread_time).run(range(items))

    have = native.load_wirepack() is not None
    run = one_run().stage("pack")
    _ordered(run.released_s, run.cpu_s, run.busy_s + TICK * run.items,
             "pack")
    # three packs of a few milliseconds: a clock that steps by the
    # scheduler's tick may stand still through them, so pack for ticks
    items = 3
    while have and run.released_s == 0.0 and items < 3000:
        items *= 10
        run = one_run(items).stage("pack")
    assert (run.released_s > 0.0) == have
    assert all(c["fused"] > 0 for _, c in packed) == have
    with_module = packed[:3]
    del packed[:]
    monkeypatch.setattr(native, "load_wirepack", lambda: None)
    run = one_run().stage("pack")
    assert run.released_s == 0.0
    assert [c["fused"] for _, c in packed] == [0, 0, 0]
    (bufs, layout), _ = with_module[0]
    for (bufs2, layout2), _ in with_module[1:] + packed:
        assert layout2 == layout and list(bufs2) == list(bufs)
        assert all(bufs2[k].tobytes() == bufs[k].tobytes() for k in bufs)


@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_released_is_at_most_cpu_is_at_most_busy(toy, pipeline):
    _client, evaluator = toy
    mgr = _raw_mgr(toy, pipeline)
    mgr.audit()  # whatever compiles, compiles here
    mgr.perf = {}
    evaluator.perf_reset()
    mgr.audit()
    perf, eperf = mgr.perf, evaluator.perf
    chunks = 3
    _ordered(perf["list_released"], perf["list_cpu"],
             perf["list"] + TICK * chunks, "list")
    if pipeline == "on":
        for s in ("flatten", "dispatch", "collect", "fold_render"):
            _ordered(perf[f"pipe_{s}_released"], perf[f"pipe_{s}_cpu"],
                     perf[f"pipe_{s}_busy"] + TICK * chunks, s)
        # every thread of the process: at least the calling thread's own
        assert perf["pipe_process_cpu"] >= perf["list_cpu"] - TICK
        cores = perf["pipe_process_cpu"] / perf["pipe_wall"]
        assert 0.0 < cores
        # the only C of ours the flatten stage calls is the columnizer
        assert perf["pipe_flatten_released"] == pytest.approx(
            eperf["fl_columnize_released"], abs=TICK)
    else:
        assert "pipe_process_cpu" not in perf
    # the masks call no C of ours that lets the lock go: all held
    _ordered(0.0, eperf["masks_cpu"], eperf["masks"] + TICK * chunks,
             "masks")
    # the flattener's table: thread CPU, inside the flatten's seconds
    _ordered(eperf["fl_columnize_released"], eperf["fl_columnize_cpu"],
             eperf["flatten"] + TICK * chunks, "columnize")
    parts = sum(eperf[k] for k in FLATTEN_RAW_KEYS
                if k != "fl_columnize_released")
    assert parts <= eperf["flatten"] + 3 * TICK * chunks


@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_the_account_keys_are_written_on_every_pass(toy, pipeline):
    """Every key of the GIL account is written by a pass whether or not
    anything was released (a 0.0 too), with a tracer installed or not:
    the same keys, the same counts."""
    _client, evaluator = toy
    mgr = _raw_mgr(toy, pipeline)
    mgr.audit()
    seen = []
    for traced in (False, True):
        mgr.perf = {}
        evaluator.perf_reset()
        if traced:
            with tracing.activate(tracing.Tracer(seed=0)):
                mgr.audit()
        else:
            mgr.audit()
        perf, eperf = mgr.perf, evaluator.perf
        want = ["list_cpu", "list_released"] + (
            RELEASED_KEYS_PIPELINED if pipeline == "on" else [])
        for key in want:
            assert isinstance(perf.get(key), float), key
        for key in EVALUATOR_KEYS + FLATTEN_RAW_KEYS:
            assert isinstance(eperf.get(key), float), key
        assert not [k for k in GONE + UNREAD if k in eperf]
        seen.append((sorted(perf), sorted(eperf),
                     {k: perf[k] for k in perf if k.startswith(
                         ("list_fast", "list_slow", "n_renders",
                          "violation_", "render_memo_"))},
                     {k: eperf[k] for k in eperf if k.startswith(
                         ("wire_cols_", "mask_rows_", "sweep_rows",
                          "wire_bytes", "d2h_bytes"))}))
    assert seen[0] == seen[1]


def test_dict_objects_write_the_stage_keys_and_no_flatten_raw_table(toy):
    # the dict lane never enters flatten_raw: its table is absent, the
    # stage's own cpu and released are there, a 0.0; nothing was prefilled
    _client, evaluator = toy
    evaluator.perf_reset()
    mgr = _toy_mgr(toy, "on")
    mgr.audit()
    assert mgr.perf["pipe_flatten_released"] == 0.0
    assert isinstance(mgr.perf["pipe_flatten_cpu"], float)
    assert isinstance(evaluator.perf["masks_cpu"], float)
    assert not [k for k in FLATTEN_RAW_KEYS + GONE + UNREAD
                if k in evaluator.perf]
    assert [evaluator.perf[k] for k in FILL_KEYS] == [0, 0, 0]


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_the_fill_counters_are_written_on_every_pass(toy, pipeline, traced):
    """The columnizer's workers prefill its arrays with the lock let go:
    every byte of it is counted released, none held, and the arrays
    arrive at a width `_stabilize` has nothing to add to; the same counts
    with a tracer and without, and on the spans of the native calls."""
    _client, evaluator = toy
    mgr = _raw_mgr(toy, pipeline)
    mgr.audit()
    mgr.perf = {}
    evaluator.perf_reset()
    tracer = tracing.Tracer(seed=0)
    if traced:
        with tracing.activate(tracer):
            mgr.audit()
    else:
        mgr.audit()
    released, held, repads = (evaluator.perf[k] for k in FILL_KEYS)
    # three chunks of 16, 16 and 8 Namespaces: at least the four identity
    # columns of every row, int32
    assert released >= 40 * 4 * 4 and released == int(released)
    assert (held, repads) == (0, 0)
    if traced:
        spans = _spans_by_name(tracer)[0]["ops.flatten.native"]
        assert len(spans) == 3
        assert sum(s["attributes"]["fill_released_bytes"]
                   for s in spans) == released
        assert {s["attributes"]["fill_held_bytes"] for s in spans} == {0}


def test_with_the_native_modules_unloaded_nothing_is_released(
        toy, monkeypatch):
    client, plain = toy
    warmed = ShardedEvaluator(plain.driver, make_mesh(), violations_limit=5)
    warmed.warm_pass(client.constraints(), _objects(40), 16)
    mgr = _raw_mgr((client, warmed), "on")
    mgr.audit()
    mgr.perf = {}
    warmed.perf_reset()
    with_modules = mgr.audit()
    fused = warmed.perf["wire_cols_fused"]
    assert (fused > 0) == (native.load_wirepack() is not None)
    # the warm pass's widths went in as the columnizer's floors
    assert (warmed.perf["fl_fill_released_bytes"] > 0) == (
        native.load_json() is not None)
    assert warmed.perf["fl_stabilize_repads"] == 0
    monkeypatch.setattr(native, "load_wirepack", lambda: None)
    monkeypatch.setattr(native, "load_json", lambda: None)
    mgr.perf = {}
    warmed.perf_reset()
    without = mgr.audit()
    released = {k: v for k, v in list(mgr.perf.items())
                + list(warmed.perf.items()) if k.endswith("_released")}
    # (without the columnizer flatten_raw is not entered: no fl_* table,
    # and nothing is prefilled by anyone's workers)
    assert warmed.perf["fl_fill_released_bytes"] == 0
    assert warmed.perf["fl_fill_held_bytes"] == 0
    assert warmed.perf["fl_stabilize_repads"] == 0
    assert set(released) >= {"list_released"} | {
        f"pipe_{s}_released"
        for s in ("flatten", "dispatch", "collect", "fold_render")}
    assert set(released.values()) == {0.0}
    assert warmed.perf["wire_cols_fused"] == 0
    assert _kept(without) == _kept(with_modules)
    assert sum(_kept(without)[0].values()) > 0


def _spans_by_name(tracer):
    by_name: dict = {}
    by_id: dict = {}
    for tr in tracer.traces():
        for s in tr["spans"]:
            by_name.setdefault(s["name"], []).append(s)
            by_id[s["span_id"]] = s
    return by_name, by_id


def _inside(child, parent):
    # float seconds near 1.8e9 resolve to ~2.4e-7
    assert child["start_ts"] >= parent["start_ts"] - 1e-6
    assert child["start_ts"] + child["duration_s"] <= \
        parent["start_ts"] + parent["duration_s"] + 1e-6


def test_the_drain_is_a_span_under_the_ambient_span():
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer), tracing.span("root") as root:
        run = StagedPipeline([
            Stage("sink", lambda x: (time.sleep(0.005), None)[1],
                  queue_cap=64),
        ]).run(range(10))
    by_name, by_id = _spans_by_name(tracer)
    (drain,) = by_name["pipeline.drain"]
    assert drain["parent_id"] == root.span_id
    assert drain["thread_id"] == threading.get_ident()
    assert drain["attributes"]["chunks"] == 10
    _inside(drain, by_id[root.span_id])
    # it opens when the last next() has been handed on and is the drain
    assert drain["start_ts"] >= max(
        s["start_ts"] + s["duration_s"]
        for s in by_name["pipeline.source"]) - 1e-6
    assert drain["duration_s"] <= run.drain_s + 1e-6
    assert drain["duration_s"] > 0.8 * run.drain_s


@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_the_flatteners_parts_are_spans_under_columnize(toy, pipeline):
    mgr = _raw_mgr(toy, pipeline)
    mgr.audit()
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        mgr.audit()
    by_name, by_id = _spans_by_name(tracer)
    columnize = by_name["ops.flatten.columnize"]
    assert len(columnize) == 3  # one a chunk
    assert {s["attributes"]["lane_used"] for s in columnize} == {"raw"}
    for part in ("items", "native", "assemble"):
        spans = by_name[f"ops.flatten.{part}"]
        assert len(spans) == 3, part
        assert sorted(s["parent_id"] for s in spans) == \
            sorted(s["span_id"] for s in columnize)
        for s in spans:
            _inside(s, by_id[s["parent_id"]])
            assert s["thread_id"] == by_id[s["parent_id"]]["thread_id"]
    # in order inside their chunk's columnize span
    for c in columnize:
        mine = sorted((s for part in ("items", "native", "assemble")
                       for s in by_name[f"ops.flatten.{part}"]
                       if s["parent_id"] == c["span_id"]),
                      key=lambda s: s["start_ts"])
        assert [s["name"].rsplit(".", 1)[1] for s in mine] == \
            ["items", "native", "assemble"]
    if pipeline == "on":
        (root,) = by_name["audit.sweep"]
        (drain,) = by_name["pipeline.drain"]
        assert drain["parent_id"] == root["span_id"]
        _inside(drain, root)


# --- result counts from the device in the account (ir Program.count) --------

LIMITS = {
    "apiVersion": "constraints.gatekeeper.sh/v1beta1",
    "kind": "K8sContainerLimits",
    "metadata": {"name": "container-must-have-limits"},
    "spec": {"match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
             "parameters": {"cpu": "200m", "memory": "1Gi"}},
}


@pytest.fixture(scope="module")
def counted_toy():
    """A row the device counts (K8sContainerLimits, one result per clause
    and distinct container name) beside one it cannot
    (K8sRequiredLabels: details beside the message)."""
    tpu = TpuDriver()
    client = Client(target=K8sValidationTarget(), drivers=[tpu],
                    enforcement_points=[AUDIT_EP])
    for name, con in (("requiredlabels", {
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sRequiredLabels",
            "metadata": {"name": "ns-must-have-gk"},
            "spec": {"match": {"kinds": [{"apiGroups": [""],
                                          "kinds": ["Namespace"]}]},
                     "parameters": {"labels": [{"key": "gatekeeper"}]}}}),
            ("containerlimits", LIMITS)):
        client.add_template(load_yaml_file(f"{LIB}/{name}/template.yaml")[0])
        client.add_constraint(con)
    assert tpu._programs["K8sContainerLimits"].program.count is not None
    assert tpu._programs["K8sRequiredLabels"].program.count is None
    # pods with two limitless containers: four results each
    objects = _objects(40) + [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": f"p{i}", "namespace": "ns-1"},
         "spec": {"containers": [{"name": "a", "image": "x"},
                                 {"name": "b", "image": "y"}]}}
        for i in range(30)]
    return client, ShardedEvaluator(tpu, make_mesh(), violations_limit=5), \
        objects


def _counted_pass(counted_toy, exact, pipeline="on"):
    client, evaluator, objects = counted_toy
    evaluator.perf_reset()
    mgr = AuditManager(
        client, lister=lambda: iter(objects),
        config=AuditConfig(chunk_size=16, exact_totals=exact,
                           pipeline=pipeline, violations_limit=5),
        evaluator=evaluator)
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        run = mgr.audit()
    dispatches = [s for t in tracer.traces() for s in t["spans"]
                  if s["name"] == "device.sweep_dispatch"]
    return run, mgr.perf, evaluator.perf, dispatches


@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_the_exact_lane_renders_uncounted_hits_and_the_kept_slots(
        counted_toy, pipeline):
    """``n_renders`` under exact totals is the uncounted row's hits plus
    the renders that fill the counted row's kept slots (plain dicts: the
    memo answers none), and the counted row's total is its results."""
    run, mperf, eperf, dispatches = _counted_pass(counted_toy, True,
                                                  pipeline)
    unlabelled = sum(not o["metadata"].get("labels")
                     for o in counted_toy[2] if o["kind"] == "Namespace")
    limits = ("K8sContainerLimits", "container-must-have-limits")
    assert run.total_violations[limits] == 30 * 4
    assert run.total_violations[("K8sRequiredLabels", "ns-must-have-gk")] \
        == unlabelled
    # five kept slots at four results a pod: the first two pods render
    assert [v.name for v in run.kept[limits]] == ["p0"] * 4 + ["p1"]
    assert mperf["n_renders"] == unlabelled + 2
    # written on every dispatch, a 0 too: the Pod group's rows counted
    assert sorted(s["attributes"]["sweep_rows_counted"]
                  for s in dispatches) == [0, 0, 0, 1, 1]
    assert eperf["sweep_rows_counted"] == 2 and eperf["sweep_rows"] == 5


def test_in_the_top_k_lane_every_row_is_counted(counted_toy):
    run, _mperf, eperf, dispatches = _counted_pass(counted_toy, False)
    assert run.total_violations[
        ("K8sContainerLimits", "container-must-have-limits")] == 30
    assert eperf["sweep_rows_counted"] == eperf["sweep_rows"] == 5
    assert all(s["attributes"]["sweep_rows_counted"]
               == s["attributes"]["sweep_rows"] for s in dispatches)


def test_an_uncertified_hit_renders_its_row_and_leaves_the_count(
        counted_toy):
    """A container named with the message's delimiter could print as
    another's message: the device does not certify that chunk's count, the
    row renders every hit there, and the total is the interpreter's."""
    client, evaluator, objects = counted_toy
    tricky = [dict(o) for o in objects]
    tricky[-1] = {"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": "odd", "namespace": "ns-1"},
                  "spec": {"containers": [{"name": "a> has no cpu limit",
                                           "image": "x"}]}}
    evaluator.perf_reset()
    mgr = AuditManager(
        client, lister=lambda: iter(tricky),
        config=AuditConfig(chunk_size=16, exact_totals=True),
        evaluator=evaluator)
    run = mgr.audit()
    assert run.total_violations[
        ("K8sContainerLimits", "container-must-have-limits")] == 29 * 4 + 2
    # the chunk with the odd pod is a row the renders counted
    assert evaluator.perf["sweep_rows_counted"] == 1

"""End-to-end tracing: one timeline from AdmissionReview to XLA
dispatch.

Covers the webhook HTTP path (traceparent ingest/emit, request →
review → batcher enqueue/flush → device.query_batch), the audit sweep
(chunk-scoped pipeline stage spans, serial-schedule chunk spans, sweep
root attributes), the /debug/traces ring-buffer endpoint, resilience
events landing on spans under chaos, and the tracer-on vs tracer-off
verdict differential over the library corpus (tracing must be
zero-cost to verdicts — the chaos-differential discipline applied to
observability)."""

import json
import time
import urllib.request

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.observability import export, tracing
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import load_library, make_cluster_objects
from gatekeeper_tpu.utils.unstructured import load_yaml_file
from gatekeeper_tpu.webhook.policy import Batcher, ValidationHandler
from gatekeeper_tpu.webhook.server import WebhookServer

LIB = "/root/repo/library/general"


# --- webhook plane --------------------------------------------------------

def _webhook_client():
    client = Client(target=K8sValidationTarget(), drivers=[TpuDriver()],
                    enforcement_points=["validation.gatekeeper.sh"])
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
    return client


def _review_body(uid="trace-u1"):
    return {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "uid": uid,
            "kind": {"group": "", "version": "v1", "kind": "Namespace"},
            "name": "bad", "namespace": "", "operation": "CREATE",
            "userInfo": {"username": "alice"},
            "object": {"apiVersion": "v1", "kind": "Namespace",
                       "metadata": {"name": "bad"}},
        },
    }


@pytest.fixture(scope="module")
def traced_server():
    client = _webhook_client()
    # small_batch=0: every admission takes the device verdict-grid lane,
    # so the timeline reaches device.query_batch deterministically
    batcher = Batcher(client, small_batch=0).start()
    srv = WebhookServer(
        validation_handler=ValidationHandler(client, batcher=batcher),
        port=0,
    ).start()
    yield srv
    srv.stop()
    batcher.stop()


def _post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def _wait_traces(tracer, n=1, timeout=5.0):
    """The kept traces, once there are ``n``: the request thread ends its
    root span AFTER the reply is on the wire, so the ring may still be
    empty when ``_post`` returns."""
    deadline = time.monotonic() + timeout
    while len(tracer.traces()) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    return tracer.traces()


def test_webhook_timeline_and_traceparent_roundtrip(traced_server):
    remote_trace = "a" * 32
    header = f"00-{remote_trace}-{'b' * 16}-01"
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        out, resp_headers = _post(
            traced_server.port, "/v1/admit", _review_body(),
            headers={"traceparent": header})
    assert out["response"]["allowed"] is False
    traces = _wait_traces(tracer)
    assert len(traces) == 1
    tr = traces[0]
    # ingest: the request span joined the caller's trace
    assert tr["trace_id"] == remote_trace
    by_name = {s["name"]: s for s in tr["spans"]}
    root = by_name["webhook.request"]
    assert root["parent_id"] == "b" * 16  # remote parent link
    assert root["attributes"]["path"] == "/v1/admit"
    assert root["attributes"]["uid"] == "trace-u1"
    assert root["attributes"]["http.status"] == 200
    # the full lane: request -> review -> batcher enqueue/flush -> device
    for name in ("webhook.review", "webhook.batcher.enqueue",
                 "webhook.batcher.flush", "device.query_batch"):
        assert name in by_name, (name, sorted(by_name))
    assert by_name["webhook.review"]["parent_id"] == root["span_id"]
    enq = by_name["webhook.batcher.enqueue"]
    assert enq["parent_id"] == by_name["webhook.review"]["span_id"]
    flush = by_name["webhook.batcher.flush"]
    assert flush["parent_id"] == enq["span_id"]  # cross-thread link
    assert flush["attributes"]["lane"] == "grid"
    assert flush["attributes"]["batch_size"] == 1
    assert by_name["device.query_batch"]["parent_id"] == flush["span_id"]
    # emit: the response carries the request span's traceparent
    tp = resp_headers.get("traceparent", "")
    assert tp.startswith(f"00-{remote_trace}-")
    assert tp.split("-")[2] == root["span_id"]


def test_webhook_without_traceparent_starts_fresh_trace(traced_server):
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        _post(traced_server.port, "/v1/admit", _review_body("u2"))
    tr = _wait_traces(tracer)[0]
    root = next(s for s in tr["spans"] if s["name"] == "webhook.request")
    assert root["parent_id"] is None
    assert len(tr["trace_id"]) == 32


def test_debug_traces_endpoint(traced_server):
    url = f"http://127.0.0.1:{traced_server.port}/debug/traces"
    # no tracer installed -> 404 with a hint
    try:
        urllib.request.urlopen(url)
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        _post(traced_server.port, "/v1/admit", _review_body("u3"))
        _wait_traces(tracer)
        with urllib.request.urlopen(url) as resp:
            doc = json.loads(resp.read())
    assert doc["kept"] >= 1
    assert doc["traces"][0]["spans"]
    names = {s["name"] for tr in doc["traces"] for s in tr["spans"]}
    assert "webhook.request" in names


# --- audit sweep ----------------------------------------------------------

def _library_mgr(objects, **cfg_kw):
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[AUDIT_EP])
    load_library(client)
    for o in objects:
        if o.get("kind") == "Ingress":
            client.add_data(o)
    cfg_kw.setdefault("exact_totals", False)
    cfg = AuditConfig(chunk_size=48, **cfg_kw)
    return AuditManager(
        client, lister=lambda: iter(objects), config=cfg,
        evaluator=ShardedEvaluator(tpu, make_mesh(), violations_limit=20),
    )


def _kept_signature(run):
    return {
        k: [(v.message, v.kind, v.name, v.namespace, v.enforcement_action)
            for v in vs]
        for k, vs in run.kept.items()
    }


def test_pipelined_sweep_emits_chunk_scoped_stage_spans(tmp_path):
    objects = make_cluster_objects(120, seed=17)
    mgr = _library_mgr(objects, pipeline="on")
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        run = mgr.audit()
    assert mgr.perf["pipelined"] == 1.0
    traces = tracer.traces()
    assert len(traces) == 1
    tr = traces[0]
    spans = tr["spans"]
    root = next(s for s in spans if s["name"] == "audit.sweep")
    # the ROADMAP's bench-JSON numbers ride the sweep root span
    assert root["attributes"]["objects"] == run.total_objects == 120
    assert root["attributes"]["violations"] == \
        sum(run.total_violations.values()) > 0
    assert root["attributes"]["stage_busy_sum_s"] == \
        mgr.pipe_stats["stage_busy_sum_s"]
    assert root["attributes"]["device_wait_s"] == \
        mgr.pipe_stats["device_wait_s"]
    # chunk-scoped stage spans, parented under the sweep root
    for stage in ("flatten", "dispatch", "collect", "fold_render"):
        st = [s for s in spans if s["name"] == f"pipeline.stage.{stage}"]
        assert st, stage
        assert all(s["parent_id"] == root["span_id"] for s in st)
        chunks = sorted(s["attributes"]["chunk"] for s in st)
        assert chunks == list(range(len(st))), (stage, chunks)
    n_chunks = mgr.pipe_stats["stages"]["flatten"]["items"]
    assert len([s for s in spans
                if s["name"] == "pipeline.stage.flatten"]) == n_chunks
    # the device lane is visible inside the dispatch/collect stages
    disp = [s for s in spans if s["name"] == "device.sweep_dispatch"]
    assert disp
    disp_parents = {s["parent_id"] for s in disp}
    stage_ids = {s["span_id"] for s in spans
                 if s["name"] == "pipeline.stage.dispatch"}
    assert disp_parents <= stage_ids
    assert any(s["name"] == "device.sweep_collect" for s in spans)

    # Chrome export of this sweep is a valid trace-event file with the
    # chunk indices riding the args (the --trace artifact shape)
    path = tmp_path / "sweep_trace.json"
    export.write_chrome_trace(str(path), tracer)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert any(e["ph"] == "X" and e["name"].startswith("pipeline.stage.")
               and "chunk" in e["args"] for e in evs)
    assert any(e["name"] == "device.sweep_dispatch" for e in evs)
    assert any(e["name"] == "audit.sweep" for e in evs)


def test_serial_sweep_emits_chunk_spans():
    objects = make_cluster_objects(100, seed=19)
    mgr = _library_mgr(objects, pipeline="off")
    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        mgr.audit()
    spans = tracer.traces()[0]["spans"]
    subs = [s for s in spans if s["name"] == "audit.chunk.submit"]
    folds = [s for s in spans if s["name"] == "audit.chunk.collect_fold"]
    assert subs and len(folds) == len(subs)
    assert sorted(s["attributes"]["chunk"] for s in subs) == \
        list(range(len(subs)))
    root = next(s for s in spans if s["name"] == "audit.sweep")
    assert all(s["parent_id"] == root["span_id"] for s in subs)


@pytest.mark.slow  # tier-1 wall budget (PR 15): observability
# on-vs-off bit-identity stays pinned in tier-1 by
# test_obs_integration.py::test_observability_on_vs_off_bit_identical;
# this tracing-scoped twin rides the slow lane
def test_tracing_differential_verdicts_bit_identical():
    """Acceptance: tracer-on vs tracer-off (and the empty sampler) are
    bit-identical on totals AND rendered kept messages over the library
    corpus."""
    objects = make_cluster_objects(150, seed=23)
    run_off = _library_mgr(objects, pipeline="on").audit()

    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        run_on = _library_mgr(objects, pipeline="on").audit()
    assert len(tracer.traces()) == 1  # tracing actually ran

    empty = tracing.Tracer(seed=0, sample_rate=0.0)
    with tracing.activate(empty):
        run_empty = _library_mgr(objects, pipeline="on").audit()
    assert empty.traces() == [] and empty.span_count > 0

    assert run_off.total_violations == run_on.total_violations \
        == run_empty.total_violations
    assert _kept_signature(run_off) == _kept_signature(run_on) \
        == _kept_signature(run_empty)
    assert sum(run_off.total_violations.values()) > 0  # non-vacuous


# --- resilience events on spans ------------------------------------------

def test_chaos_fault_lands_as_span_event():
    """--chaos + --trace: the injected fault is an event on the exact
    span it hit, and the stage retry rides the same span."""
    from gatekeeper_tpu.resilience.faults import FaultPlan, inject

    objects = make_cluster_objects(100, seed=29)
    mgr = _library_mgr(objects, pipeline="on")
    tracer = tracing.Tracer(seed=0)
    plan = FaultPlan([{"site": "pipeline.stage.flatten", "mode": "error",
                       "times": 1}])
    with tracing.activate(tracer), inject(plan):
        run = mgr.audit()
    assert plan.fired() == 1
    spans = tracer.traces()[0]["spans"]
    flat = [s for s in spans if s["name"] == "pipeline.stage.flatten"]
    faulted = [s for s in flat
               if any(e["name"] == "fault_injected" for e in s["events"])]
    assert len(faulted) == 1
    ev = {e["name"]: e for e in faulted[0]["events"]}
    assert ev["fault_injected"]["attrs"] == {
        "site": "pipeline.stage.flatten", "mode": "error"}
    assert ev["stage_retry"]["attrs"]["attempt"] == 1
    # the retried stage still produced bit-identical output
    clean = _library_mgr(objects, pipeline="off").audit()
    assert run.total_violations == clean.total_violations


def test_gator_bench_prints_span_summary(tmp_path, capsys):
    """Satellite: one-line top-3-by-self-time span summary after each
    engine run."""
    import shutil

    from gatekeeper_tpu.gator import bench as gbench

    shutil.copy(f"{LIB}/requiredlabels/template.yaml", tmp_path)
    shutil.copy(f"{LIB}/requiredlabels/samples/constraint.yaml", tmp_path)
    (tmp_path / "data.yaml").write_text(
        "apiVersion: v1\nkind: Namespace\nmetadata:\n  name: no-owner\n")
    trace_out = tmp_path / "trace.json"
    rc = gbench.run_cli(["-f", str(tmp_path), "--engine", "rego", "-n",
                         "2", "--trace", str(trace_out)])
    assert rc == 0
    err = capsys.readouterr().err
    line = next(ln for ln in err.splitlines() if ln.startswith("[rego]"))
    assert "spans (top self-time):" in line
    assert "gator.bench.pass" in line
    doc = json.loads(trace_out.read_text())
    assert any(e.get("name") == "gator.bench.pass"
               for e in doc["traceEvents"])
    # the bench-scoped tracer did not leak into the process
    assert tracing.active_tracer() is None


def test_retry_and_breaker_events_ride_the_ambient_span():
    from gatekeeper_tpu.resilience.policy import CircuitBreaker, RetryPolicy

    tracer = tracing.Tracer(seed=0)
    with tracing.activate(tracer):
        with tracing.span("op"):
            calls = [0]

            def flaky():
                calls[0] += 1
                if calls[0] < 3:
                    raise OSError("transient")
                return "ok"

            rp = RetryPolicy(attempts=3, base_s=0.0, cap_s=0.0,
                             dependency="dep", sleep=lambda _s: None)
            assert rp.call(flaky) == "ok"
            br = CircuitBreaker("dep2", failure_threshold=1,
                                clock=lambda: 0.0)
            br.record_failure()
    sp = tracer.traces()[0]["spans"][0]
    events = [(e["name"], e["attrs"]) for e in sp["events"]]
    retries = [a for n, a in events if n == "retry"]
    assert [a["attempt"] for a in retries] == [1, 2]
    assert all(a["dependency"] == "dep" for a in retries)
    transitions = [a for n, a in events if n == "breaker_transition"]
    assert transitions == [{"dependency": "dep2", "breaker_from": "closed",
                            "breaker_to": "open"}]

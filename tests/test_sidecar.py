"""Evaluate-sidecar seam tests: the control plane evaluates only through
gRPC (SURVEY.md §7 "only Driver.Query crosses the boundary"); verdicts,
messages and audit results must be identical to the in-process driver."""

import json
import os

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.remote import RemoteDriver, RemoteEvaluator
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.rpc.sidecar import serve
from gatekeeper_tpu.target.review import AugmentedUnstructured
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.synthetic import load_library, make_cluster_objects

LIB = os.path.join(os.path.dirname(__file__), "..", "library")


@pytest.fixture(scope="module")
def sidecar():
    server, port, servicer = serve(port=0, violations_limit=20)
    yield f"127.0.0.1:{port}", servicer
    server.stop(grace=1)


def _remote_client(address):
    remote = RemoteDriver(address)
    client = Client(target=K8sValidationTarget(),
                    drivers=[remote, CELDriver()],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    return client, remote


def _local_client():
    cel = CELDriver()
    tpu = TpuDriver(cel_driver=cel)
    client = Client(target=K8sValidationTarget(), drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    return client, tpu


def test_remote_driver_review_parity(sidecar):
    address, _svc = sidecar
    rc, remote = _remote_client(address)
    lc, _tpu = _local_client()
    load_library(rc)
    load_library(lc)
    assert remote.fallback_kinds() == {}
    assert len(remote.lowered_kinds()) >= 40  # full shipped library

    objects = make_cluster_objects(120, seed=17)
    for o in objects:
        if o.get("kind") == "Ingress":
            rc.add_data(o)
            lc.add_data(o)
    for o in objects[:60]:
        aug = AugmentedUnstructured(object=o, source=SOURCE_ORIGINAL)
        rr = rc.review(aug, enforcement_point=AUDIT_EP)
        lr = lc.review(aug, enforcement_point=AUDIT_EP)
        key = lambda r: ((r.constraint.get("metadata") or {})
                         .get("name", ""), r.msg)
        assert sorted(map(key, rr.results())) == \
            sorted(map(key, lr.results())), o.get("metadata")


def test_remote_audit_sweep_parity(sidecar):
    address, _svc = sidecar
    rc, remote = _remote_client(address)
    lc, ltpu = _local_client()
    load_library(rc)
    load_library(lc)
    remote.wipe_data()  # the module-scoped sidecar keeps prior tests' data
    objects = make_cluster_objects(300, seed=23)
    for o in objects:
        if o.get("kind") == "Ingress":
            rc.add_data(o)
            lc.add_data(o)

    r_mgr = AuditManager(
        rc, lister=lambda: iter(objects),
        config=AuditConfig(chunk_size=128, exact_totals=False),
        evaluator=RemoteEvaluator(remote, violations_limit=20),
    )
    l_mgr = AuditManager(
        lc, lister=lambda: iter(objects),
        config=AuditConfig(chunk_size=128, exact_totals=False),
        evaluator=ShardedEvaluator(ltpu, make_mesh(), violations_limit=20),
    )
    r_run = r_mgr.audit()
    l_run = l_mgr.audit()
    assert r_run.total_objects == l_run.total_objects == 300
    assert r_run.total_violations == l_run.total_violations
    for k in l_run.kept:
        assert sorted(v.message for v in r_run.kept[k]) == \
            sorted(v.message for v in l_run.kept[k]), k


def test_remote_exact_totals(sidecar):
    """exact_totals through the sidecar must match the local exact path
    (the CEL noprivileged template yields ONE result per violating pod —
    its validation is size(badContainers)==0 — so totals count pods), and
    both the interpreter's, on a library where the device counts some rows'
    results (container rules: one result per distinct container name, two
    here a pod) and the renders count the others' (K8sDisallowedRepos,
    K8sContainerRatios)."""
    from gatekeeper_tpu.drivers.rego_driver import RegoDriver

    address, _svc = sidecar
    rc, remote = _remote_client(address)
    lc, ltpu = _local_client()
    pc = Client(target=K8sValidationTarget(),
                drivers=[RegoDriver(), CELDriver()],
                enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    for c in (rc, lc, pc):
        load_library(c, skip_kinds=("K8sUniqueIngressHost",))
    remote.wipe_data()
    pods = [{
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"p{i}"},
        "spec": {"containers": [
            {"name": "a", "image": "evilcorp.io/x", "securityContext":
                {"privileged": True},
             "resources": {"limits": {"cpu": "4", "memory": "4Gi"},
                           "requests": {"cpu": "1", "memory": "1Gi"}}},
            {"name": "b", "image": "y", "securityContext":
                {"privileged": True}},
        ]},
    } for i in range(4)] + [o for o in make_cluster_objects(80, seed=41)
                            if o.get("kind") == "Pod"]
    config = AuditConfig(exact_totals=True, violations_limit=5)
    r_mgr = AuditManager(
        rc, lister=lambda: iter(pods), config=config,
        evaluator=RemoteEvaluator(remote, violations_limit=5,
                                  exact_totals=True),
    )
    ev = ShardedEvaluator(ltpu, make_mesh(), violations_limit=5)
    l_mgr = AuditManager(lc, lister=lambda: iter(pods), config=config,
                         evaluator=ev)
    p_mgr = AuditManager(pc, lister=lambda: iter(pods), config=config)
    r_run, l_run, p_run = r_mgr.audit(), l_mgr.audit(), p_mgr.audit()
    assert r_run.total_violations == l_run.total_violations \
        == p_run.total_violations
    for key in p_run.kept:
        want = [(v.name, v.message) for v in p_run.kept[key]]
        for run in (r_run, l_run):
            assert [(v.name, v.message)
                    for v in run.kept[key]] == want, key
    key = ("K8sNoPrivileged", "no-privileged-containers")
    privileged = sum(any(
        (c.get("securityContext") or {}).get("privileged") is True
        for c in (p["spec"].get("containers") or [])
        + (p["spec"].get("initContainers") or [])) for p in pods)
    assert privileged >= 4
    assert r_run.total_violations[key] == privileged  # one result a pod
    assert l_run.total_violations[
        ("K8sContainerLimits", "container-must-have-limits")] > 4
    assert l_run.total_violations[
        ("K8sDisallowedRepos", "repo-must-not-be-k8s-gcr")] >= 4
    # counted and uncounted rows both swept
    assert 0 < ev.perf["sweep_rows_counted"] < ev.perf["sweep_rows"]


def test_sidecar_process_e2e(tmp_path):
    """Two real processes: device-owning sidecar + control plane running
    an audit through it (the reference's two-pod deployment shape)."""
    import socket
    import subprocess
    import sys
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.join(os.path.dirname(__file__), "..")
    side = subprocess.Popen(
        [sys.executable, "-m", "gatekeeper_tpu.rpc.sidecar",
         "--port", str(port)],
        env=env, cwd=root, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            line = side.stderr.readline()
            if "serving on" in line:
                break
        else:
            pytest.fail("sidecar never came up")
        mani = tmp_path / "m"
        mani.mkdir()
        for name in ("noprivileged", "containerlimitscel"):
            src = os.path.join(LIB, "general", name)
            (mani / f"{name}-t.yaml").write_text(
                open(os.path.join(src, "template.yaml")).read())
            (mani / f"{name}-c.yaml").write_text(
                open(os.path.join(src, "samples", "constraint.yaml"))
                .read())
        (mani / "bad.yaml").write_text(json.dumps({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "privpod"},
            "spec": {"containers": [{
                "name": "c", "image": "x",
                "securityContext": {"privileged": True}}]},
        }))
        out = subprocess.run(
            [sys.executable, "-m", "gatekeeper_tpu",
             "--manifests", str(mani),
             "--evaluate-sidecar", f"127.0.0.1:{port}", "--once"],
            env=env, cwd=root, capture_output=True, text=True,
            timeout=180)
        assert "Privileged container is not allowed" in out.stdout, (
            out.stdout, out.stderr[-2000:])
        assert "memory limit" in out.stdout
    finally:
        side.terminate()
        side.wait(timeout=10)


def test_concurrent_sweeps_pipeline_and_agree(sidecar):
    """Round-3 de-serialization: the Sweep handler holds the lock only
    through flatten+submit; device waits overlap.  Four threads sweeping
    concurrently must each get results identical to a serial sweep of
    the same chunk (correctness under contention), and the concurrent
    wall-clock must not exceed the serial wall-clock by more than a
    small factor (the old one-lock design serialized fully)."""
    import threading
    import time

    address, _svc = sidecar
    rc, remote = _remote_client(address)
    load_library(rc)
    remote.wipe_data()
    ev = RemoteEvaluator(remote, violations_limit=20)
    cons = [c for c in rc.constraints()]

    chunks = [make_cluster_objects(200, seed=100 + i) for i in range(4)]

    # serial reference pass (also warms vocab + jit for both lanes)
    serial = []
    t0 = time.perf_counter()
    for ch in chunks:
        serial.append(ev.sweep(cons, ch))
    serial_s = time.perf_counter() - t0

    results = [None] * 4
    errors = []

    # instrument the pipelining claim directly: with the split lock, one
    # RPC's flatten+submit (lock-held) runs WHILE another RPC waits on
    # the device in sweep_collect (unlocked).  Record both spans per
    # server thread; a cross-call submit/collect overlap proves the
    # split — under the old one-lock design every span is mutually
    # exclusive, so no overlap can ever be observed.  The sleep widens
    # the wait window so scheduling jitter can't mask genuine overlap.
    orig_submit = _svc.evaluator.sweep_submit
    orig_collect = _svc.evaluator.sweep_collect
    spans = []  # (phase, server-thread id, t0, t1)
    spans_lock = threading.Lock()

    def timed(phase, orig):
        def wrapper(*a, **k):
            if phase == "collect":
                time.sleep(0.05)
            t0 = time.perf_counter()
            try:
                return orig(*a, **k)
            finally:
                with spans_lock:
                    spans.append((phase, threading.get_ident(), t0,
                                  time.perf_counter()))
        return wrapper

    _svc.evaluator.sweep_submit = timed("submit", orig_submit)
    _svc.evaluator.sweep_collect = timed("collect", orig_collect)

    def run(i):
        try:
            results[i] = ev.sweep(cons, chunks[i])
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    concurrent_s = time.perf_counter() - t0
    _svc.evaluator.sweep_submit = orig_submit
    _svc.evaluator.sweep_collect = orig_collect
    assert not errors, errors
    # the collect wrapper's sleep sits BEFORE its span, widening the
    # window in which another thread's submit can land
    submits = [s for s in spans if s[0] == "submit"]
    collects = [s for s in spans if s[0] == "collect"]
    overlapped = any(
        st != ct and s0 < c1 and c0 < s1
        for _, st, s0, s1 in submits
        for _, ct, c0, c1 in collects)
    pre_waits = [(ct, c0 - 0.05, c1) for _, ct, c0, c1 in collects]
    overlapped = overlapped or any(
        st != ct and s0 < c1 and c0 < s1
        for _, st, s0, s1 in submits
        for ct, c0, c1 in pre_waits)
    assert overlapped, (
        "no cross-call submit/collect overlap: sweeps serialized\n"
        + "\n".join(map(str, spans)))

    def fold(swept):
        # RemoteEvaluator.sweep returns {(kind, name): (total, kept)}
        return {k: (total, sorted(oi for oi, _m, _d in kept))
                for k, (total, kept) in swept.items()}

    for i in range(4):
        assert fold(results[i]) == fold(serial[i]), f"chunk {i} diverged"
    # not a benchmark: just catch a regression to full serialization
    # (warm serial pass vs concurrent pass of identical work)
    assert concurrent_s < serial_s * 2.0, (concurrent_s, serial_s)

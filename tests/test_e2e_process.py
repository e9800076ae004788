"""End-to-end process tests: drive `python -m gatekeeper_tpu` as a real
subprocess (the reference's bats e2e suite shape, test/bats/test.bats) —
audit --once output, the served webhook admit path, and SIGTERM shutdown.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")

MANIFESTS = """\
apiVersion: templates.gatekeeper.sh/v1
kind: ConstraintTemplate
metadata:
  name: k8spsphostnamespace
spec:
  crd:
    spec:
      names:
        kind: K8sPSPHostNamespace
  targets:
    - target: admission.k8s.gatekeeper.sh
      rego: |
        package k8spsphostnamespace

        violation[{"msg": "host namespace"}] {
          input.review.object.spec.hostPID
        }
---
apiVersion: constraints.gatekeeper.sh/v1beta1
kind: K8sPSPHostNamespace
metadata:
  name: no-host-ns
spec: {}
---
apiVersion: v1
kind: Pod
metadata:
  name: bad-pod
  namespace: default
spec:
  hostPID: true
---
apiVersion: v1
kind: Pod
metadata:
  name: good-pod
  namespace: default
spec:
  hostPID: false
"""


@pytest.fixture()
def manifest_dir(tmp_path):
    d = tmp_path / "manifests"
    d.mkdir()
    (d / "all.yaml").write_text(MANIFESTS)
    return str(d)


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def test_audit_once_end_to_end(manifest_dir):
    proc = subprocess.run(
        [sys.executable, "-m", "gatekeeper_tpu", "--manifests", manifest_dir,
         "--once"],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "1 violations" in proc.stderr or ", 1 violations" in proc.stderr, \
        proc.stderr[-500:]
    assert "bad-pod" in proc.stdout and "host namespace" in proc.stdout
    assert "good-pod" not in proc.stdout


def test_audit_once_incomplete_exits_nonzero(manifest_dir, tmp_path):
    """A one-shot audit whose device dispatches all fail drops its
    chunks: the run is marked incomplete AND the process says so with
    its exit code (a pass without verdicts is not a success)."""
    spec = tmp_path / "chaos.json"
    spec.write_text(json.dumps({"seed": 0, "faults": [
        {"site": "device.dispatch", "mode": "error"}]}))
    proc = subprocess.run(
        [sys.executable, "-m", "gatekeeper_tpu", "--manifests", manifest_dir,
         "--once", "--chaos", str(spec)],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=_env(),
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "[INCOMPLETE" in proc.stderr, proc.stderr[-2000:]
    assert "bad-pod" not in proc.stdout


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_webhook_serve_admit_and_sigterm(manifest_dir):
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "gatekeeper_tpu", "--manifests", manifest_dir,
         "--operation", "webhook", "--port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO, env=_env(),
    )
    try:
        deadline = time.time() + 120
        up = False
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=2) as r:
                    up = r.status == 200
                    break
            except Exception:
                if proc.poll() is not None:
                    raise AssertionError(proc.stderr.read()[-2000:])
                time.sleep(0.5)
        assert up, "webhook never became ready"

        review = {"request": {
            "uid": "u1", "operation": "CREATE",
            "kind": {"kind": "Pod", "version": "v1"},
            "object": {"apiVersion": "v1", "kind": "Pod",
                       "metadata": {"name": "p"},
                       "spec": {"hostPID": True}},
        }}
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/admit",
            data=json.dumps(review).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = json.load(r)
        resp = body["response"]
        assert resp["allowed"] is False
        assert "host namespace" in resp["status"]["message"]

        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
        assert proc.returncode == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

"""The plain reference, in JAX-free children of their own.

    python benchmark/reference.py <job.json>

A child builds the exact interpreter alone (``wiring.interpreter_client``:
RegoDriver + CELDriver, no TpuDriver), syncs the same inventory, and reviews
its share of the inputs one at a time.  The parent starts the children
before it touches JAX and joins them before the window opens, so they cost
the measured window nothing and the chip never sees them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def audit_results(client, lines) -> list:
    """[corpus index, [[constraint kind, constraint name, message], ...]]
    per sample line: every result of the exact review, under the audit's
    enforcement point."""
    from gatekeeper_tpu.apis.constraints import AUDIT_EP
    from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
    from gatekeeper_tpu.target.review import AugmentedUnstructured

    out = []
    for line in lines:
        idx, _, raw = line.partition(b"\t")
        resp = client.review(
            AugmentedUnstructured(object=json.loads(raw),
                                  source=SOURCE_ORIGINAL),
            enforcement_point=AUDIT_EP)
        rows = []
        for r in resp.results():
            con = r.constraint or {}
            rows.append([con.get("kind"),
                         (con.get("metadata") or {}).get("name"), r.msg])
        out.append([int(idx), sorted(rows)])
    return out


def admit_digests(client, namespaces: dict, lines) -> list:
    """The digest of the answer the webhook owes each AdmissionReview."""
    from benchmark import answers
    from gatekeeper_tpu.webhook.policy import ValidationHandler

    handler = ValidationHandler(client, namespace_lookup=namespaces.get)
    return [answers.of_validation(handler.handle(json.loads(line)))
            for line in lines]


def main(job_path: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import cluster, wiring

    with open(job_path) as f:
        job = json.load(f)
    config = job["config"]
    client = wiring.interpreter_client(config)
    for path in job["inventory"]:
        with open(path, "rb") as f:
            for line in f:
                client.add_data(json.loads(line))
    with open(job["input"], "rb") as f:
        lines = [ln.rstrip(b"\n") for ln in f][job["part"]::job["parts"]]
    if job["mode"] == "audit":
        out = audit_results(client, lines)
    else:
        out = admit_digests(
            client, cluster.Cluster(config["cluster"], config["objects"],
                                    job["seed"]).namespace_objects(), lines)
    if "jax" in sys.modules:
        raise RuntimeError("the reference child imported jax")
    with open(job["output"], "w") as f:
        json.dump(out, f)
    return 0


class Children:
    """The reference children of one run: started early, joined late.
    ``spawn`` is the run's (``harness.Run.spawn``), which also sees to it
    that none outlives the run."""

    def __init__(self, spawn, config: dict, mode: str, seed: int,
                 inventory: list, input_path: str, work_dir: str, parts: int,
                 tag: str = "reference"):
        self.outputs = []
        self.procs = []
        for part in range(parts):
            job = os.path.join(work_dir, f"{tag}.{part}.job.json")
            out = os.path.join(work_dir, f"{tag}.{part}.out.json")
            with open(job, "w") as f:
                json.dump({"config": config, "mode": mode, "seed": seed,
                           "inventory": inventory, "input": input_path,
                           "part": part, "parts": parts, "output": out}, f)
            self.outputs.append(out)
            self.procs.append(spawn([os.path.abspath(__file__), job],
                                    stdout=subprocess.DEVNULL))

    def join(self, timeout: float = 300.0) -> list:
        """Each child's output, in part order.  Raises if one failed."""
        for p in self.procs:
            if p.wait(timeout=timeout) != 0:
                raise RuntimeError(f"reference child exited {p.returncode}")
        outs = []
        for path in self.outputs:
            with open(path) as f:
                outs.append(json.load(f))
        return outs


def by_index(parts: list) -> dict:
    """{index: {(constraint kind, constraint name): [messages]}} of audit
    children's outputs, an entry for every line reviewed."""
    out: dict = {}
    for part in parts:
        for idx, rows in part:
            per = out.setdefault(idx, {})
            for kind, name, msg in rows:
                per.setdefault((kind, name), []).append(msg)
    return out


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

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


def audit_results(client, lines, expansion=None) -> list:
    """[corpus index, [[constraint kind, constraint name, message], ...]]
    per sample line: every result of the exact review, under the audit's
    enforcement point.  With ``expansion`` (``expansion_reference.
    Expansion``), a line that some template applies to also gets what it
    implies, as a third item: [[[kind, namespace, name], [[constraint kind,
    constraint name, message, enforcement action], ...]], ...], one entry a
    resultant in upstream's order (``implied_results``)."""
    from gatekeeper_tpu.apis.constraints import AUDIT_EP
    from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
    from gatekeeper_tpu.target.review import AugmentedUnstructured

    out = []
    for line in lines:
        idx, _, raw = line.partition(b"\t")
        obj = json.loads(raw)
        resp = client.review(
            AugmentedUnstructured(object=obj, source=SOURCE_ORIGINAL),
            enforcement_point=AUDIT_EP)
        rows = []
        for r in resp.results():
            con = r.constraint or {}
            rows.append([con.get("kind"),
                         (con.get("metadata") or {}).get("name"), r.msg])
        entry = [int(idx), sorted(rows)]
        if expansion is not None and expansion.generates(obj):
            entry.append(implied_results(client, expansion, obj))
        out.append(entry)
    return out


def implied_results(client, expansion, obj: dict) -> list:
    """What upstream's audit adds to a generator object's review
    (``pkg/audit/manager.go``, expand + review resultants + aggregate):
    ``expansion_reference``'s resultants, each reviewed by the interpreter
    as Generated, each result under ``[Implied by <template>]`` and the
    template's enforcementAction where it sets one.  Nothing of the
    program's expansion or mutation code runs here."""
    from benchmark.expansion_reference import IMPLIED_PREFIX
    from gatekeeper_tpu.apis.constraints import AUDIT_EP
    from gatekeeper_tpu.match.match import SOURCE_GENERATED
    from gatekeeper_tpu.target.review import AugmentedUnstructured

    out = []
    for template, res in expansion.expand(obj):
        resp = client.review(
            AugmentedUnstructured(object=res, source=SOURCE_GENERATED),
            enforcement_point=AUDIT_EP)
        prefix = IMPLIED_PREFIX % template.name
        rows = []
        for r in resp.results():
            con = r.constraint or {}
            rows.append([con.get("kind"),
                         (con.get("metadata") or {}).get("name"),
                         f"{prefix} {r.msg}",
                         template.action or r.enforcement_action])
        meta = res["metadata"]
        out.append([[res["kind"], meta.get("namespace", ""), meta["name"]],
                    sorted(rows)])
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
    from benchmark import cluster, expansion_reference, wiring

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
        expansion = None
        if config["audit"].get("expand_generated"):
            expansion = expansion_reference.Expansion(config["expansion"])
        out = audit_results(client, lines, expansion)
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
        for idx, rows, *_implied in part:
            per = out.setdefault(idx, {})
            for kind, name, msg in rows:
                per.setdefault((kind, name), []).append(msg)
    return out


def implied_by_index(parts: list) -> dict:
    """{index: [((kind, namespace, name), {(constraint kind, constraint
    name): [(message, enforcement action)]}), ...]} of the generator lines
    among audit children's outputs: what each implies, resultant by
    resultant, in expansion order."""
    out: dict = {}
    for part in parts:
        for idx, _rows, *implied in part:
            if not implied:
                continue
            entries = out.setdefault(idx, [])
            for ident, rows in implied[0]:
                per: dict = {}
                for kind, name, msg, action in rows:
                    per.setdefault((kind, name), []).append((msg, action))
                entries.append((tuple(ident), per))
    return out


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

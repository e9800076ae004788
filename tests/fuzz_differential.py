"""Extended differential fuzzing: lowered programs vs the interpreter.

Not part of the default pytest run (no test_ prefix) — invoke manually:

    python tests/fuzz_differential.py [n_objects] [seeds...]

Generates randomized object populations against every library policy and
asserts verdict-set equality between TpuDriver.query_batch and the exact
interpreter, printing a summary per seed.  Exit 1 on any divergence.
"""

import os
import random
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from gatekeeper_tpu.apis.constraints import Constraint  # noqa: E402
from gatekeeper_tpu.apis.templates import ConstraintTemplate  # noqa: E402
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver  # noqa: E402
# the seeded object generator moved to the shared corpus module (ISSUE 17)
# so this manual fuzzer, tests/test_fuzz.py, and the soak harness draw
# identical populations per seed; re-exported here for callers that
# imported it from this module
from gatekeeper_tpu.fuzz.corpus import (IMAGES, VALUES,  # noqa: E402,F401
                                        rand_obj, rand_value)
from gatekeeper_tpu.target.review import AugmentedUnstructured  # noqa: E402
from gatekeeper_tpu.target.target import K8sValidationTarget  # noqa: E402
from gatekeeper_tpu.utils.unstructured import load_yaml_file  # noqa: E402

LIB = os.path.join(os.path.dirname(__file__), "..", "library", "general")
LIB_PSP = os.path.join(os.path.dirname(__file__), "..", "library",
                       "pod-security-policy")
TARGET = "admission.k8s.gatekeeper.sh"


def build_fuzz_driver():
    """(tpu, constraints): the full library incl. CEL templates on a
    unified TpuDriver, with referential inventory seeded."""

    from gatekeeper_tpu.drivers.cel_driver import CELDriver

    tpu = TpuDriver(batch_bucket=64, cel_driver=CELDriver())
    constraints = []
    entries = [os.path.join(LIB, n) for n in sorted(os.listdir(LIB))] + \
        [os.path.join(LIB_PSP, n) for n in sorted(os.listdir(LIB_PSP))]
    for entry in entries:
        t = ConstraintTemplate.from_unstructured(
            load_yaml_file(os.path.join(entry, "template.yaml"))[0])
        tpu.add_template(t)
        constraints.append(Constraint.from_unstructured(load_yaml_file(
            os.path.join(entry, "samples", "constraint.yaml"))[0]))
    # cluster-scope referential coverage (storageclass joins)
    for nm in ("standard", "fast"):
        tpu.add_data(
            TARGET, ["cluster", "storage.k8s.io/v1", "StorageClass", nm],
            {"apiVersion": "storage.k8s.io/v1", "kind": "StorageClass",
             "metadata": {"name": nm}})
    # referential coverage: seed the inventory with ingresses sharing
    # hosts/names/namespaces with the generated review objects
    inv_rng = random.Random(991)
    for i in range(25):
        ns = inv_rng.choice(["default", "prod", "kube-system"])
        name = inv_rng.choice([f"o{j}" for j in range(40)] + ["inv-only"])
        hosts = [inv_rng.choice(["a.com", "b.com", "", "inv.com"])
                 for _ in range(inv_rng.randint(0, 2))]
        tpu.add_data(
            TARGET, ["namespace", ns, "networking.k8s.io/v1", "Ingress",
                     f"{name}-{i}" if inv_rng.random() < 0.5 else name],
            {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
             "metadata": {"name": name, "namespace": ns},
             "spec": {"rules": [{"host": h} for h in hosts]}})
    assert not tpu.fallback_kinds(), (
        "library templates fell back to the interpreter — the fuzz would "
        f"compare the oracle to itself: {tpu.fallback_kinds()}")
    return tpu, constraints


def oracle_results(tpu, con, review):
    """The exact engine for one (constraint, review): the CEL evaluator
    for CEL-owned kinds, the Rego interpreter otherwise."""
    if con.kind in tpu._cel_kinds:
        return tpu._cel.query(TARGET, [con], review).results
    return tpu._interp.query(TARGET, [con], review).results


def run_fuzz(n, seeds, quiet=False, tpu=None, constraints=None):
    """Differential fuzz: returns the number of diverging objects."""
    if tpu is None or constraints is None:
        tpu, constraints = build_fuzz_driver()
    if not quiet:
        print(f"templates: {len(constraints)} "
              f"({len(tpu.lowered_kinds())} lowered)")

    target = K8sValidationTarget()
    failures = 0
    for seed in seeds:
        rng = random.Random(seed)
        objs = [rand_obj(rng, i) for i in range(n)]
        reviews = [target.handle_review(AugmentedUnstructured(object=o))
                   for o in objs]
        got = tpu.query_batch(TARGET, constraints, reviews)
        # raw grid lane: render_messages=False keeps every device hit as a
        # Result — the rendered lane re-checks hits through the exact
        # engine, which would MASK false-positive lowering bugs (the grid
        # drives audit totals, so its hits must be exact both ways)
        raw = tpu.query_batch(TARGET, constraints, reviews,
                              render_messages=False)
        mismatches = 0
        for oi, review in enumerate(reviews):
            expected = []
            exp_hit_kinds = set()
            for con in constraints:
                if not target.to_matcher(con.match).match(review):
                    continue
                results = oracle_results(tpu, con, review)
                expected.extend(results)
                if results:
                    exp_hit_kinds.add(con.name)
            key = lambda r: (r.constraint["metadata"]["name"], r.msg)
            raw_hits = {r.constraint["metadata"]["name"]
                        for r in raw[oi].results}
            ok_rendered = sorted(map(key, got[oi].results)) == sorted(
                map(key, expected))
            ok_raw = raw_hits == exp_hit_kinds
            if not (ok_rendered and ok_raw):
                mismatches += 1
                if mismatches <= 3:
                    print(f"  DIVERGENCE seed={seed} obj={oi}: {objs[oi]}")
                    if not ok_rendered:
                        print(f"    got:  {sorted(map(key, got[oi].results))}")
                        print(f"    want: {sorted(map(key, expected))}")
                    if not ok_raw:
                        print(f"    raw grid hits: {sorted(raw_hits)}")
                        print(f"    oracle hits:   {sorted(exp_hit_kinds)}")
        total = sum(len(g.results) for g in got)
        status = "OK" if mismatches == 0 else f"{mismatches} MISMATCHES"
        if not quiet or mismatches:
            print(f"seed {seed}: {n} objects, {total} violations -> {status}")
        failures += mismatches
    return failures


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 400
    seeds = [int(s) for s in sys.argv[2:]] or [0, 1, 2, 3, 4]
    return 1 if run_fuzz(n, seeds) else 0


if __name__ == "__main__":
    sys.exit(main())

"""``ir/masks.py:constraint_masks`` against ``match.matches``, cell for
cell, and against the loop it replaced (``tests/masks_reference.py``), on
seeded match blocks over seeded objects: each matcher is a case."""

from __future__ import annotations

import random

import numpy as np
import pytest

from gatekeeper_tpu.apis.constraints import Constraint
from gatekeeper_tpu.ir import masks as masks_mod
from gatekeeper_tpu.match.match import Matchable, matches
from gatekeeper_tpu.ops.flatten import Flattener, Schema, Vocab
from tests.masks_reference import constraint_masks_loop

NAMESPACES = ["ns-1", "ns-12", "ns-19", "ns-190", "kube-system", "default",
              "prod-a", "prod-b", "x"]
NS_PATTERNS = NAMESPACES + ["ns-1*", "*-system", "*od-*", "ns-*", "*",
                            "absent", "kube-*", "*b"]
NAME_PATTERNS = ["obj-3", "obj-1*", "*-7", "*bj-2*", "ns-1", "ns-*",
                 "nobody", "*"]
KINDS = [("", "v1", "Pod"), ("", "v1", "Service"), ("", "v1", "Namespace"),
         ("apps", "apps/v1", "Deployment"),
         ("networking.k8s.io", "networking.k8s.io/v1", "Ingress"),
         ("extensions", "extensions/v1beta1", "Ingress"),
         ("rbac.authorization.k8s.io", "rbac.authorization.k8s.io/v1",
          "ClusterRoleBinding"),
         # a Namespace of another group is no Namespace object
         ("example.io", "example.io/v1", "Namespace")]
KIND_BLOCKS = [
    [{"apiGroups": [""], "kinds": ["Pod"]}],
    [{"apiGroups": ["*"], "kinds": ["*"]}],
    [{"apiGroups": ["networking.k8s.io", "extensions"],
      "kinds": ["Ingress"]}],
    [{"apiGroups": [""], "kinds": ["Pod", "Service"]},
     {"apiGroups": ["apps"], "kinds": ["Deployment"]}],
    [{"kinds": ["Namespace"]}],
    [{"apiGroups": ["apps"]}],
    [{"apiGroups": [""], "kinds": ["NeverSeen"]}],
    [{"apiGroups": ["rbac.authorization.k8s.io"],
      "kinds": ["ClusterRoleBinding", "RoleBinding"]}],
]


def some(rng, pool, lo=1, hi=4):
    return rng.sample(pool, rng.randint(lo, min(hi, len(pool))))


MATCHERS = {
    "empty": lambda r: {},
    "kinds": lambda r: {"kinds": r.choice(KIND_BLOCKS)},
    "namespaces_exact": lambda r: {"namespaces": some(r, NAMESPACES)},
    "namespaces_glob": lambda r: {"namespaces": some(r, NS_PATTERNS)},
    "excluded_exact": lambda r: {"excludedNamespaces": some(r, NAMESPACES)},
    "excluded_glob": lambda r: {"excludedNamespaces": some(r, NS_PATTERNS)},
    "namespaces_and_excluded": lambda r: {
        "namespaces": some(r, NS_PATTERNS),
        "excludedNamespaces": some(r, NS_PATTERNS)},
    "name": lambda r: {"name": r.choice(NAME_PATTERNS)},
    "all_four": lambda r: {
        "kinds": r.choice(KIND_BLOCKS), "namespaces": some(r, NS_PATTERNS),
        "excludedNamespaces": some(r, NS_PATTERNS, 1, 2),
        "name": r.choice(NAME_PATTERNS)},
    # an object's structure decides: tables too since PR 32
    # (tests/test_masks_selectors.py has the selectors' own cases)
    "labelSelector": lambda r: {
        "labelSelector": {"matchLabels": {"app": r.choice("abc")}},
        "namespaces": some(r, NS_PATTERNS)},
    "scope": lambda r: {"scope": r.choice(["Cluster", "Namespaced", "*"]),
                        "kinds": r.choice(KIND_BLOCKS)},
    "source": lambda r: {"source": r.choice(["All", "Original",
                                             "Generated"])},
}
SELECTOR = {"labelSelector"}


def make_objects(rng, n: int, generate_name: bool = False) -> list:
    objs = []
    for i in range(n):
        group, api, kind = rng.choice(KINDS)
        meta: dict = {"name": f"obj-{i}"}
        if kind == "Namespace" and group == "":
            meta["name"] = rng.choice(NAMESPACES + [f"ns-x{i}"])
        elif kind != "ClusterRoleBinding" and rng.random() < 0.85:
            meta["namespace"] = rng.choice(NAMESPACES)
        if rng.random() < 0.5:
            meta["labels"] = {"app": rng.choice("abc")}
        if generate_name and kind != "Namespace" and rng.random() < 0.3:
            # (a Namespace object without a name is the loop's known
            # fault: it has a test of its own below)
            del meta["name"]
            meta["generateName"] = rng.choice(["obj-1", "web-", "ns-"])
        objs.append({"apiVersion": api, "kind": kind, "metadata": meta})
    return objs


def make_constraints(rng, matcher: str, n: int = 12) -> list:
    return [Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1", "kind": "K8sX",
        "metadata": {"name": f"c{i}"},
        "spec": {"match": MATCHERS[matcher](rng)}}) for i in range(n)]


def oracle(cons, objs, pad_n, namespaces=None, sources=None) -> np.ndarray:
    want = np.zeros((len(cons), pad_n), bool)
    for ci, con in enumerate(cons):
        for oi, obj in enumerate(objs):
            want[ci, oi] = matches(con.match, Matchable(
                obj=obj, namespace=namespaces[oi] if namespaces else None,
                source=sources[oi] if sources else ""))
    return want


def flat(objs, pad_n=None):
    vocab = Vocab()
    return Flattener(Schema(), vocab).flatten(objs, pad_n=pad_n), vocab


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("matcher", sorted(MATCHERS))
def test_masks_equal_the_oracle_and_the_loop(matcher, seed):
    rng = random.Random(f"{matcher}:{seed}")
    objs = make_objects(rng, 150)
    cons = make_constraints(rng, matcher)
    sources = ([rng.choice(["Original", "Generated"]) for _ in objs]
               if matcher == "source" else None)
    batch, vocab = flat(objs, pad_n=256)  # 106 pad rows
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, objs,
                                     sources=sources, counts=counts)
    assert got.shape == (12, 256) and got.dtype == np.bool_
    assert not got[:, 150:].any()
    np.testing.assert_array_equal(got, oracle(cons, objs, 256,
                                              sources=sources))
    np.testing.assert_array_equal(got, constraint_masks_loop(
        cons, batch, vocab, objs, sources=sources))
    assert (counts["rows_predicate"], counts["rows_vectorized"]) == (0, 12)
    assert counts["rows_selector"] == (12 if matcher in SELECTOR else 0)


@pytest.mark.parametrize("matcher", ["name", "all_four", "namespaces_glob"])
def test_generate_name_sends_name_rows_to_the_predicate(matcher):
    rng = random.Random(f"gen:{matcher}")
    objs = make_objects(rng, 120, generate_name=True)
    cons = make_constraints(rng, matcher)
    batch, vocab = flat(objs)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, objs, counts=counts)
    np.testing.assert_array_equal(got, oracle(cons, objs, 120))
    np.testing.assert_array_equal(got, constraint_masks_loop(
        cons, batch, vocab, objs))
    named = sum(1 for c in cons if c.match.get("name"))
    assert counts["rows_predicate"] == named
    assert counts["rows_vectorized"] == 12 - named


@pytest.mark.parametrize("matcher", ["namespaces_exact", "namespaces_glob",
                                     "excluded_glob", "all_four",
                                     "namespaceSelector"])
def test_a_provided_namespace_object_goes_before_metadata_namespace(matcher):
    """Admission: the review's Namespace object names the effective
    namespace (match.go:162-163), an empty name included; the table path
    answers it without the predicate."""
    rng = random.Random(f"adm:{matcher}")
    objs = make_objects(rng, 64)
    namespaces = []
    for obj in objs:
        r = rng.random()
        name = (obj["metadata"].get("namespace", "other") if r < 0.5
                else rng.choice(NAMESPACES + [""]))
        namespaces.append(None if r < 0.2 and "namespace" not in
                          obj["metadata"] else
                          {"apiVersion": "v1", "kind": "Namespace",
                           "metadata": {"name": name,
                                        "labels": {"app": rng.choice("ab")}}})
    if matcher == "namespaceSelector":
        # a namespaced object without its Namespace object is an error of
        # the oracle's, not a verdict
        namespaces = [ns or {"metadata": {"name": "default"}}
                      for ns in namespaces]
        cons = [Constraint.from_unstructured({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sX", "metadata": {"name": f"c{i}"},
            "spec": {"match": {
                "namespaceSelector": {"matchLabels": {"app": "a"}},
                "excludedNamespaces": some(rng, NS_PATTERNS)}}})
            for i in range(4)]
    else:
        cons = make_constraints(rng, matcher)
    batch, vocab = flat(objs)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, objs, namespaces,
                                     counts=counts)
    np.testing.assert_array_equal(got, oracle(cons, objs, 64, namespaces))
    np.testing.assert_array_equal(got, constraint_masks_loop(
        cons, batch, vocab, objs, namespaces))
    assert counts["rows_predicate"] == 0
    assert counts["rows_selector"] == (
        len(cons) if matcher == "namespaceSelector" else 0)


def test_a_namespace_object_without_a_name_is_tested_against_the_empty_name():
    """Where the loop and the oracle disagreed: the table path follows
    the oracle."""
    objs = [{"apiVersion": "v1", "kind": "Namespace", "metadata": {}},
            {"apiVersion": "v1", "kind": "Namespace",
             "metadata": {"name": "ns-1"}},
            {"apiVersion": "rbac.authorization.k8s.io/v1",
             "kind": "ClusterRole", "metadata": {"name": "view"}}]
    cons = [Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1", "kind": "K8sX",
        "metadata": {"name": f"c{i}"}, "spec": {"match": m}})
        for i, m in enumerate([{"namespaces": ["ns-1"]},
                               {"excludedNamespaces": ["ns-*"]},
                               {"namespaces": ["*"]}])]
    batch, vocab = flat(objs)
    got = masks_mod.constraint_masks(cons, batch, vocab, objs)
    np.testing.assert_array_equal(got, oracle(cons, objs, 3))
    assert got.tolist() == [[False, True, True], [True, False, True],
                            [True, True, True]]
    assert constraint_masks_loop(cons, batch, vocab, objs)[0, 0]


def test_no_objects_and_no_constraints():
    batch, vocab = flat([], pad_n=8)
    cons = make_constraints(random.Random(0), "namespaces_glob", 3)
    assert not masks_mod.constraint_masks(cons, batch, vocab, []).any()
    objs = make_objects(random.Random(0), 5)
    batch, vocab = flat(objs)
    assert masks_mod.constraint_masks([], batch, vocab, objs).shape == (0, 5)


def test_a_wide_group_costs_no_python_per_object():
    """500 namespace-scoped constraints over 20,000 objects: the table is
    constraints x distinct values, so the wildcard is asked once per
    distinct glob and namespace, never once per cell."""
    from gatekeeper_tpu.match import wildcard

    rng = random.Random(7)
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}",
                          "namespace": f"ns-{rng.randrange(200)}"}}
            for i in range(20_000)]
    cons = [Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1", "kind": "K8sX",
        "metadata": {"name": f"c{i}"}, "spec": {"match": {
            "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
            "namespaces": ([f"ns-{i % 50 + 50 * j}" for j in range(4)]
                           if i % 4 else [f"ns-{1 + i % 19}*"]),
            "excludedNamespaces": ["kube-system", "ns-19*"]}}})
        for i in range(500)]
    batch, vocab = flat(objs)
    asked = []
    real = wildcard.matches

    def counting(pattern, candidate):
        asked.append(pattern)
        return real(pattern, candidate)

    wildcard.matches = counting
    try:
        got = masks_mod.constraint_masks(cons, batch, vocab, objs)
    finally:
        wildcard.matches = real
    # once per distinct glob and distinct namespace, of 10M cells; an
    # exact name is looked up
    assert len(asked) == len(set(asked)) * 200
    assert set(asked) == {f"ns-{i}*" for i in range(1, 20)}
    for ci in (0, 1, 2, 3, 250, 499):
        want = [matches(cons[ci].match, Matchable(obj=o)) for o in objs]
        assert got[ci].tolist() == want

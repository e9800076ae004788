"""``ir/masks.py:constraint_masks`` on the matchers that need an object's
structure (``labelSelector``, ``namespaceSelector``, ``scope``, ``source``)
beside the other four, against ``match.matches`` cell for cell and against
the loop of ``tests/masks_reference.py``: seeded clusters with labelled
objects and labelled Namespaces, every operator, both call shapes (the
sweep's lookup by ``metadata.namespace``, the admission path's Namespace per
object), both flatten lanes (label columns from the raw-JSON lane, labels
read from the objects on the dict lane), and the inputs on which the oracle
raises, where the masks must raise the same."""

from __future__ import annotations

import json
import random

import numpy as np
import pytest

from gatekeeper_tpu.apis.constraints import Constraint
from gatekeeper_tpu.ir import masks as masks_mod
from gatekeeper_tpu.match.match import Matchable, MatchError, matches
from gatekeeper_tpu.ops import native
from gatekeeper_tpu.ops.flatten import Flattener, Schema, Vocab
from gatekeeper_tpu.utils.rawjson import RawJSON
from tests.masks_reference import constraint_masks_loop

TENANTS = [f"t{i}" for i in range(6)]
ENVS = ["prod", "staging", "dev"]
TIERS = ["frontend", "backend", "batch"]
KINDS = [("v1", "Pod", True), ("v1", "Service", True),
         ("apps/v1", "Deployment", True),
         ("networking.k8s.io/v1", "Ingress", True),
         ("v1", "Namespace", False),
         ("rbac.authorization.k8s.io/v1", "ClusterRoleBinding", False),
         ("example.io/v1", "Namespace", True)]  # no Namespace object
KIND_BLOCKS = [
    [{"apiGroups": [""], "kinds": ["Pod"]}],
    [{"apiGroups": ["*"], "kinds": ["*"]}],
    [{"apiGroups": [""], "kinds": ["Namespace"]}],
    [{"apiGroups": ["", "apps"], "kinds": ["Service", "Deployment"]}],
]
LANES = ["dict"] + (["raw"] if native.load_json() is not None else [])


def namespace_objects(rng, n: int = 24) -> dict:
    out = {}
    for i in range(n):
        labels = {"tenant": TENANTS[i % len(TENANTS)]}
        if rng.random() < 0.8:
            labels["env"] = rng.choice(ENVS)
        if rng.random() < 0.15:
            labels["policy.example.com/exempt"] = "true"
        meta = {"name": f"ns-{i}"}
        if rng.random() < 0.9:  # a Namespace without labels at all
            meta["labels"] = labels
        out[f"ns-{i}"] = {"apiVersion": "v1", "kind": "Namespace",
                          "metadata": meta}
    return out


def make_objects(rng, ns_objs: dict, n: int,
                 generate_name: bool = False) -> list:
    objs = []
    names = sorted(ns_objs)
    for i in range(n):
        api, kind, namespaced = rng.choice(KINDS)
        meta: dict = {"name": f"obj-{i}"}
        if kind == "Namespace" and api == "v1":
            # the cluster's own Namespace objects, as listed: selected on
            # their own labels, which need not be the synced ones
            src = ns_objs[rng.choice(names)]
            meta = json.loads(json.dumps(src["metadata"]))
            if rng.random() < 0.3:
                meta.setdefault("labels", {})["env"] = rng.choice(ENVS)
        elif namespaced:
            meta["namespace"] = rng.choice(names)
        labels = {}
        if rng.random() < 0.8:
            labels["tier"] = rng.choice(TIERS)
        if rng.random() < 0.5:
            labels["app.kubernetes.io/managed-by"] = rng.choice(
                ["helm", "kustomize"])
        if labels and not (kind == "Namespace" and api == "v1"):
            meta["labels"] = labels
        elif rng.random() < 0.1:
            meta["labels"] = rng.choice([{}, None])
        if generate_name and kind == "Pod" and rng.random() < 0.3:
            del meta["name"]
            meta["generateName"] = "obj-1"
        objs.append({"apiVersion": api, "kind": kind, "metadata": meta})
    return objs


def selector(rng, keys: dict) -> dict:
    """A random LabelSelector over ``keys`` (key -> its values): matchLabels
    and each of the four operators."""
    out: dict = {}
    key = rng.choice(sorted(keys))
    form = rng.choice(["labels", "In", "NotIn", "Exists", "DoesNotExist",
                       "both", "two", "empty", "absent_key"])
    if form == "empty":
        return {}
    if form in ("labels", "both"):
        out["matchLabels"] = {key: rng.choice(keys[key])}
    if form == "absent_key":
        out["matchLabels"] = {"never-set": "x"}
    if form in ("In", "NotIn", "both", "two"):
        k2 = rng.choice(sorted(keys))
        out["matchExpressions"] = [{
            "key": k2, "operator": rng.choice(["In", "NotIn"])
            if form in ("both", "two") else form,
            "values": rng.sample(keys[k2], rng.randint(1, len(keys[k2])))}]
    if form in ("Exists", "DoesNotExist"):
        out["matchExpressions"] = [{"key": key, "operator": form}]
    if form == "two":
        out["matchExpressions"].append(
            {"key": rng.choice(sorted(keys)),
             "operator": rng.choice(["Exists", "DoesNotExist"])})
    return out


NS_KEYS = {"tenant": TENANTS, "env": ENVS,
           "policy.example.com/exempt": ["true", "false"]}
OBJ_KEYS = {"tier": TIERS, "app.kubernetes.io/managed-by":
            ["helm", "kustomize", "argo"], "env": ENVS}

MATCHERS = {
    "labelSelector": lambda r: {"labelSelector": selector(r, OBJ_KEYS)},
    "namespaceSelector": lambda r: {
        "namespaceSelector": selector(r, NS_KEYS)},
    "scope": lambda r: {"scope": r.choice(["Cluster", "Namespaced", "*",
                                           "Namespacd", ""])},
    "source": lambda r: {"source": r.choice(["All", "Original",
                                             "Generated", ""])},
    "selectors_and_names": lambda r: {
        "kinds": r.choice(KIND_BLOCKS),
        "excludedNamespaces": ["ns-1*", "kube-system"],
        "namespaceSelector": selector(r, NS_KEYS),
        "labelSelector": selector(r, OBJ_KEYS)},
    "all_eight": lambda r: {
        "kinds": r.choice(KIND_BLOCKS),
        "scope": r.choice(["Cluster", "Namespaced", "*"]),
        "namespaces": ["ns-*", "obj-*"],
        "excludedNamespaces": [r.choice(["ns-2*", "ns-7"])],
        "labelSelector": selector(r, OBJ_KEYS),
        "namespaceSelector": selector(r, NS_KEYS),
        "name": r.choice(["obj-*", "ns-*", "*"]),
        "source": r.choice(["All", "Original"])},
}


def make_constraints(rng, matcher, n: int = 16) -> list:
    make = MATCHERS[matcher] if isinstance(matcher, str) else matcher
    return [Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1", "kind": "K8sX",
        "metadata": {"name": f"c{i}"}, "spec": {"match": make(rng)}})
        for i in range(n)]


def flat(objs, cons, lane: str, pad_n=None):
    """(batch, vocab, the objects as the lane's lister hands them over)."""
    vocab = Vocab()
    keys = masks_mod.selector_label_keys(cons)
    if lane == "raw":
        objs = [RawJSON(json.dumps(o).encode()) for o in objs]
    batch = Flattener(Schema(), vocab, lane=lane,
                      label_keys=keys).flatten(objs, pad_n=pad_n)
    assert (batch.labels is not None) == (lane == "raw" and bool(keys))
    return batch, vocab, objs


def oracle(cons, objs, pad_n, namespaces, sources=None) -> np.ndarray:
    want = np.zeros((len(cons), pad_n), bool)
    for ci, con in enumerate(cons):
        for oi, obj in enumerate(objs):
            want[ci, oi] = matches(con.match, Matchable(
                obj=obj, namespace=namespaces[oi],
                source=sources[oi] if sources else ""))
    return want


def looked_up(objs, ns_objs: dict) -> list:
    """What ``target.Matcher.match`` finds for an audit review: the cached
    Namespace of the object's metadata.namespace."""
    return [ns_objs.get(o["metadata"].get("namespace") or "")
            for o in objs]


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("matcher", sorted(MATCHERS))
def test_the_sweeps_lookup_equals_the_oracle_and_the_loop(matcher, seed,
                                                          lane):
    rng = random.Random(f"{matcher}:{seed}")
    ns_objs = namespace_objects(rng)
    objs = make_objects(rng, ns_objs, 180)
    cons = make_constraints(rng, matcher)
    sources = ([rng.choice(["Original", "Generated"]) for _ in objs]
               if "source" in MATCHERS[matcher](random.Random(0)) else None)
    batch, vocab, listed = flat(objs, cons, lane, pad_n=256)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                     sources=sources, counts=counts,
                                     namespace_of=ns_objs.get)
    assert got.shape == (16, 256) and not got[:, 180:].any()
    namespaces = looked_up(objs, ns_objs)
    np.testing.assert_array_equal(
        got, oracle(cons, objs, 256, namespaces, sources))
    np.testing.assert_array_equal(got, constraint_masks_loop(
        cons, batch, vocab, objs, namespaces, sources))
    assert (counts["rows_predicate"], counts["rows_vectorized"]) == (0, 16)
    selected = sum(any(c.match.get(f) is not None for f in
                       ("labelSelector", "namespaceSelector")) for c in cons)
    assert counts["rows_selector"] == selected
    assert counts["ns_missing"] == 0
    if lane == "raw":
        # matched from the columns: the lister's objects stay unloaded
        assert not any(o._loaded for o in listed)


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("matcher", ["namespaceSelector", "scope",
                                     "selectors_and_names", "all_eight"])
def test_a_reviews_namespace_goes_before_the_lookup(matcher, lane):
    """The admission call shape: a Namespace object per review (which may
    be another than the cached one, or of another name than
    metadata.namespace), None for some, and the lookup for those."""
    rng = random.Random(f"adm:{matcher}")
    ns_objs = namespace_objects(rng)
    other = namespace_objects(random.Random("other"))
    objs = make_objects(rng, ns_objs, 64)
    namespaces = []
    for obj in objs:
        r = rng.random()
        ns = obj["metadata"].get("namespace")
        namespaces.append(None if r < 0.3 else other[ns] if ns and r < 0.6
                          else other[rng.choice(sorted(other))])
    cons = make_constraints(rng, matcher)
    sources = ["Original"] * 64 if matcher == "all_eight" else None
    batch, vocab, listed = flat(objs, cons, lane)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, listed, namespaces,
                                     sources, counts=counts,
                                     namespace_of=ns_objs.get)
    effective = [ns if ns is not None else found
                 for ns, found in zip(namespaces, looked_up(objs, ns_objs))]
    np.testing.assert_array_equal(
        got, oracle(cons, objs, 64, effective, sources))
    np.testing.assert_array_equal(got, constraint_masks_loop(
        cons, batch, vocab, objs, effective, sources))
    assert counts["rows_predicate"] == 0


@pytest.mark.parametrize("field", ["scope", "labelSelector",
                                   "namespaceSelector"])
def test_a_fault_in_the_tables_is_raised_not_handed_to_the_oracle(
        field, monkeypatch):
    """The predicate is for what the oracle decides (its ``MatchError``,
    a selector that is no mapping).  Any other error of the table code
    is a fault of this module: answered per object it would cost
    rows x objects ``match.matches`` calls a chunk and show nowhere but in
    ``rows_predicate``."""
    rng = random.Random("fault")
    ns_objs = namespace_objects(rng)
    objs = make_objects(rng, ns_objs, 40)
    cons = make_constraints(rng, "all_eight", 4)
    batch, vocab, listed = flat(objs, cons, "dict")

    def broken(self, value):
        raise KeyError("a bug in the table code")

    monkeypatch.setattr(masks_mod._Chunk, "_" + field, broken)
    with pytest.raises(KeyError, match="a bug in the table code"):
        masks_mod.constraint_masks(cons, batch, vocab, listed,
                                   sources=["Original"] * 40,
                                   namespace_of=ns_objs.get)


# --- where the oracle raises, the masks raise -------------------------------

def pods(n_ns: int = 4) -> list:
    return [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}", "namespace": f"ns-{i % n_ns}",
                          "labels": {"tier": TIERS[i % 3]}}}
            for i in range(12)]


def one(match: dict) -> list:
    return make_constraints(random.Random(0), lambda r: match, 1)


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("shape", ["lookup", "per_object", "neither"])
def test_a_namespaced_object_without_its_namespace_raises(shape, lane):
    ns_objs = namespace_objects(random.Random(5), 3)  # ns-3 is not synced
    objs = pods()
    cons = one({"namespaceSelector": {"matchLabels": {"tenant": "t0"}}})
    batch, vocab, listed = flat(objs, cons, lane)
    kwargs = {"lookup": {"namespace_of": ns_objs.get},
              "per_object": {"namespaces": looked_up(objs, ns_objs)},
              "neither": {}}[shape]
    counts: dict = {}
    with pytest.raises(MatchError, match="missing Namespace"):
        masks_mod.constraint_masks(cons, batch, vocab, listed,
                                   counts=counts, **kwargs)
    with pytest.raises(MatchError, match="missing Namespace"):
        constraint_masks_loop(cons, batch, vocab, objs,
                              looked_up(objs, ns_objs)
                              if shape != "neither" else None)


@pytest.mark.parametrize("lane", LANES)
def test_a_missing_namespace_an_earlier_matcher_excludes_is_no_error(lane):
    """``matches`` stops at its first False: the objects of ns-3 are not
    of the constraint's kind, or are excluded by name, so their missing
    Namespace is never asked for.  Never a silent True or False: the row
    is the oracle's, object for object."""
    ns_objs = namespace_objects(random.Random(5), 3)
    objs = pods() + [{"apiVersion": "v1", "kind": "Service",
                      "metadata": {"name": "s", "namespace": "ns-9"}}]
    cons = one({"excludedNamespaces": ["ns-3"],
                "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
                "namespaceSelector": {"matchExpressions": [
                    {"key": "tenant", "operator": "In",
                     "values": ["t0", "t1"]}]}})
    batch, vocab, listed = flat(objs, cons, lane)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                     counts=counts,
                                     namespace_of=ns_objs.get)
    np.testing.assert_array_equal(
        got, oracle(cons, objs, 13, looked_up(objs, ns_objs)))
    assert got.any() and not got[0, 12]
    # 3 Pods of ns-3 and the Service: counted, and the row is the predicate's
    assert counts["ns_missing"] == 4
    assert (counts["rows_predicate"], counts["rows_selector"]) == (1, 0)


@pytest.mark.parametrize("field", ["labelSelector", "namespaceSelector"])
def test_an_invalid_operator_raises(field):
    ns_objs = namespace_objects(random.Random(5), 4)
    objs = pods()
    bad = {"matchExpressions": [{"key": "tier", "operator": "Within",
                                 "values": ["backend"]}]}
    cons = one({field: bad})
    batch, vocab, listed = flat(objs, cons, LANES[-1])
    with pytest.raises(MatchError, match="invalid labelSelector operator"):
        masks_mod.constraint_masks(cons, batch, vocab, listed,
                                   namespace_of=ns_objs.get)
    # behind a matchLabels that already says no, the oracle never reads
    # the operator, and neither does the row
    cons = one({field: dict(bad, matchLabels={"tier": "nobody",
                                              "tenant": "nobody"})})
    got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                     namespace_of=ns_objs.get)
    assert not got.any()
    np.testing.assert_array_equal(
        got, oracle(cons, objs, 12, looked_up(objs, ns_objs)))


@pytest.mark.parametrize("match,sources,message", [
    ({"source": "Sideways"}, ["Original"] * 12, "invalid source field"),
    ({"source": "Original"}, None, "source field not specified"),
    ({"source": "Generated"}, ["Original"] * 11 + ["Odd"],
     "invalid source field"),
])
def test_an_invalid_source_raises(match, sources, message):
    objs = pods()
    cons = one(match)
    batch, vocab, listed = flat(objs, cons, "dict")
    with pytest.raises(MatchError, match=message):
        masks_mod.constraint_masks(cons, batch, vocab, listed,
                                   sources=sources)


def test_a_scope_typo_matches_everything():
    rng = random.Random("typo")
    ns_objs = namespace_objects(rng)
    objs = make_objects(rng, ns_objs, 60)
    cons = one({"scope": "Namespacd"}) + one({"scope": "Namespaced"})
    batch, vocab, listed = flat(objs, cons, "dict")
    got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                     namespace_of=ns_objs.get)
    assert got[0].all() and not got[1].all() and got[1].any()


@pytest.mark.parametrize("lane", LANES)
def test_labels_that_are_no_strings_take_the_oracles_word(lane):
    """What no apiserver stores, but a file may hold: the label columns
    say so, and the row is the predicate's."""
    objs = pods()
    objs[3]["metadata"]["labels"] = {"tier": 7}
    objs[4]["metadata"]["labels"] = {"tier": None}
    objs[5]["metadata"]["labels"] = {"tier": ["backend"]}
    cons = one({"labelSelector": {"matchExpressions": [
        {"key": "tier", "operator": "NotIn", "values": ["backend", 7]}]}})
    batch, vocab, listed = flat(objs, cons, lane)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                     counts=counts)
    np.testing.assert_array_equal(got, oracle(cons, objs, 12, [None] * 12))
    assert counts["rows_predicate"] == 1


@pytest.mark.parametrize("lane", LANES)
def test_generate_name_beside_a_selector(lane):
    """``name`` with a generateName in the chunk is the predicate's, with
    the lookup's Namespaces; the rows without ``name`` stay the tables'."""
    rng = random.Random("gen")
    ns_objs = namespace_objects(rng)
    objs = make_objects(rng, ns_objs, 120, generate_name=True)
    named = make_constraints(rng, lambda r: {
        "name": r.choice(["obj-1*", "obj-1", "*"]),
        "namespaceSelector": selector(r, NS_KEYS),
        "labelSelector": selector(r, OBJ_KEYS)}, 6)
    plain = make_constraints(rng, "selectors_and_names", 6)
    cons = named + plain
    batch, vocab, listed = flat(objs, cons, lane)
    counts: dict = {}
    got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                     counts=counts,
                                     namespace_of=ns_objs.get)
    namespaces = looked_up(objs, ns_objs)
    np.testing.assert_array_equal(got, oracle(cons, objs, 120, namespaces))
    np.testing.assert_array_equal(got, constraint_masks_loop(
        cons, batch, vocab, objs, namespaces))
    assert (counts["rows_predicate"], counts["rows_vectorized"],
            counts["rows_selector"]) == (6, 6, 6)


def test_a_selector_is_asked_once_per_distinct_label_set():
    """20,000 Pods over 200 namespaces under 300 selector rows: the
    selectors are evaluated on the distinct Namespaces and label sets,
    never once per object."""
    from gatekeeper_tpu.match import match as match_mod

    rng = random.Random(9)
    ns_objs = {f"ns-{i}": {
        "apiVersion": "v1", "kind": "Namespace", "metadata": {
            "name": f"ns-{i}", "labels": {"tenant": f"t{i % 50}",
                                          "env": ENVS[i % 3]}}}
        for i in range(200)}
    objs = [{"apiVersion": "v1", "kind": "Pod", "metadata": {
        "name": f"p{i}", "namespace": f"ns-{rng.randrange(200)}",
        "labels": {"tier": rng.choice(TIERS)}}} for i in range(20_000)]
    cons = make_constraints(rng, lambda r: {
        "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
        "namespaceSelector": {"matchLabels": {
            "tenant": f"t{r.randrange(50)}"}},
        **({"labelSelector": {"matchLabels": {"tier": "backend"}}}
           if r.random() < 0.25 else {})}, 300)
    batch, vocab, listed = flat(objs, cons, "dict")
    asked = []
    real = match_mod.label_selector_matches

    def counting(sel, labels):
        asked.append(1)
        return real(sel, labels)

    masks_mod.label_selector_matches = counting
    try:
        counts: dict = {}
        got = masks_mod.constraint_masks(cons, batch, vocab, listed,
                                         counts=counts,
                                         namespace_of=ns_objs.get)
    finally:
        masks_mod.label_selector_matches = real
    # 50 distinct tenant selectors x 200 Namespaces, one tier selector x 3
    assert len(asked) <= 50 * 200 + 3
    assert (counts["namespaces"], counts["label_sets"]) == (200, 3)
    assert counts["rows_selector"] == 300 and counts["rows_predicate"] == 0
    namespaces = looked_up(objs, ns_objs)
    for ci in (0, 1, 150, 299):
        want = [matches(cons[ci].match, Matchable(obj=o, namespace=ns))
                for o, ns in zip(objs, namespaces)]
        assert got[ci].tolist() == want


# --- the label columns of the raw-JSON lane ------------------------------------

needs_raw = pytest.mark.skipif(native.load_json() is None,
                               reason="native json build unavailable")


def labelled_corpus(n: int = 300) -> list:
    rng = random.Random("columns")
    objs = make_objects(rng, namespace_objects(rng), n)
    objs[7]["metadata"]["labels"] = {"tier": 7, "env": None}
    objs[8]["metadata"]["labels"] = ["tier"]
    objs[9]["metadata"].pop("labels", None)
    return objs


def label_values(batch, vocab, key, n) -> list:
    from gatekeeper_tpu.ops.flatten import K_ABSENT, K_STR

    col = batch.labels[(key,)]
    return [vocab.string(s) if k == K_STR else None if k == K_ABSENT
            else ("odd", int(k))
            for k, s in zip(col.kind[:n].tolist(), col.sid[:n].tolist())]


@needs_raw
@pytest.mark.parametrize("workers", [0, 2])
def test_the_raw_lane_columnizes_the_labels_it_is_asked_for(workers):
    from gatekeeper_tpu.ops.flatten import K_ABSENT, K_MAP, K_OTHER

    objs = labelled_corpus()
    keys = ("app.kubernetes.io/managed-by", "env", "tier")
    vocab = Vocab()
    fl = Flattener(Schema(), vocab, lane="raw", workers=workers,
                   label_keys=keys)
    batch = fl.flatten([RawJSON(json.dumps(o).encode()) for o in objs],
                       pad_n=320)
    assert fl.lane_used == ("raw+workers" if workers else "raw")
    assert set(batch.labels) == {()} | {(k,) for k in keys}
    for key in keys:
        got = label_values(batch, vocab, key, len(objs))
        for oi, obj in enumerate(objs):
            labels = obj["metadata"].get("labels")
            want = labels.get(key) if isinstance(labels, dict) else None
            if isinstance(want, str) or want is None and not (
                    isinstance(labels, dict) and key in labels):
                assert got[oi] == want, (key, oi)
            else:  # a number, a null: no string, and said so
                assert got[oi][0] == "odd", (key, oi)
        assert not batch.labels[(key,)].kind[len(objs):].any()  # the pad
    whole = batch.labels[()].kind
    assert whole[8] == K_OTHER and whole[9] == K_ABSENT and whole[7] == K_MAP
    # without the keys the lane writes no such column
    plain = Flattener(Schema(), Vocab(), lane="raw").flatten(
        [RawJSON(json.dumps(o).encode()) for o in objs])
    assert plain.labels is None


@needs_raw
def test_a_label_the_schema_already_holds_is_one_column():
    """``metadata.labels.owner`` as a program's scalar and as a selector's
    key: the kernel's path trie holds one column a path, so the label
    column is the schema's own and the program's column stays filled."""
    from gatekeeper_tpu.ops.flatten import K_STR, ScalarCol

    objs = labelled_corpus(140)
    for i, o in enumerate(objs):
        if isinstance(o["metadata"].get("labels"), dict) and i % 2:
            o["metadata"]["labels"]["owner"] = f"team-{i % 5}"
    schema = Schema()
    spec = ScalarCol(("metadata", "labels", "owner"))
    schema.scalars.append(spec)
    vocab = Vocab()
    batch = Flattener(schema, vocab, lane="raw",
                      label_keys=("owner", "tier")).flatten(
        [RawJSON(json.dumps(o).encode()) for o in objs])
    assert batch.labels[("owner",)].sid is batch.scalars[spec].sid
    assert (batch.scalars[spec].kind == K_STR).sum() >= 60
    assert label_values(batch, vocab, "owner", 140)[11] == "team-1"


@needs_raw
def test_the_differential_lanes_take_the_label_keys():
    """raw against dict, and the worker pool against the in-process lane
    (columns and the vocabulary's order): a label column changes
    neither comparison."""
    objs = labelled_corpus()
    for workers in (0, 2):
        fl = Flattener(Schema(), Vocab(), lane="differential",
                       workers=workers, label_keys=("tier", "env"))
        batch = fl.flatten([RawJSON(json.dumps(o).encode()) for o in objs])
        assert fl.lane_used.startswith("differential:raw")
        assert batch.labels is not None

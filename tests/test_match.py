"""Match predicate semantics (reference: pkg/mutation/match/match_test.go
table-driven cases, condensed)."""

import pytest

from gatekeeper_tpu.match.match import Matchable, MatchError, matches
from gatekeeper_tpu.match import wildcard


def pod(name="p", ns="default", labels=None):
    meta = {"name": name, "namespace": ns}
    if labels:
        meta["labels"] = labels
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta}


def namespace(name, labels=None):
    meta = {"name": name}
    if labels:
        meta["labels"] = labels
    return {"apiVersion": "v1", "kind": "Namespace", "metadata": meta}


def test_empty_match_matches_everything():
    assert matches({}, Matchable(pod()))


def test_kinds_with_wildcards():
    m = {"kinds": [{"apiGroups": ["*"], "kinds": ["Pod"]}]}
    assert matches(m, Matchable(pod()))
    assert not matches(m, Matchable(namespace("x")))
    m2 = {"kinds": [{"apiGroups": ["apps"], "kinds": ["*"]}]}
    assert not matches(m2, Matchable(pod()))  # pod group is ""
    m3 = {"kinds": [{"apiGroups": [""], "kinds": ["Deployment"]},
                    {"apiGroups": [""], "kinds": ["Pod"]}]}
    assert matches(m3, Matchable(pod()))


def test_namespaces_globs():
    m = {"namespaces": ["kube-*"]}
    assert matches(m, Matchable(pod(ns="kube-system")))
    assert not matches(m, Matchable(pod(ns="default")))
    # namespace objects match on their own name (match.go:160-161)
    assert matches(m, Matchable(namespace("kube-public")))
    # cluster-scoped non-namespace objects can't be disqualified
    crd = {"apiVersion": "apiextensions.k8s.io/v1", "kind": "CustomResourceDefinition",
           "metadata": {"name": "x"}}
    assert matches(m, Matchable(crd))


def test_excluded_namespaces():
    m = {"excludedNamespaces": ["*-system"]}
    assert not matches(m, Matchable(pod(ns="kube-system")))
    assert matches(m, Matchable(pod(ns="default")))


def test_label_selector():
    m = {"labelSelector": {"matchLabels": {"app": "web"}}}
    assert matches(m, Matchable(pod(labels={"app": "web"})))
    assert not matches(m, Matchable(pod(labels={"app": "db"})))
    assert not matches(m, Matchable(pod()))
    m2 = {"labelSelector": {"matchExpressions": [
        {"key": "env", "operator": "In", "values": ["prod", "stage"]}]}}
    assert matches(m2, Matchable(pod(labels={"env": "prod"})))
    assert not matches(m2, Matchable(pod(labels={"env": "dev"})))
    m3 = {"labelSelector": {"matchExpressions": [
        {"key": "env", "operator": "DoesNotExist"}]}}
    assert matches(m3, Matchable(pod()))
    assert not matches(m3, Matchable(pod(labels={"env": "prod"})))


def test_namespace_selector():
    m = {"namespaceSelector": {"matchLabels": {"team": "a"}}}
    ns_obj = namespace("default", labels={"team": "a"})
    assert matches(m, Matchable(pod(), namespace=ns_obj))
    # namespace objects: selector applies to their own labels (match.go:92-93)
    assert matches(m, Matchable(namespace("x", labels={"team": "a"})))
    assert not matches(m, Matchable(namespace("x")))
    # cluster-scoped non-namespace: matches all (match.go:82-85)
    crd = {"apiVersion": "apiextensions.k8s.io/v1", "kind": "CustomResourceDefinition",
           "metadata": {"name": "x"}}
    assert matches(m, Matchable(crd))
    # namespaced object with no ns data: error (match.go:96-98)
    with pytest.raises(MatchError):
        matches(m, Matchable(pod()))


def test_scope():
    assert matches({"scope": "Cluster"}, Matchable(namespace("x")))
    assert not matches({"scope": "Cluster"}, Matchable(pod()))
    assert matches({"scope": "Namespaced"}, Matchable(pod()))
    assert not matches({"scope": "Namespaced"}, Matchable(namespace("x")))
    # invalid scope matches everything (match.go:223-226)
    assert matches({"scope": "cluster"}, Matchable(pod()))


def test_name_and_generate_name():
    m = {"name": "web-*"}
    assert matches(m, Matchable(pod(name="web-1")))
    assert not matches(m, Matchable(pod(name="db-1")))
    gen = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"generateName": "web-", "namespace": "default"}}
    assert matches(m, Matchable(gen))


def test_source():
    m = {"source": "Generated"}
    assert matches(m, Matchable(pod(), source="Generated"))
    assert not matches(m, Matchable(pod(), source="Original"))
    assert matches({"source": "All"}, Matchable(pod(), source="Original"))
    assert matches({}, Matchable(pod(), source=""))
    with pytest.raises(MatchError):
        matches({"source": "Generated"}, Matchable(pod(), source=""))


def test_wildcard_globs():
    assert wildcard.matches("*", "anything")
    assert wildcard.matches("*sys*", "kube-system")
    assert not wildcard.matches("kube", "kube-system")
    assert not wildcard.matches_generate_name("*-system", "kube-")


# --- NamespaceCache.version: what a kept namespaceSelector answer compares ----

def _ns(name, labels=None, **meta):
    return {"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": name, "labels": labels, **meta}}


@pytest.mark.parametrize("step,moves", [
    (lambda c: c.add(_ns("b", {"tenant": "t1"})), True),   # comes
    (lambda c: c.add(_ns("a", {"tenant": "t0"})), False),  # the same again
    (lambda c: c.add(_ns("a", {"tenant": "t0"},
                         annotations={"x": "y"})), False),
    (lambda c: c.add(_ns("a", {"tenant": "t9"})), True),   # other labels
    (lambda c: c.add(_ns("a", None)), True),               # labels gone
    (lambda c: c.remove(_ns("a")), True),                  # goes
    (lambda c: c.remove(_ns("never-there")), False),
    (lambda c: c.wipe(), True),
    (lambda c: c.add({"apiVersion": "v1", "kind": "Pod",
                      "metadata": {"name": "a"}}), False),
])
def test_namespace_cache_version_moves_with_what_a_selector_reads(step,
                                                                  moves):
    from gatekeeper_tpu.target.target import NamespaceCache

    cache = NamespaceCache()
    cache.add(_ns("a", {"tenant": "t0"}))
    before = cache.version
    step(cache)
    assert (cache.version != before) == moves
    if not moves:
        assert cache.get("a")["metadata"]["labels"] == {"tenant": "t0"}


def test_an_empty_namespace_cache_wiped_stays_where_it_is():
    from gatekeeper_tpu.target.target import NamespaceCache

    cache = NamespaceCache()
    cache.wipe()
    assert cache.version == 0

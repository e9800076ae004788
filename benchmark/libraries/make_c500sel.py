"""The ``library-c500sel`` library: ``library-c500``'s 46 templates, 500
constraints, 50 tenants and parameter variants (``make_c500.py``, the same
seed), with every ``match`` block rewritten to scope by labels, as
``website/docs/howto.md`` ("The match field") shows ``scope``,
``labelSelector`` and ``namespaceSelector``.  No constraint lists
``namespaces``; every constraint carries a ``namespaceSelector``.

    python3 benchmark/libraries/make_c500sel.py [--out DIR] [--seed N]

writes ``benchmark/libraries/c500sel/`` anew (``tests/benchmark`` holds the
committed files to this script, byte for byte).

- the 46 *baseline* constraints keep ``kinds`` and ``excludedNamespaces:
  [kube-system, gatekeeper-system]``; where ``library-c500`` exempts the
  namespaces ``ns-19*`` by name, these exempt by label, ``namespaceSelector:
  policy.example.com/exempt DoesNotExist``; ``scope: Namespaced`` where every
  kind of the constraint is namespaced, ``scope: Cluster`` where none is;
- the 341 *tenant* constraints that list the tenant's four namespaces there
  select them here: ``namespaceSelector.matchLabels: {tenant: t<k>}`` (the
  cluster labels ``ns-i`` with ``tenant: t<i mod 50>``, so the selection is
  the list);
- the 113 that name the tenant's prefix glob there select ``tenant: t<k>``
  and an environment here: ``env In [prod, staging]``, every second of them
  ``env NotIn [dev]`` (which takes in the namespaces without the label);
- every fourth tenant constraint whose kinds are among Pod, Service, Ingress
  and Deployment carries a ``labelSelector`` on the object as well, the forms
  of ``LABEL_FORMS`` in turn; a kind that does not draw a form's key
  (``DRAWS``, the configuration's ``cluster.labels``) takes the next form.

The labels, their shares and which rows carry a ``labelSelector`` are this
file's and the configuration's (``assumed``); upstream fixes none of them.
"""

from __future__ import annotations

import argparse
import copy
import os
import shutil

import yaml

try:
    from benchmark.libraries import make_c500
except ImportError:  # run as a script from anywhere
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.libraries import make_c500

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = make_c500.SEED
EXCLUDED = ["kube-system", "gatekeeper-system"]
EXEMPT = "policy.example.com/exempt"
MANAGED = "app.kubernetes.io/managed-by"
NAMESPACED = {"Pod", "Service", "Ingress", "Deployment", "RoleBinding",
              "HorizontalPodAutoscaler", "PodDisruptionBudget",
              "PersistentVolumeClaim"}
CLUSTER_SCOPED = {"Namespace", "ClusterRole", "ClusterRoleBinding"}
# the object labels the configuration's cluster draws, by kind
DRAWS = {"Pod": {"tier", MANAGED}, "Service": {"tier"}, "Ingress": {"env"},
         "Deployment": {MANAGED}}
# (the key a form reads, the selector): taken in turn
LABEL_FORMS = [
    ("tier", {"matchLabels": {"tier": "backend"}}),
    ("tier", {"matchExpressions": [
        {"key": "tier", "operator": "In", "values": ["backend", "batch"]}]}),
    (MANAGED, {"matchExpressions": [{"key": MANAGED, "operator": "Exists"}]}),
    (MANAGED, {"matchExpressions": [
        {"key": MANAGED, "operator": "DoesNotExist"}]}),
    ("env", {"matchExpressions": [
        {"key": "env", "operator": "In", "values": ["prod", "staging"]}]}),
]


def kinds_of(match: dict) -> set:
    """The kinds a match block names; {"*"} where it names all or none."""
    blocks = match.get("kinds") or []
    kinds = {k for b in blocks for k in (b.get("kinds") or ["*"])}
    return kinds or {"*"}


def scope_of(match: dict):
    kinds = kinds_of(match)
    if kinds <= NAMESPACED:
        return "Namespaced"
    if kinds <= CLUSTER_SCOPED:
        return "Cluster"
    return None


def baseline(doc: dict) -> dict:
    doc = copy.deepcopy(doc)
    match = doc["spec"]["match"]
    match["excludedNamespaces"] = list(EXCLUDED)
    scope = scope_of(match)
    if scope:
        match["scope"] = scope
    match["namespaceSelector"] = {"matchExpressions": [
        {"key": EXEMPT, "operator": "DoesNotExist"}]}
    return doc


def tenant(doc: dict, env_form: int, label_form) -> dict:
    """``env_form``: 0 the tenant's label alone, 1 ``env In``, 2 ``env
    NotIn``; ``label_form``: a selector of ``LABEL_FORMS`` or None."""
    doc = copy.deepcopy(doc)
    match = doc["spec"]["match"]
    del match["namespaces"]
    t = int(doc["metadata"]["name"][1:3])
    selector: dict = {"matchLabels": {"tenant": f"t{t}"}}
    if env_form == 1:
        selector["matchExpressions"] = [
            {"key": "env", "operator": "In", "values": ["prod", "staging"]}]
    elif env_form == 2:
        selector["matchExpressions"] = [
            {"key": "env", "operator": "NotIn", "values": ["dev"]}]
    match["namespaceSelector"] = selector
    if label_form is not None:
        match["labelSelector"] = copy.deepcopy(label_form)
    return doc


def constraint_set(seed: int = SEED) -> dict:
    """{template directory name: [constraint documents]}: those of
    ``make_c500.constraint_set(seed)``, in its order, rescoped."""
    out: dict = {}
    globs = labelled = form = 0
    for name, docs in make_c500.constraint_set(seed).items():
        out[name] = []
        for doc in docs:
            match = doc["spec"]["match"]
            if "namespaces" not in match:
                out[name].append(baseline(doc))
                continue
            env_form = 0
            if match["namespaces"][0].endswith("*"):
                env_form = 1 + globs % 2
                globs += 1
            label_form = None
            kinds = kinds_of(match)
            if kinds <= set(DRAWS):
                if labelled % 4 == 0:
                    drawn = set.intersection(*(DRAWS[k] for k in kinds))
                    while LABEL_FORMS[form % len(LABEL_FORMS)][0] \
                            not in drawn:
                        form += 1
                    label_form = LABEL_FORMS[form % len(LABEL_FORMS)][1]
                    form += 1
                labelled += 1
            out[name].append(tenant(doc, env_form, label_form))
    return out


def counts(docs: dict) -> dict:
    """What the configuration's file records under ``library.constraints``."""
    rows = [d["spec"]["match"] for ds in docs.values() for d in ds]
    tenants = [m for m in rows if "tenant" in (
        m["namespaceSelector"].get("matchLabels") or {})]
    return {
        "baseline": len(rows) - len(tenants),
        "tenant": len(tenants),
        "tenant_match_labels_only": sum(
            "matchExpressions" not in m["namespaceSelector"]
            for m in tenants),
        "tenant_env_in": sum(
            e["operator"] == "In" for m in tenants
            for e in m["namespaceSelector"].get("matchExpressions", ())),
        "tenant_env_not_in": sum(
            e["operator"] == "NotIn" for m in tenants
            for e in m["namespaceSelector"].get("matchExpressions", ())),
        "tenants": len({m["namespaceSelector"]["matchLabels"]["tenant"]
                        for m in tenants}),
        "namespace_selector": sum("namespaceSelector" in m for m in rows),
        "label_selector": sum("labelSelector" in m for m in rows),
        "scope_namespaced": sum(m.get("scope") == "Namespaced"
                                for m in rows),
        "scope_cluster": sum(m.get("scope") == "Cluster" for m in rows),
        "namespaces_list": sum("namespaces" in m for m in rows),
    }


def write(out_dir: str, seed: int = SEED) -> int:
    docs = constraint_set(seed)
    n = 0
    for name, path in make_c500.templates():
        d = os.path.join(out_dir, name)
        os.makedirs(os.path.join(d, "samples"), exist_ok=True)
        shutil.copyfile(os.path.join(path, "template.yaml"),
                        os.path.join(d, "template.yaml"))
        with open(os.path.join(d, "samples", "constraint.yaml"), "w") as f:
            yaml.safe_dump_all(docs[name], f, sort_keys=False,
                               default_flow_style=False)
        n += len(docs[name])
    return n


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "c500sel"))
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args()
    print(f"{write(args.out, args.seed)} constraints under {args.out}")
    print(counts(constraint_set(args.seed)))

"""Constraint match → boolean masks over the flattened batch.

The reference evaluates ``match.Matches`` per (object, constraint) in Go
(pkg/mutation/match/match.go); here ``kinds``, ``namespaces``,
``excludedNamespaces`` and ``name`` are answered for a whole group of
constraints at once, on the host, from the batch's identity columns: the
columns are coded into small integers (one code per distinct kind, group
and effective namespace of the chunk), every constraint is evaluated once
per distinct value, as strings and by the oracle's own rules, into a
constraint x value table, and one gather spreads the table over the
objects.  The work is constraints x distinct values plus one gather; there
is no Python per object on that path, so it holds at hundreds of
namespace-scoped constraints over tens of thousands of objects.
Matchers that need an object's structure (labelSelector, namespaceSelector,
source, scope, and ``name`` where an object carries generateName) take the
exact host predicate, per object, for the constraints that use them,
preserving bit-exact semantics; ``counts`` says how many rows went which
way.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from gatekeeper_tpu.match import wildcard
from gatekeeper_tpu.match.match import Matchable, matches
from gatekeeper_tpu.ops.flatten import ColumnBatch, Vocab
from gatekeeper_tpu.utils.unstructured import deep_get

_TABLE_KEYS = {"kinds", "namespaces", "excludedNamespaces", "name"}


def constraint_masks(
    constraints: Sequence,
    batch: ColumnBatch,
    vocab: Vocab,
    objects: Sequence[dict],
    namespaces: Optional[Sequence[Optional[dict]]] = None,
    sources: Optional[Sequence[str]] = None,
    any_generate_name: Optional[bool] = None,
    counts: Optional[dict] = None,
) -> np.ndarray:
    """[C, N] bool: does constraint c match object n.  ``counts``, where
    given, gains this call's constraint rows under ``rows_vectorized``
    (answered from the table) and ``rows_predicate`` (per object)."""
    c, n = len(constraints), batch.n
    n_real = len(objects)
    out = np.zeros((c, n), bool)
    if any_generate_name is None:  # callers sweeping chunks hoist this
        any_generate_name = any(
            "generateName" in (o.get("metadata") or {}) for o in objects
        )
    matchers = [con.match or {} for con in constraints]
    # a constraint with a matcher outside the table's takes the exact host
    # predicate for every object: never AND a partial table row with a
    # predicate that skips already-False cells (a name row's False must
    # not suppress a generateName match)
    predicate = [
        ci for ci, m in enumerate(matchers)
        if set(m) - _TABLE_KEYS
        or ((m.get("name") or "") and any_generate_name)
    ]
    if counts is not None:
        counts["rows_predicate"] = (counts.get("rows_predicate", 0)
                                    + len(predicate))
        counts["rows_vectorized"] = (counts.get("rows_vectorized", 0)
                                     + c - len(predicate))
    if n_real and len(predicate) < c:
        _table_masks(matchers, frozenset(predicate), batch, vocab, n_real,
                     namespaces, out)
    for ci in predicate:
        m = matchers[ci]
        for oi in range(n_real):
            ns_obj = namespaces[oi] if namespaces else None
            src = sources[oi] if sources else ""
            out[ci, oi] = matches(
                m, Matchable(obj=objects[oi], namespace=ns_obj, source=src)
            )
    return out


def _distinct(ids: np.ndarray, vocab: Vocab) -> tuple:
    """(strings of the distinct ids, code of every element)."""
    uniq, codes = np.unique(ids, return_inverse=True)
    return ([vocab.string(s) if s >= 0 else "" for s in uniq.tolist()],
            codes)


def _effective_namespaces(is_namespace_obj, name_ids, ns_ids, vocab,
                          namespaces) -> tuple:
    """(distinct effective namespaces, code of every object), by
    match.go:125-139: a Namespace object answers with its own name, any
    other with the Namespace object provided for it or else with its
    metadata.namespace; one with none of these has no namespace to be
    disqualified by, and its value is None."""
    if namespaces is None or all(ns is None for ns in namespaces):
        # -3: below every id a column holds (-1 absent, -2 unseen)
        eff = np.where(is_namespace_obj, name_ids,
                       np.where(ns_ids == vocab.lookup(""), -3, ns_ids))
        uniq, codes = np.unique(eff, return_inverse=True)
        return ([None if s == -3 else vocab.string(s) if s >= 0 else ""
                 for s in uniq.tolist()], codes)
    # admission: a review brings its Namespace object, whose name goes
    # before metadata.namespace.  One pass over the objects, not one per
    # constraint
    index: dict = {}
    codes = np.empty(len(ns_ids), np.intp)
    own = is_namespace_obj.tolist()
    for oi, (name_id, ns_id) in enumerate(zip(name_ids.tolist(),
                                              ns_ids.tolist())):
        if own[oi]:
            val = vocab.string(name_id) if name_id >= 0 else ""
        elif namespaces[oi] is not None:
            val = deep_get(namespaces[oi], ("metadata", "name"), "") or ""
        else:
            val = (vocab.string(ns_id) if ns_id >= 0 else "") or None
        codes[oi] = index.setdefault(val, len(index))
    return list(index), codes


class _ValueTable:
    """One column's distinct values, and for every pattern asked of them
    the row of values it matches."""

    def __init__(self, values: list):
        self.values = ["" if v is None else v for v in values]
        self.free = np.array([v is None for v in values], bool)
        self.at: dict = {}  # absent and unseen ids both read ""
        for i, v in enumerate(values):
            if v is not None:
                self.at.setdefault(v, []).append(i)
        self.rows: dict = {}

    def any_of(self, patterns) -> np.ndarray:
        hit = np.zeros(len(self.values), bool)
        for p in patterns:
            row = self.rows.get(p)
            if row is None:
                row = self.rows[p] = self._row(p)
            hit |= row
        return hit

    def _row(self, pattern: str) -> np.ndarray:
        if pattern.startswith("*") or pattern.endswith("*"):
            return np.array([wildcard.matches(pattern, v)
                             for v in self.values], bool)
        # no glob: the value equal to it, found without asking the others
        # (a Namespace group has a distinct value for every object)
        row = np.zeros(len(self.values), bool)
        row[self.at.get(pattern, [])] = True
        return row


def _kinds_row(kinds: tuple, kind_strs: list, group_strs: list) -> np.ndarray:
    """[kinds x groups] flat: match.go:181-201 on every pair."""
    hit = np.zeros((len(kind_strs), len(group_strs)), bool)
    for klist, glist in kinds:
        k_ok = np.array([not klist or "*" in klist or k in klist
                         for k in kind_strs], bool)
        g_ok = np.array([not glist or "*" in glist or g in glist
                         for g in group_strs], bool)
        hit |= k_ok[:, None] & g_ok[None, :]
    return hit.ravel()


def _table_masks(matchers: list, predicate: frozenset, batch: ColumnBatch,
                 vocab: Vocab, n_real: int, namespaces, out: np.ndarray):
    """Fill ``out[:, :n_real]`` for every constraint not in ``predicate``."""
    kind_ids = batch.kind_sid[:n_real]
    group_ids = batch.group_sid[:n_real]
    name_ids = batch.name_sid[:n_real]
    is_namespace_obj = (kind_ids == vocab.lookup("Namespace")) & (
        group_ids == vocab.lookup("")
    )
    kind_strs, kind_codes = _distinct(kind_ids, vocab)
    group_strs, group_codes = _distinct(group_ids, vocab)
    ns_vals, ns_codes = _effective_namespaces(
        is_namespace_obj, name_ids, batch.ns_sid[:n_real], vocab, namespaces)
    # the distinct (kind, group, effective namespace) of the chunk: the
    # columns of the table, and every object's column
    joint, codes = np.unique(
        (kind_codes * len(group_strs) + group_codes) * len(ns_vals)
        + ns_codes, return_inverse=True)
    j_kg, j_ns = np.divmod(joint, len(ns_vals))

    ns_table = _ValueTable(ns_vals)
    kg_all = np.ones(len(kind_strs) * len(group_strs), bool)
    ns_all = np.ones(len(ns_vals), bool)
    kg_rows: dict = {(): kg_all}
    ns_rows: dict = {((), ()): ns_all}
    kg = np.zeros((len(matchers), len(kg_all)), bool)
    ns = np.zeros((len(matchers), len(ns_all)), bool)
    named = []
    for ci, m in enumerate(matchers):
        if ci in predicate:
            continue
        key = tuple((tuple(kk.get("kinds") or ()),
                     tuple(kk.get("apiGroups") or ()))
                    for kk in m.get("kinds") or ())
        row = kg_rows.get(key)
        if row is None:
            row = kg_rows[key] = _kinds_row(key, kind_strs, group_strs)
        kg[ci] = row
        # namespaces / excludedNamespaces (match.go:118-179)
        key = (tuple(m.get("namespaces") or ()),
               tuple(m.get("excludedNamespaces") or ()))
        row = ns_rows.get(key)
        if row is None:
            row = ns_all
            if key[0]:
                row = ns_table.any_of(key[0])
            if key[1]:
                row = row & ~ns_table.any_of(key[1])
            row = ns_rows[key] = row | ns_table.free
        ns[ci] = row
        if m.get("name") or "":
            named.append(ci)
    np.take(kg[:, j_kg] & ns[:, j_ns], codes, axis=1,
            out=out[:, :n_real], mode="clip")
    if named:
        # name (match.go:203-212); an object with generateName sent these
        # constraints to the predicate above
        name_strs, name_codes = _distinct(name_ids, vocab)
        name_table = _ValueTable(name_strs)
        for ci in named:
            out[ci, :n_real] &= name_table.any_of(
                (matchers[ci]["name"],))[name_codes]

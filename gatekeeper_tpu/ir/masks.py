"""Constraint match → boolean masks over the flattened batch.

The reference evaluates ``match.Matches`` per (object, constraint) in Go
(pkg/mutation/match/match.go); here all eight matchers are answered for a
whole group of constraints at once, on the host, from the batch's identity
columns, as tables over the chunk's distinct values, by the oracle's own
rules (``match/match.py``), and gathered over the objects:

- ``kinds``, ``namespaces``, ``excludedNamespaces``: the columns are coded
  into small integers (one code per distinct kind, group and effective
  namespace of the chunk), every constraint is evaluated once per distinct
  value, as strings, into a constraint x value table, and one gather spreads
  the table over the objects;
- ``name``, ``scope``, ``source``, ``labelSelector``, ``namespaceSelector``:
  one bool row per distinct matcher value, ANDed into the rows of the
  constraints that carry it.  A selector is evaluated once per distinct
  label set: an object's own labels come from the batch's label columns
  (``ColumnBatch.labels``, filled by the raw-JSON flattener for the keys
  ``selector_label_keys`` names; read from the objects where a lane has no
  such column), a ``namespaceSelector`` is evaluated once per distinct
  Namespace object of the chunk (hundreds, against tens of thousands of
  objects).

The Namespace object of a namespaced object comes either with the call,
one per object (``namespaces=``, the admission path: a review brings its
own), or from ``namespace_of``, a lookup by ``metadata.namespace`` (the audit
sweep: the target's ``NamespaceCache``, which ``Client.add_data`` fills, keyed
by the Namespace's own name); a per-object entry goes before the lookup, as
in ``target.Matcher.match``.  The lookup is made once per distinct namespace
of the chunk, and only where a ``namespaceSelector`` asks: no other matcher
reads the Namespace object, so a group without one depends on nothing
outside its own rows.

The work is constraints x distinct values plus gathers; there is no Python
per object on that path.  The exact host predicate (``match.matches``, per
object) stays for what a table cannot answer: ``name`` where an object of
the chunk carries generateName, and inputs on which the oracle raises (a
namespaced object under a ``namespaceSelector`` whose Namespace is in
neither the call nor the lookup, an invalid operator or ``source``, a
malformed selector, labels that are no strings), so that it raises here as
it does there, or does not where an earlier matcher already said no.
Anything else a table raises is a fault of this module and propagates.
``counts`` says how many rows went which way, and the seconds the selector
tables took.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Optional, Sequence

import numpy as np

from gatekeeper_tpu.match import wildcard
from gatekeeper_tpu.match.match import (SOURCE_ALL, VALID_SOURCES, Matchable,
                                        MatchError, label_selector_matches,
                                        matches)
from gatekeeper_tpu.observability import tracing
from gatekeeper_tpu.ops.flatten import (K_ABSENT, K_FALSE, K_MAP, K_NULL,
                                        K_STR, ColumnBatch, Vocab)
from gatekeeper_tpu.utils.unstructured import deep_get

_SELECTORS = ("labelSelector", "namespaceSelector")
_PLAIN_FACTORS = ("name", "scope", "source")
_ABSENT = object()


class _AskTheOracle(Exception):
    """A table cannot answer this constraint on this chunk."""


# what sends a row to the predicate: the oracle's own error, and what it
# makes of a selector that is no mapping of lists.  Anything else is a fault
# of this module, and is raised
_ORACLES_TO_DECIDE = (_AskTheOracle, MatchError, TypeError, AttributeError)


def selector_label_keys(constraints: Sequence) -> tuple:
    """The label keys the selectors of ``constraints`` read, sorted: what
    the flattener columnizes beside the identity columns so that no object
    is loaded for matching.  A ``namespaceSelector``'s keys are among
    them, because a Namespace object is selected on its own labels."""
    keys: set = set()
    for con in constraints:
        for field in _SELECTORS:
            selector = (con.match or {}).get(field)
            if selector is not None:
                keys.update(_keys_of(selector))
    return tuple(sorted(keys))


def reads_namespace_labels(constraints: Sequence) -> bool:
    """Whether a match of ``constraints`` depends on more than the object
    itself: under a ``namespaceSelector`` it follows the labels of the
    object's Namespace, so an answer kept for an unchanged object (a
    resident mask, a clean row's verdict) goes stale when those move."""
    return any((con.match or {}).get("namespaceSelector") is not None
               for con in constraints)


def _keys_of(selector) -> list:
    """The string keys a selector reads; a malformed one reads none (it
    goes to the predicate when its row is built)."""
    try:
        keys = list(selector.get("matchLabels") or {})
        keys += [expr.get("key", "")
                 for expr in selector.get("matchExpressions") or []]
    except (AttributeError, TypeError):
        return []
    # a JSON object has string keys only: another key is absent from every
    # label set, in the column as in the object
    return [k for k in keys if isinstance(k, str)]


def constraint_masks(
    constraints: Sequence,
    batch: ColumnBatch,
    vocab: Vocab,
    objects: Sequence[dict],
    namespaces: Optional[Sequence[Optional[dict]]] = None,
    sources: Optional[Sequence[str]] = None,
    any_generate_name: Optional[bool] = None,
    counts: Optional[dict] = None,
    namespace_of: Optional[Callable[[str], Optional[dict]]] = None,
) -> np.ndarray:
    """[C, N] bool: does constraint c match object n.  ``counts``, where
    given, gains this call's constraint rows under ``rows_vectorized``
    (answered from tables), ``rows_predicate`` (per object) and
    ``rows_selector`` (table-answered rows with a ``labelSelector`` or a
    ``namespaceSelector``), the distinct ``label_sets`` and ``namespaces``
    the selectors were evaluated on, ``ns_missing``, the namespaced
    objects under a ``namespaceSelector`` whose Namespace was not found, and
    ``selector_s``, the seconds of the span ``ir.masks.selectors``: the
    Namespace lookups, the label sets' coding and the selectors' tables."""
    c, n = len(constraints), batch.n
    n_real = len(objects)
    out = np.zeros((c, n), bool)
    if any_generate_name is None:  # callers sweeping chunks hoist this
        if batch.has_generate_name is not None:
            any_generate_name = bool(batch.has_generate_name[:n_real].any())
        else:
            any_generate_name = any(
                "generateName" in (o.get("metadata") or {}) for o in objects)
    matchers = [con.match or {} for con in constraints]
    # a constraint the tables cannot answer takes the exact host predicate
    # for every object: never AND a partial table row with a predicate that
    # skips already-False cells (a name row's False must not suppress a
    # generateName match)
    predicate = {ci for ci, m in enumerate(matchers)
                 if (m.get("name") or "") and any_generate_name}
    stats = {"rows_selector": 0, "label_sets": 0, "namespaces": 0,
             "ns_missing": 0, "selector_s": 0.0}
    try:
        if n_real and len(predicate) < c:
            chunk = _Chunk(batch, vocab, objects, n_real, namespaces,
                           namespace_of, sources, stats)
            _table_masks(matchers, predicate, chunk, out)
        for ci in sorted(predicate):
            m = matchers[ci]
            for oi in range(n_real):
                obj = objects[oi]
                src = sources[oi] if sources else ""
                out[ci, oi] = matches(m, Matchable(
                    obj=obj, source=src, namespace=_namespace_for(
                        obj, oi, namespaces, namespace_of)))
    finally:  # counted where the oracle raises too
        if counts is not None:
            stats["rows_predicate"] = len(predicate)
            stats["rows_vectorized"] = c - len(predicate)
            for key, value in stats.items():
                counts[key] = counts.get(key, 0) + value
    return out


def _namespace_for(obj, oi: int, namespaces, namespace_of):
    """The Namespace object ``match.matches`` is handed for one object, as
    ``target.Matcher.match`` finds it: the call's own, else the lookup's."""
    ns_obj = namespaces[oi] if namespaces else None
    if ns_obj is None and namespace_of is not None:
        name = deep_get(obj, ("metadata", "namespace"), "")
        if name and isinstance(name, str):
            ns_obj = namespace_of(name)
    return ns_obj


def _distinct(ids: np.ndarray, vocab: Vocab) -> tuple:
    """(strings of the distinct ids, code of every element)."""
    uniq, codes = np.unique(ids, return_inverse=True)
    return ([vocab.string(s) if s >= 0 else "" for s in uniq.tolist()],
            codes)


def _distinct_columns(rows: list) -> tuple:
    """(the distinct columns of the int rows, as lists; every column's
    code): an integer sort a row and one of the joint code, which costs a
    fraction of ``np.unique(axis=1)``'s sort of records."""
    parts, joint, span = [], np.zeros(len(rows[0]), np.int64), 1
    for row in rows:
        uniq, codes = np.unique(row, return_inverse=True)
        parts.append(uniq.tolist())
        span *= len(uniq)
        if span >= 2 ** 62:  # more combinations than a code can number
            uniq, codes = np.unique(np.stack(rows), axis=1,
                                    return_inverse=True)
            return uniq.T.tolist(), codes.reshape(-1)
        joint = joint * len(uniq) + codes
    joint, codes = np.unique(joint, return_inverse=True)
    cols = []
    for j in joint.tolist():
        col = []
        for uniq in reversed(parts):
            j, at = divmod(j, len(uniq))
            col.append(uniq[at])
        cols.append(col[::-1])
    return cols, codes


def _labels_of(obj) -> dict:
    return deep_get(obj, ("metadata", "labels"), {}) or {}


class _Chunk:
    """One call's objects as codes over their distinct values; every part
    is computed once, when the first constraint asks for it."""

    def __init__(self, batch, vocab, objects, n_real, namespaces,
                 namespace_of, sources, stats):
        self.batch, self.vocab, self.objects = batch, vocab, objects
        self.n = n_real
        self.stats = stats
        self.sources = sources
        self.kind_ids = batch.kind_sid[:n_real]
        self.group_ids = batch.group_sid[:n_real]
        self.name_ids = batch.name_sid[:n_real]
        self.ns_ids = batch.ns_sid[:n_real]
        self.is_namespace_obj = (
            self.kind_ids == vocab.lookup("Namespace")) & (
            self.group_ids == vocab.lookup(""))
        self.namespace_of = namespace_of
        self._resolve_namespaces(namespaces)
        self._label_sets: dict = {}
        self._factors: dict = {}
        self._names = None
        self._source_codes = None
        self._subjects = None

    # --- the Namespace of every object -----------------------------------
    def _resolve_namespaces(self, namespaces) -> None:
        """``ns_vals`` / ``ns_codes``: the distinct effective namespaces
        and every object's, by match.go:125-139: a Namespace object answers
        with its own name, any other with the Namespace object provided for
        it or else with its metadata.namespace (which is the name of what
        ``namespace_of`` would find); one with none of these has no
        namespace to be disqualified by, and its value is None.
        ``ns_objs`` / ``ns_obj_codes``: the distinct Namespace objects the
        call brought, and every object's (-1: none)."""
        vocab, ns_ids, name_ids = self.vocab, self.ns_ids, self.name_ids
        self.ns_objs: list = []
        self.ns_obj_codes = np.full(self.n, -1, np.intp)
        if namespaces is None or all(ns is None for ns in namespaces):
            # -3: below every id a column holds (-1 absent, -2 unseen)
            eff = np.where(self.is_namespace_obj, name_ids,
                           np.where(ns_ids != vocab.lookup(""), ns_ids, -3))
            uniq, self.ns_codes = np.unique(eff, return_inverse=True)
            self.ns_vals = [
                None if s == -3 else vocab.string(s) if s >= 0 else ""
                for s in uniq.tolist()]
            return
        # admission: a review brings its Namespace object, whose name goes
        # before metadata.namespace.  One pass over the objects, not one per
        # constraint
        index: dict = {}
        ns_index: dict = {}
        self.ns_codes = np.empty(self.n, np.intp)
        own = self.is_namespace_obj.tolist()
        for oi, (name_id, ns_id) in enumerate(zip(name_ids.tolist(),
                                                  ns_ids.tolist())):
            ns_obj = namespaces[oi]
            if ns_obj is not None:
                code = ns_index.get(id(ns_obj))
                if code is None:
                    code = ns_index[id(ns_obj)] = len(self.ns_objs)
                    self.ns_objs.append(ns_obj)
                self.ns_obj_codes[oi] = code
            if own[oi]:
                val = vocab.string(name_id) if name_id >= 0 else ""
            elif ns_obj is not None:
                val = deep_get(ns_obj, ("metadata", "name"), "") or ""
            else:
                val = (vocab.string(ns_id) if ns_id >= 0 else "") or None
            self.ns_codes[oi] = index.setdefault(val, len(index))
        self.ns_vals = list(index)

    # --- label sets ----------------------------------------------------------
    def label_sets(self, selector) -> tuple:
        """(distinct label sets of the objects, restricted to the keys
        ``selector`` reads; code of every object)."""
        keys = tuple(sorted(set(_keys_of(selector))))
        got = self._label_sets.get(keys)
        if got is None:
            got = self._label_sets[keys] = (
                self._label_sets_of_columns(keys)
                or self._label_sets_of_objects(keys))
            if got[0] is not None:
                self.stats["label_sets"] += len(got[0])
        if got[0] is None:
            raise _AskTheOracle("labels that are no mapping of strings")
        return got

    def _label_sets_of_columns(self, keys: tuple):
        labels = self.batch.labels
        if labels is None or () not in labels \
                or any((k,) not in labels for k in keys):
            return None
        n = self.n
        # metadata.labels itself: a mapping, or what ``or {}`` makes one
        whole = labels[()].kind[:n]
        odd = ~np.isin(whole, (K_ABSENT, K_MAP, K_NULL, K_FALSE))
        if not keys:
            return ([{}], np.zeros(n, np.intp)) if not odd.any() \
                else (None, None)
        rows = []
        for k in keys:
            kind, sid = labels[(k,)].kind[:n], labels[(k,)].sid[:n]
            is_str = kind == K_STR
            odd |= ~(is_str | (kind == K_ABSENT)) | (is_str & (sid < 0))
            rows.append(np.where(is_str, sid, -1))
        if odd.any():
            return None, None
        uniq, codes = _distinct_columns(rows)
        string = self.vocab.string
        return ([{k: string(s) for k, s in zip(keys, col) if s >= 0}
                 for col in uniq], codes)

    def _label_sets_of_objects(self, keys: tuple):
        """A lane without label columns (the dict lanes, a resident row):
        one pass over the objects, for every selector of these keys."""
        index: dict = {}
        codes = np.empty(self.n, np.intp)
        for oi in range(self.n):
            labels = _labels_of(self.objects[oi])
            if not isinstance(labels, dict):
                return None, None
            vals = tuple(labels.get(k, _ABSENT) for k in keys)
            if not all(v is _ABSENT or isinstance(v, str) for v in vals):
                return None, None
            codes[oi] = index.setdefault(vals, len(index))
        return ([{k: v for k, v in zip(keys, vals) if v is not _ABSENT}
                 for vals in index], codes)

    # --- one bool row per distinct matcher value ---------------------------------
    def factor(self, field: str, value) -> Optional[np.ndarray]:
        """[n] bool, or None for a matcher that says yes to everything."""
        key = (field, isinstance(value, str), value if isinstance(value, str)
               else json.dumps(value, sort_keys=True))
        if key not in self._factors:
            self._factors[key] = getattr(self, "_" + field)(value)
        return self._factors[key]

    def _name(self, pattern):
        # (match.go:203-212); an object with generateName sent these
        # constraints to the predicate
        if not pattern:
            return None
        if self._names is None:
            strs, codes = _distinct(self.name_ids, self.vocab)
            self._names = (_ValueTable(strs), codes)
        table, codes = self._names
        return table.any_of((pattern,))[codes]

    def _scope(self, scope):
        has_namespace = (self.ns_ids != self.vocab.lookup("")) | (
            self.ns_obj_codes >= 0)
        if scope == "Cluster":
            return self.is_namespace_obj | ~has_namespace
        if scope == "Namespaced":
            return ~self.is_namespace_obj & has_namespace
        # invalid scopes (typos) match everything, mirroring match.go:223-226
        return None

    def _source(self, msrc):
        msrc = msrc or SOURCE_ALL
        if msrc not in VALID_SOURCES:
            raise _AskTheOracle("invalid source")
        if msrc == SOURCE_ALL:
            return None
        if self._source_codes is None:
            index: dict = {}
            given = self.sources[:self.n] if self.sources else [""]
            if len(set(given)) == 1:
                index[given[0]] = 0
                codes = np.zeros(self.n, np.intp)
            else:
                codes = np.array([index.setdefault(s, len(index))
                                  for s in given], np.intp)
            self._source_codes = (list(index), codes)
        vals, codes = self._source_codes
        if any(v not in VALID_SOURCES for v in vals):
            raise _AskTheOracle("a resource without a valid source")
        return np.array([msrc == v for v in vals], bool)[codes]

    def _labelSelector(self, selector):
        if selector is None:
            return None
        sets, codes = self.label_sets(selector)
        return np.array([label_selector_matches(selector, labels)
                         for labels in sets], bool)[codes]

    def _namespace_subjects(self) -> tuple:
        """What a ``namespaceSelector`` is asked of, once per chunk: (the
        labels of the distinct Namespace objects, every object's code
        among them with the last for none, the objects that match every
        selector, the Namespace objects, selected on their own labels,
        whether a namespaced object lacks its Namespace)."""
        own = self.is_namespace_obj
        named = self.ns_ids != self.vocab.lookup("")
        ns_objs, ns_obj_codes = list(self.ns_objs), self.ns_obj_codes
        # looked up for the namespaced objects that are no Namespace and
        # brought none (a Namespace object's own Namespace is never asked
        # for), once per distinct metadata.namespace
        asks = ~own & named & (ns_obj_codes < 0)
        if self.namespace_of is not None and asks.any():
            strs, inverse = _distinct(self.ns_ids[asks], self.vocab)
            found = np.full(len(strs), -1, np.intp)
            for at, name in enumerate(strs):
                ns = self.namespace_of(name) if name else None
                if ns is not None:
                    found[at] = len(ns_objs)
                    ns_objs.append(ns)
            ns_obj_codes = ns_obj_codes.copy()
            ns_obj_codes[asks] = found[inverse]
        provided = ns_obj_codes >= 0
        missing = ~own & ~provided & named
        self.stats["namespaces"] += len(ns_objs)
        self.stats["ns_missing"] += int(missing.sum())
        # a cluster-scoped object that is no Namespace matches every
        # selector (match.go:82-85)
        free = ~own & ~provided & ~named
        codes = np.where(own | ~provided, len(ns_objs), ns_obj_codes)
        return ([_labels_of(ns) for ns in ns_objs], codes, free,
                own if own.any() else None, bool(missing.any()))

    def _namespaceSelector(self, selector):
        if selector is None:
            return None
        if self._subjects is None:
            self._subjects = self._namespace_subjects()
        labels, codes, free, own, missing = self._subjects
        if missing:
            raise _AskTheOracle("a namespaced object without its Namespace")
        # any other object is selected on its Namespace's labels
        hit = np.array([label_selector_matches(selector, ns)
                        for ns in labels] + [False], bool)
        row = hit[codes] | free
        if own is not None:
            sets, own_codes = self.label_sets(selector)
            row = np.where(own, np.array(
                [label_selector_matches(selector, ls) for ls in sets],
                bool)[own_codes], row)
        return row


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


def _table_masks(matchers: list, predicate: set, chunk: _Chunk,
                 out: np.ndarray) -> None:
    """Fill ``out[:, :n]`` for every constraint not in ``predicate``, and
    add to ``predicate`` those the tables cannot answer."""
    n_real, vocab = chunk.n, chunk.vocab
    kind_strs, kind_codes = _distinct(chunk.kind_ids, vocab)
    group_strs, group_codes = _distinct(chunk.group_ids, vocab)
    ns_vals, ns_codes = chunk.ns_vals, chunk.ns_codes
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
    factored = []
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
        if any(f in m for f in _PLAIN_FACTORS + _SELECTORS):
            factored.append(ci)
    np.take(kg[:, j_kg] & ns[:, j_ns], codes, axis=1,
            out=out[:, :n_real], mode="clip")

    def and_factors(ci: int, fields: tuple) -> None:
        m = matchers[ci]
        try:
            for field in fields:
                if field in m:
                    row = chunk.factor(field, m[field])
                    if row is not None:
                        out[ci, :n_real] &= row
        except _ORACLES_TO_DECIDE:  # the oracle's to raise, or to answer
            predicate.add(ci)

    for ci in factored:
        and_factors(ci, _PLAIN_FACTORS)
    selected = [ci for ci in factored if ci not in predicate
                and any(matchers[ci].get(f) is not None for f in _SELECTORS)]
    if not selected:
        return
    t0 = time.perf_counter()
    with tracing.span("ir.masks.selectors") as sp:
        for ci in selected:
            and_factors(ci, _SELECTORS)
        chunk.stats["rows_selector"] += sum(
            ci not in predicate for ci in selected)
        for key in ("rows_selector", "label_sets", "namespaces"):
            sp.set_attribute(key, chunk.stats[key])
    chunk.stats["selector_s"] += time.perf_counter() - t0

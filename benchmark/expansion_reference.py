"""What a generator object implies, for the plain reference: upstream
Gatekeeper's expansion and the Assign mutator, written here from upstream's
semantics and sharing no code with the program under test.

Upstream, ``pkg/expansion/system.go``: ``Expand`` applies every
ExpansionTemplate whose ``applyTo`` names the object's group, version and
kind; ``expandResource`` copies the field at ``templateSource`` into an
object of ``generatedGVK``, in the generator's Namespace, named
``<generator name>-<kind>`` in lower case, with an owner reference to the
generator; each resultant is then mutated as a Generated object and expanded
in turn.  ``pkg/mutation/system.go``: the mutators that match are applied
in order of their IDs until a round changes nothing.  ``pkg/mutation``'s
Assign sets ``parameters.assign.value`` at ``location``: a missing object
field is created on the way, ``[key: *]`` walks every element of a list and
creates none, ``[key: value]`` walks the element of that key and creates it
where it is missing; a ``pathTests`` entry stops the walk where its sub-path
exists (``MustNotExist``) or is missing (``MustExist``).
``pkg/expansion/aggregate.go``: a resultant's results reach the generator's
review with the prefix ``[Implied by <template>]`` and, where the template
sets one, its enforcementAction.

Whatever a configuration asks of these that is not written here (another
mutator kind, a label or Namespace selector, a value from metadata or an
external provider) is refused, so the reference never differs by leaving
something out."""

from __future__ import annotations

import copy
import fnmatch
import os
import re

import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IMPLIED_PREFIX = "[Implied by %s]"
MAX_DEPTH = 30  # system.go: the recursion stops with an error past this
_SEGMENT = re.compile(r"\s*(?:\[\s*([^:\]\s]+)\s*:\s*([^\]]*?)\s*\]|"
                      r"([^.\[\]\s]+))\s*(?:\.|(?=\[)|$)")
_MATCH_KEYS = {"source", "scope", "kinds", "namespaces",
               "excludedNamespaces", "name"}


def _docs(paths: list) -> list:
    out = []
    for path in paths:
        with open(os.path.join(ROOT, path)) as f:
            out.extend(d for d in yaml.safe_load_all(f) if d)
    return out


def _gvk(obj: dict) -> tuple:
    group, _, version = (obj.get("apiVersion") or "").rpartition("/")
    return group, version, obj.get("kind", "")


def _applies(apply_to: list, obj: dict) -> bool:
    """An ``applyTo`` list names the object's group, version and kind."""
    group, version, kind = _gvk(obj)
    return any(group in e.get("groups", ()) and
               version in e.get("versions", ()) and
               kind in e.get("kinds", ()) for e in apply_to)


def parse_location(location: str) -> list:
    """``a.b[k: v].c`` as [("field", "a"), ("field", "b"), ("list", "k",
    "v"), ("field", "c")]; ``v`` is None for the glob ``*``."""
    out, pos = [], 0
    while pos < len(location):
        m = _SEGMENT.match(location, pos)
        if m is None or m.end() == pos:
            raise ValueError(f"location {location!r}: cannot read at {pos}")
        key, value, field = m.groups()
        if field is not None:
            if field.startswith(('"', "'")):
                raise ValueError(f"location {location!r}: quoted fields are "
                                 "not written here")
            out.append(("field", field))
        else:
            value = value.strip("\"'")
            out.append(("list", key, None if value == "*" else value))
        pos = m.end()
    return out


class Template:
    """One ExpansionTemplate."""

    def __init__(self, doc: dict):
        spec = doc["spec"]
        self.name = doc["metadata"]["name"]
        self.apply_to = spec["applyTo"]
        self.source = spec["templateSource"].split(".")
        self.gvk = spec["generatedGVK"]
        self.action = spec.get("enforcementAction", "")

    def applies_to(self, obj: dict) -> bool:
        return _applies(self.apply_to, obj)

    def resultant(self, obj: dict) -> dict:
        """``expandResource``: the object the generator implies."""
        src = obj
        for key in self.source:
            src = src.get(key) if isinstance(src, dict) else None
        if not isinstance(src, dict):
            raise ValueError(f"{obj['metadata'].get('name')}: no "
                             f"{'.'.join(self.source)}")
        out = copy.deepcopy(src)
        group, version = self.gvk.get("group", ""), self.gvk["version"]
        out["apiVersion"] = f"{group}/{version}" if group else version
        out["kind"] = self.gvk["kind"]
        meta = out.setdefault("metadata", {})
        parent = obj["metadata"]
        if parent.get("namespace"):
            meta["namespace"] = parent["namespace"]
        meta["name"] = f"{parent['name']}-{self.gvk['kind']}".lower()
        meta.setdefault("ownerReferences", []).append({
            "apiVersion": obj["apiVersion"], "kind": obj["kind"],
            "name": parent["name"], "uid": parent.get("uid", "")})
        return out


class Assign:
    """One Assign mutator."""

    def __init__(self, doc: dict):
        if doc["kind"] != "Assign":
            raise ValueError(f"mutator kind {doc['kind']}: only Assign is "
                             "written here")
        spec = doc["spec"]
        self.id = (doc["kind"], doc["metadata"]["name"])
        self.apply_to = spec["applyTo"]
        self.match = spec.get("match", {})
        unknown = set(self.match) - _MATCH_KEYS
        if unknown:
            raise ValueError(f"{self.id}: match keys {sorted(unknown)} are "
                             "not written here")
        params = spec["parameters"]
        if set(params["assign"]) != {"value"}:
            raise ValueError(f"{self.id}: only assign.value is written here")
        self.value = params["assign"]["value"]
        self.path = parse_location(spec["location"])
        self.tests = {}
        for test in params.get("pathTests", ()):
            depth = len(parse_location(test["subPath"])) - 1
            self.tests[depth] = test["condition"]

    def matches(self, obj: dict, source: str) -> bool:
        """``match.Matches`` and the ``applyTo`` check for ``obj``."""
        if not _applies(self.apply_to, obj):
            return False
        group, _version, kind = _gvk(obj)
        m = self.match
        if m.get("source", "All") not in ("All", "", source):
            return False
        ns = obj.get("metadata", {}).get("namespace", "")
        scope = m.get("scope", "*")
        if (scope == "Namespaced" and not ns) or (scope == "Cluster" and ns):
            return False
        kinds = m.get("kinds") or []
        if kinds and not any(
                (not k.get("apiGroups") or "*" in k["apiGroups"]
                 or group in k["apiGroups"]) and
                (not k.get("kinds") or "*" in k["kinds"]
                 or kind in k["kinds"]) for k in kinds):
            return False
        if "namespaces" in m and not any(fnmatch.fnmatchcase(ns, p)
                                         for p in m["namespaces"]):
            return False
        if any(fnmatch.fnmatchcase(ns, p)
               for p in m.get("excludedNamespaces", ())):
            return False
        if "name" in m and not fnmatch.fnmatchcase(
                obj["metadata"].get("name", ""), m["name"]):
            return False
        return True

    def apply(self, obj: dict) -> bool:
        """Assign the value in place; whether anything changed."""
        return self._walk(obj, 0)

    def _walk(self, node, depth: int) -> bool:
        seg = self.path[depth]
        cond = self.tests.get(depth)
        last = depth == len(self.path) - 1
        if seg[0] == "field":
            if not isinstance(node, dict):
                raise ValueError(f"{self.id}: {seg[1]} under a "
                                 f"{type(node).__name__}")
            present = seg[1] in node
            if (present and cond == "MustNotExist") or (
                    not present and cond == "MustExist"):
                return False
            if last:
                if present and node[seg[1]] == self.value:
                    return False
                node[seg[1]] = copy.deepcopy(self.value)
                return True
            if present:
                return self._walk(node[seg[1]], depth + 1)
            node[seg[1]] = [] if self.path[depth + 1][0] == "list" else {}
            if self._walk(node[seg[1]], depth + 1):
                return True
            del node[seg[1]]
            return False
        _, key, want = seg
        if not isinstance(node, list):
            raise ValueError(f"{self.id}: [{key}: ...] under a "
                             f"{type(node).__name__}")
        if last:
            raise ValueError(f"{self.id}: a whole list element is not "
                             "assigned here")
        items = [e for e in node if isinstance(e, dict)
                 and (want is None or str(e.get(key)) == want)]
        if items and cond == "MustNotExist":
            return False
        if not items:
            if want is None or cond == "MustExist":
                return False
            item = {key: want}
            node.append(item)
            if self._walk(item, depth + 1):
                return True
            node.remove(item)
            return False
        changed = False
        for item in items:
            changed |= self._walk(item, depth + 1)
        return changed


class Expansion:
    """A configuration's ``expansion`` block, read from its files."""

    def __init__(self, block: dict):
        self.templates = sorted((Template(d) for d in
                                 _docs(block["templates"])),
                                key=lambda t: t.name)
        self.mutators = sorted((Assign(d) for d in _docs(block["mutators"])),
                               key=lambda m: m.id)

    def generates(self, obj: dict) -> bool:
        return any(t.applies_to(obj) for t in self.templates)

    def mutate(self, obj: dict) -> None:
        """The mutators that match ``obj`` as Generated, round after round
        until one changes nothing (``system.go``: at most one round more
        than there are mutators)."""
        for _ in range(len(self.mutators) + 1):
            changed = False
            for m in self.mutators:
                if m.matches(obj, "Generated"):
                    changed |= m.apply(obj)
            if not changed:
                return
        raise ValueError(f"{obj['metadata'].get('name')}: the mutators do "
                         "not converge")

    def expand(self, obj: dict, depth: int = 0) -> list:
        """[(template, resultant)] of ``obj``, mutated, its own resultants'
        first, in upstream's order."""
        if depth >= MAX_DEPTH:
            raise ValueError("expansion deeper than upstream allows")
        own = []
        for t in self.templates:
            if t.applies_to(obj):
                res = t.resultant(obj)
                self.mutate(res)
                own.append((t, res))
        out = []
        for _t, res in own:
            out.extend(self.expand(res, depth + 1))
        return out + own

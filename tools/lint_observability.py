"""Observability registry lint.

Cross-checks the code's observability surface against the documented
registry (``tools/observability_registry.md``):

- every ``fault_point("<site>")`` call site in ``gatekeeper_tpu/`` must
  be documented (f-string sites like ``pipeline.stage.{name}`` are
  normalized to their ``pipeline.stage.*`` pattern);
- every metric-name constant in ``gatekeeper_tpu/metrics/registry.py``
  must be documented under its exposed ``gatekeeper_*`` name;
- every tracer span name (``span("...")`` call sites) must be
  documented — the trace timeline is an API surface too;
- every built-in SLO objective name
  (``observability/slo.py:DEFAULT_OBJECTIVES``) must be documented —
  dashboards key on ``gatekeeper_slo_*{objective=...}`` values;
- every built-in degradation action
  (``resilience/overload.py:BUILTIN_ACTIONS``) must be documented —
  SLO degradation maps and ``--slo-config`` files name them, and
  ``gatekeeper_slo_degradation_active{action=...}`` keys on them;
- every ``/debug/*`` endpoint constant in ``webhook/server.py``
  (``*_PATH = "/debug/..."``) must be documented — runbooks and
  ``gator triage`` depend on those paths existing;
- stale documentation (a documented site/metric/span/objective/
  endpoint that no longer exists in the source) fails too, so the
  registry can be trusted.

Run standalone (``python tools/lint_observability.py``) or via tier-1
(``tests/test_observability_lint.py``).
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "gatekeeper_tpu"
REGISTRY_MD = REPO / "tools" / "observability_registry.md"
METRICS_PY = PKG / "metrics" / "registry.py"
SLO_PY = PKG / "observability" / "slo.py"
SHADOW_PY = PKG / "replay" / "shadow.py"
SERVER_PY = PKG / "webhook" / "server.py"
OVERLOAD_PY = PKG / "resilience" / "overload.py"

_FAULT_CALL = re.compile(r'fault_point\(\s*(f?)"([^"]+)"')
# tracer span call sites: tracing.span("..."), otel.span("..."),
# tracer.start_span("..."), and the tracer's own _finished_span("...")
# (spans it records after the fact: full garbage collections) — the \s*
# spans a line wrap after the paren
_SPAN_CALL = re.compile(
    r'\b(?:span|start_span|_finished_span)\(\s*(f?)"([^"]+)"')
_DOC_ENTRY = re.compile(r"^\s*-\s+`([^`]+)`")
_FSTRING_FIELD = re.compile(r"\{[^}]*\}")
# route constants at the top of webhook/server.py; only the /debug/*
# surface is registry-checked (the serving paths are API, not debug)
_ENDPOINT_CONST = re.compile(
    r'^([A-Z][A-Z0-9_]*_PATH)\s*=\s*"(/debug/[^"]*)"', re.M)


def documented() -> tuple[set, set, set, set]:
    """(fault sites, metric names, span names, slo objectives) parsed
    from the registry markdown."""
    sites: set = set()
    metrics: set = set()
    spans: set = set()
    objectives: set = set()
    section = ""
    for line in REGISTRY_MD.read_text().splitlines():
        if line.startswith("## "):
            section = line[3:].strip().lower()
            continue
        m = _DOC_ENTRY.match(line)
        if not m:
            continue
        if section.startswith("fault sites"):
            sites.add(m.group(1))
        elif section.startswith("metrics"):
            metrics.add(m.group(1))
        elif section.startswith("spans"):
            spans.add(m.group(1))
        elif section.startswith("slo objectives"):
            objectives.add(m.group(1))
    return sites, metrics, spans, objectives


def documented_endpoints() -> set:
    """Debug endpoint paths parsed from the registry markdown's
    ``## Debug endpoints`` section (kept apart from :func:`documented`
    so its 4-tuple shape stays stable for callers)."""
    endpoints: set = set()
    section = ""
    for line in REGISTRY_MD.read_text().splitlines():
        if line.startswith("## "):
            section = line[3:].strip().lower()
            continue
        m = _DOC_ENTRY.match(line)
        if m and section.startswith("debug endpoints"):
            endpoints.add(m.group(1))
    return endpoints


def documented_actions() -> set:
    """Degradation action names parsed from the registry markdown's
    ``## Degradation actions`` section (kept apart from
    :func:`documented` so its 4-tuple shape stays stable)."""
    actions: set = set()
    section = ""
    for line in REGISTRY_MD.read_text().splitlines():
        if line.startswith("## "):
            section = line[3:].strip().lower()
            continue
        m = _DOC_ENTRY.match(line)
        if m and section.startswith("degradation actions"):
            actions.add(m.group(1))
    return actions


def degradation_actions_in_source() -> dict:
    """action name -> defining constant, from the
    ``BUILTIN_ACTIONS`` dict of resilience/overload.py.  Keys are
    module-constant references (``NS_CACHE_STALE``), so constant
    assignments resolve first; a literal string key works too."""
    tree = ast.parse(OVERLOAD_PY.read_text())
    consts: dict = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            consts[node.targets[0].id] = node.value.value
    out: dict = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name) \
                or target.id != "BUILTIN_ACTIONS" \
                or not isinstance(node.value, ast.Dict):
            continue
        for k in node.value.keys:
            if isinstance(k, ast.Name) and k.id in consts:
                out[consts[k.id]] = k.id
            elif isinstance(k, ast.Constant) \
                    and isinstance(k.value, str):
                out[k.value] = "<literal>"
    return out


def debug_endpoints_in_source() -> dict:
    """path -> constant name for every ``*_PATH = "/debug/..."`` route
    constant in webhook/server.py — the surface ``gator triage``
    snapshots and runbooks link to."""
    return {m.group(2): m.group(1)
            for m in _ENDPOINT_CONST.finditer(SERVER_PY.read_text())}


def fault_sites_in_source() -> dict:
    """site -> [file:line] for every ``fault_point("...")`` literal in
    the package (docstrings included — a documented example must name a
    real site too).  F-string sites normalize ``{expr}`` to ``*``."""
    out: dict = {}
    for path in sorted(PKG.rglob("*.py")):
        text = path.read_text()
        # whole-text scan: call sites wrap across lines (the \s* spans
        # the newline between the paren and the site string)
        for m in _FAULT_CALL.finditer(text):
            site = m.group(2)
            if m.group(1):  # f-string: dynamic segments become *
                site = _FSTRING_FIELD.sub("*", site)
            line = text.count("\n", 0, m.start()) + 1
            out.setdefault(site, []).append(
                f"{path.relative_to(REPO)}:{line}")
    return out


def span_names_in_source() -> dict:
    """span name -> [file:line] for every ``span("...")`` /
    ``start_span("...")`` literal in the package.  F-string names
    (``pipeline.stage.{name}``) normalize their dynamic segments to
    ``*`` patterns, like fault sites."""
    out: dict = {}
    for path in sorted(PKG.rglob("*.py")):
        text = path.read_text()
        for m in _SPAN_CALL.finditer(text):
            name = m.group(2)
            if m.group(1):
                name = _FSTRING_FIELD.sub("*", name)
            line = text.count("\n", 0, m.start()) + 1
            out.setdefault(name, []).append(
                f"{path.relative_to(REPO)}:{line}")
    return out


def _objective_names(node) -> list:
    """``name`` values from an objective literal: a list of dicts
    (DEFAULT_OBJECTIVES) or one bare dict (SHADOW_OBJECTIVE)."""
    dicts = node.elts if isinstance(node, ast.List) else [node]
    names: list = []
    for elt in dicts:
        if not isinstance(elt, ast.Dict):
            continue
        for k, v in zip(elt.keys, elt.values):
            if isinstance(k, ast.Constant) and k.value == "name" \
                    and isinstance(v, ast.Constant):
                names.append(v.value)
    return names


def slo_objectives_in_source() -> dict:
    """objective name -> defining file, for every entry of
    ``slo.py:DEFAULT_OBJECTIVES`` plus opt-in objectives other modules
    define as module-level literals (``replay/shadow.py:
    SHADOW_OBJECTIVE``) — the names are the values dashboards and the
    breach counter key on."""
    out: dict = {}
    for path, wanted in ((SLO_PY, "DEFAULT_OBJECTIVES"),
                         (SHADOW_PY, "SHADOW_OBJECTIVE")):
        if not path.exists():
            continue
        for node in ast.parse(path.read_text()).body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target = node.targets[0]
            if not isinstance(target, ast.Name) or target.id != wanted:
                continue
            for name in _objective_names(node.value):
                out[name] = str(path.relative_to(REPO))
    return out


def metric_names_in_source() -> dict:
    """exposed name ('gatekeeper_' + value) -> constant name, from the
    module-level string constants of metrics/registry.py."""
    tree = ast.parse(METRICS_PY.read_text())
    prefix = "gatekeeper_"
    out: dict = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name) or not target.id.isupper():
            continue
        if target.id == "PREFIX":
            if isinstance(node.value, ast.Constant):
                prefix = node.value.value
            continue
        if isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, str) and \
                re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", node.value.value):
            # shape filter: module constants that aren't metric names
            # (content-type strings etc.) don't belong in the registry
            out[prefix + node.value.value] = target.id
    return out


def check() -> list:
    """List of problem strings; empty means the registry is in sync."""
    problems: list = []
    doc_sites, doc_metrics, doc_spans, doc_slo = documented()
    src_sites = fault_sites_in_source()
    src_metrics = metric_names_in_source()
    src_spans = span_names_in_source()
    src_slo = slo_objectives_in_source()
    for site, where in sorted(src_sites.items()):
        if site not in doc_sites:
            problems.append(
                f"undocumented fault site {site!r} ({where[0]}) — add it "
                f"to {REGISTRY_MD.relative_to(REPO)}")
    for site in sorted(doc_sites - set(src_sites)):
        problems.append(
            f"stale documented fault site {site!r} — no fault_point() "
            "call site matches; remove it from the registry")
    for name, const in sorted(src_metrics.items()):
        if name not in doc_metrics:
            problems.append(
                f"undocumented metric {name!r} (constant {const} in "
                f"{METRICS_PY.relative_to(REPO)}) — add it to "
                f"{REGISTRY_MD.relative_to(REPO)}")
    for name in sorted(doc_metrics - set(src_metrics)):
        problems.append(
            f"stale documented metric {name!r} — no matching constant in "
            f"{METRICS_PY.relative_to(REPO)}; remove it from the registry")
    for name, where in sorted(src_spans.items()):
        if name not in doc_spans:
            problems.append(
                f"undocumented span name {name!r} ({where[0]}) — add it "
                f"to {REGISTRY_MD.relative_to(REPO)}")
    for name in sorted(doc_spans - set(src_spans)):
        problems.append(
            f"stale documented span name {name!r} — no span() call site "
            "matches; remove it from the registry")
    for name, where in sorted(src_slo.items()):
        if name not in doc_slo:
            problems.append(
                f"undocumented SLO objective {name!r} ({where}) — add it "
                f"to {REGISTRY_MD.relative_to(REPO)}")
    for name in sorted(doc_slo - set(src_slo)):
        problems.append(
            f"stale documented SLO objective {name!r} — not in "
            f"{SLO_PY.relative_to(REPO)}:DEFAULT_OBJECTIVES or "
            f"{SHADOW_PY.relative_to(REPO)}:SHADOW_OBJECTIVE; remove it "
            "from the registry")
    doc_actions = documented_actions()
    src_actions = degradation_actions_in_source()
    for name, const in sorted(src_actions.items()):
        if name not in doc_actions:
            problems.append(
                f"undocumented degradation action {name!r} (constant "
                f"{const} in {OVERLOAD_PY.relative_to(REPO)}:"
                f"BUILTIN_ACTIONS) — add it to "
                f"{REGISTRY_MD.relative_to(REPO)}")
    for name in sorted(doc_actions - set(src_actions)):
        problems.append(
            f"stale documented degradation action {name!r} — not in "
            f"{OVERLOAD_PY.relative_to(REPO)}:BUILTIN_ACTIONS; remove "
            "it from the registry")
    doc_endpoints = documented_endpoints()
    src_endpoints = debug_endpoints_in_source()
    for path, const in sorted(src_endpoints.items()):
        if path not in doc_endpoints:
            problems.append(
                f"undocumented debug endpoint {path!r} (constant {const} "
                f"in {SERVER_PY.relative_to(REPO)}) — add it to "
                f"{REGISTRY_MD.relative_to(REPO)}")
    for path in sorted(doc_endpoints - set(src_endpoints)):
        problems.append(
            f"stale documented debug endpoint {path!r} — no *_PATH "
            f"constant in {SERVER_PY.relative_to(REPO)} matches; remove "
            "it from the registry")
    return problems


def main() -> int:
    problems = check()
    for p in problems:
        print(f"lint: {p}", file=sys.stderr)
    if not problems:
        sites, metrics, spans, slo = documented()
        print(f"observability registry in sync: {len(sites)} fault "
              f"sites, {len(metrics)} metrics, {len(spans)} spans, "
              f"{len(slo)} SLO objectives, "
              f"{len(documented_actions())} degradation actions, "
              f"{len(documented_endpoints())} debug endpoints")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())

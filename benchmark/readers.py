"""Per-layer metric readers.

A per-layer metric is a file of its own, ``layer_metrics/<name>.json``; its
``read`` block says where the number comes from, in one of the general
forms below.  What they cannot say is a reader in code beside the file
(``"from": "python"``, a module with ``read(obs)``).  A reader that finds
nothing to read returns None, and the harness leaves the metric out.

``obs`` is what a traced run observed inside its window:

    perf      {"evaluator": {...}, "manager": {...}}  the program's own
              busy seconds and byte counts, summed over the window
    passes    whole audit passes in the window; objects, constraints
    spans     the program's spans that started in the window (Tracer)
    hist      {series: {"count", "sum"}}  MetricsRegistry histograms, as
              the window changed them
    counts    {"compiles_in_window": n, ...}
    full_gc_s seconds the window spent in full (generation 2) collections
              of the interpreter's garbage collector (audit cells)
    loadgen   the load generator's own numbers
    trace     the device trace, reduced (xplane.reduce) + "passes"
    peaks     the chip's row of peaks.json
"""

from __future__ import annotations

import importlib.util
import os

from benchmark import stats

HERE = os.path.dirname(os.path.abspath(__file__))


def _perf(obs: dict, spec: dict):
    table = obs["perf"].get(spec["of"], {})
    if not all(k in table for k in spec["keys"]):
        return None
    return sum(table[k] for k in spec["keys"])


def read_perf(obs: dict, spec: dict):
    value = _perf(obs, spec)
    if value is None:
        return None
    if "over" in spec:
        under = _perf(obs, spec["over"])
        return value / under if under else None
    per = spec.get("per")
    if per == "pass":
        return value / obs["passes"] if obs.get("passes") else None
    if per == "object":
        n = obs.get("passes", 0) * obs.get("objects", 0)
        return value / n if n else None
    return value


def read_gc(obs: dict, spec: dict):
    value = obs.get("full_gc_s")
    if value is None:
        return None
    if spec.get("per") == "pass":
        return value / obs["passes"] if obs.get("passes") else None
    return value


def _span_value(sp: dict, what: str, children: dict):
    if what == "duration":
        return sp["duration_s"]
    if what == "self":
        # a span's self time: its duration less its direct children's
        return sp["duration_s"] - sum(
            c["duration_s"] for c in children.get(sp["span_id"], ()))
    return sp["attributes"].get(what[len("attr:"):])


def read_spans(obs: dict, spec: dict):
    spans = [sp for sp in obs.get("spans", ()) if sp["name"] == spec["name"]]
    if not spans:
        return None
    agg = spec["agg"]
    if agg == "share":
        hits = sum(1 for sp in spans if all(
            sp["attributes"].get(k) == v for k, v in spec["where"].items()))
        return hits / len(spans)
    children: dict = {}
    if spec["value"] == "self":
        for sp in obs["spans"]:
            children.setdefault(sp["parent_id"], []).append(sp)
    values = [v for v in (_span_value(sp, spec["value"], children)
                          for sp in spans) if v is not None]
    if not values:
        return None
    value = (sum(values) / len(values) if agg == "mean"
             else stats.percentile(values, float(agg[1:])))
    return value * spec.get("scale", 1)


def read_hist(obs: dict, spec: dict):
    h = obs.get("hist", {}).get(spec["name"])
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] * spec.get("scale", 1)


def read_trace(obs: dict, spec: dict):
    trace = obs.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    if spec["key"] == "idle_share":
        return 1.0 - trace["busy_s"] / trace["window_s"]
    value = trace[spec["key"]]
    if spec.get("per") == "pass":
        return value / trace["passes"] if trace.get("passes") else None
    return value


def read_python(obs: dict, spec: dict):
    path = os.path.join(HERE, "layer_metrics", spec["file"])
    mod_spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + spec["file"][:-3].replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read(obs)


READERS = {
    "perf": read_perf,
    "spans": read_spans,
    "hist": read_hist,
    "trace": read_trace,
    "python": read_python,
    "gc": read_gc,
    "counts": lambda obs, spec: obs.get("counts", {}).get(spec["key"]),
    "loadgen": lambda obs, spec: (obs.get("loadgen") or {}).get(spec["key"]),
}


def read_all(per_layer: list, obs: dict) -> dict:
    """{name: {"value", "unit"}} of every metric whose reader found its
    source."""
    out = {}
    for metric in per_layer:
        spec = metric["read"]
        value = READERS[spec["from"]](obs, spec)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out

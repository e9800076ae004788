"""``BENCHMARK.json`` and the files its names stand for.

The harness is driven by data.  A cell is ``{config, traffic, chips, why}``
and nothing more; the harness finds

- the configuration at the ``file`` its ``configs`` entry gives,
- the traffic mix at ``benchmark/traffic/<traffic>.json``,
- each per-layer metric at ``benchmark/layer_metrics/<name>.json``,

so a later PR adds a cell, a configuration, a mix or a metric as new files
and new entries, and edits no file that is there.  Standard library only.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "layer_metrics", f"{name}.json")


def apply_rehearsal(doc: dict) -> dict:
    """The toy sizes of ``--rehearse``: each ``{"a.b": value}`` of the
    file's own ``rehearse`` block replaces ``doc["a"]["b"]``."""
    for dotted, value in doc.get("rehearse", {}).items():
        at = doc
        *path, last = dotted.split(".")
        for key in path:
            at = at[key]
        at[last] = value
    return doc


class Cell:
    """A configuration under a traffic mix, with everything it names,
    loaded: an entry of ``workloads`` (``Cell(name)``), that entry under a
    mix no cell lists yet (``Cell(name, traffic=...)``: the cell is the
    mix's control, and lends it its configuration and its metrics), or a
    pair that is no cell yet and held to no metric (``Cell.unlisted``)."""

    def __init__(self, name: str, rehearse: bool = False,
                 traffic: str | None = None):
        m = read_json(MANIFEST)
        cells = {w["name"]: w for w in m["workloads"]}
        if name not in cells:
            raise KeyError(f"BENCHMARK.json has no workload {name!r}; it "
                           f"has {sorted(cells)}")
        entry = cells[name]
        cfg = next(c for c in m["configs"] if c["name"] == entry["config"])
        self._load(name if traffic is None else f"{name}.under.{traffic}",
                   os.path.join(ROOT, cfg["file"]),
                   traffic or entry["traffic"], int(entry["chips"]), rehearse)
        # the mix says which end-to-end metrics its kind of load yields;
        # the manifest says which of them this cell is held to
        self.end_to_end = [e for e in m["end_to_end"]
                           if name in e.get("workloads", [name])]
        self.per_layer = [
            {**p, **read_json(metric_path(p["name"]))}
            for p in m["per_layer"]
            if name in p.get("workloads", [name])
            and p["moves"] in {e["name"] for e in self.end_to_end}]

    @classmethod
    def unlisted(cls, config: str, traffic: str, rehearse: bool = False):
        """``configs/<config>.json`` under ``traffic/<traffic>.json`` on one
        chip, held to no metric: what ``find_knee.py`` serves before the
        pair has a rate and can become a cell."""
        self = cls.__new__(cls)
        self._load(f"{config}.{traffic}",
                   os.path.join(HERE, "configs", f"{config}.json"), traffic,
                   1, rehearse)
        self.end_to_end = self.per_layer = []
        return self

    def _load(self, name: str, config_path: str, traffic: str, chips: int,
              rehearse: bool) -> None:
        self.name = name
        self.chips = chips
        self.config = read_json(config_path)
        self.traffic = read_json(traffic_path(traffic))
        if rehearse:
            apply_rehearsal(self.config)
            apply_rehearsal(self.traffic)


def check() -> list:
    """The manifest's self-check (``run.py --check``): every fault found,
    as a line of text; none means the manifest holds together."""
    faults = []
    m = read_json(MANIFEST)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[section]]
        for n in names:
            if not NAME.match(n):
                faults.append(f"{section}: name {n!r} is not plain")
        if len(set(names)) != len(names):
            faults.append(f"{section}: a name is used twice")
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    for c in m["configs"]:
        path = os.path.join(ROOT, c["file"])
        if not os.path.exists(path):
            faults.append(f"config {c['name']}: no file {c['file']}")
            continue
        doc = read_json(path)
        for key in ("source", "assumed", "reduced", "guarantees"):
            if key not in doc:
                faults.append(f"config {c['name']}: file lacks {key!r}")
        if sorted(c["reduced"]) != sorted(doc.get("reduced", {})):
            faults.append(f"config {c['name']}: 'reduced' differs between "
                          "BENCHMARK.json and the file")
        if c["name"] not in {w["config"] for w in m["workloads"]}:
            faults.append(f"config {c['name']}: no cell uses it")
        if len(c["why"]) > 200:
            faults.append(f"config {c['name']}: 'why' is over 200 characters")
    for w in m["workloads"]:
        if w["config"] not in configs:
            faults.append(f"cell {w['name']}: no config {w['config']!r}")
        if not os.path.exists(traffic_path(w["traffic"])):
            faults.append(f"cell {w['name']}: no traffic file for "
                          f"{w['traffic']!r}")
        if w["chips"] not in (1, 4):
            faults.append(f"cell {w['name']}: chips must be 1 or 4")
        if len(w["why"]) > 200:
            faults.append(f"cell {w['name']}: 'why' is over 200 characters")
        reported = [e["name"] for e in m["end_to_end"]
                    if w["name"] in e.get("workloads", [w["name"]])]
        if "setup_s" not in reported or len(reported) < 2:
            faults.append(f"cell {w['name']}: reports {reported}; it needs "
                          "setup_s and one more end-to-end metric")
        elif os.path.exists(traffic_path(w["traffic"])):
            yields = set(read_json(traffic_path(w["traffic"]))["yields"])
            missing = set(reported) - yields - {"setup_s"}
            if missing:
                faults.append(f"cell {w['name']}: traffic {w['traffic']!r} "
                              f"does not yield {sorted(missing)}")
    for file in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        mix = read_json(os.path.join(HERE, "traffic", file))
        if "churn" in mix and "assumed" not in mix:
            faults.append(f"traffic {mix['name']!r} changes the cluster and "
                          "lists nothing under 'assumed'")
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["source"] not in SOURCES:
            faults.append(f"metric {metric['name']}: source "
                          f"{metric['source']!r}")
        for w in metric.get("workloads", []):
            if w not in cells:
                faults.append(f"metric {metric['name']}: no cell {w!r}")
    for e in m["end_to_end"]:
        if e["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end {e['name']}: source {e['source']!r}")
        if not 0.01 <= e["bound"] <= 0.25:
            faults.append(f"end-to-end {e['name']}: bound {e['bound']}")
    for p in m["per_layer"]:
        path = metric_path(p["name"])
        if not os.path.exists(path):
            faults.append(f"per-layer {p['name']}: no file {path}")
            continue
        doc = read_json(path)
        if "read" not in doc:
            faults.append(f"per-layer {p['name']}: file has no reader")
        elif doc["read"]["from"] == "python" and not os.path.exists(
                os.path.join(HERE, "layer_metrics", doc["read"]["file"])):
            faults.append(f"per-layer {p['name']}: no reader "
                          f"{doc['read']['file']}")
        if p["moves"] not in e2e:
            faults.append(f"per-layer {p['name']}: moves {p['moves']!r}, "
                          "which is no end-to-end metric")
            continue
        moved_in = e2e[p["moves"]].get("workloads", list(cells))
        for w in p.get("workloads", list(cells)):
            if w not in moved_in:
                faults.append(f"per-layer {p['name']}: reported in {w} "
                              f"where {p['moves']} is not")
    for w in m["workloads"]:
        layered = [p for p in m["per_layer"]
                   if w["name"] in p.get("workloads", [w["name"]])]
        if not layered:
            faults.append(f"cell {w['name']}: no per-layer metric")
    if not peaks:
        faults.append("peaks.json lists no device")
    for kind, row in peaks.items():
        if "source" not in row or "hbm_bytes_per_s" not in row:
            faults.append(f"peaks.json: {kind!r} lacks its source or "
                          "its bandwidth")
    return faults

"""From a ``jax.profiler`` trace to device busy and idle time.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  ``load`` turns
it into plain lists first, so that the arithmetic below runs (and is tested)
on data anyone can write down:

    planes = [{"name": str, "lines": [{"name": str, "events": [Event]}]}]
    Event  = (name, start_ns, duration_ns, {stat: value})

Times are nanoseconds from the start of the profiling session, not wall
time.  The benchmark wraps the stretch it wants measured in one
``TraceAnnotation`` named ``WINDOW`` that carries ``time.time_ns()`` as a
stat: the event gives the stretch on the trace's clock, and the stat ties
that clock to the wall clock of the program's own spans (``window``) to
within the cost of entering the annotation, some tens of microseconds.

Which events are device operations:

- a TPU: every plane named ``/device:TPU:<n>`` is one chip; its line
  ``XLA Ops`` holds one event per executed HLO operation, named by the
  operation's whole HLO text (cut here to the name before `` = ``), and its
  line ``XLA Modules`` one per executed program, ``<name>(<fingerprint>)``.
  Both carry the device's own picosecond clock as stats; the profiler has
  already put them on the host's clock, to about a millisecond (on the v5e
  host of PR 22 a program shows ~1 ms before the host call that launched
  it), so a gap is labelled reliably only if it is longer than that;
- the CPU backend (``--rehearse`` only, never a device number): operations
  run on host threads, as events of ``/host:CPU`` that carry an ``hlo_op``
  stat.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW = "benchmark.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns),
                 dict(e.stats)) for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def window(planes: list) -> tuple:
    """(start_ns, end_ns, wall ns minus trace ns) of the ``WINDOW``
    annotation."""
    for plane in planes:
        for line in plane["lines"]:
            for name, start, dur, stats in line["events"]:
                if name.startswith(WINDOW) and "wall_ns" in stats:
                    return start, start + dur, int(stats["wall_ns"]) - start
    raise ValueError(f"the trace holds no {WINDOW} annotation")


def device_ops(planes: list) -> dict:
    """{device: [(start_ns, end_ns, name)]} sorted by start.  ``name`` is
    ``<program>/<operation>`` where the trace names the program."""
    out: dict = {}
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        modules = sorted((s, s + d, n.split("(")[0])
                         for n, s, d, _ in lines.get(MODULES_LINE, ()))
        starts = [m[0] for m in modules]
        ops = []
        for name, start, dur, stats in lines.get(OPS_LINE, ()):
            program = stats.get("hlo_module")
            if program is None and modules:
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and start < modules[i][1]:
                    program = modules[i][2]
            name = name.split(" = ")[0].lstrip("%")
            ops.append((start, start + dur,
                        f"{program}/{name}" if program else name))
        out[plane["name"][len("/device:"):]] = sorted(ops)
    if out:
        return out
    ops = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur, stats in line["events"]:
                if "hlo_op" in stats:
                    ops.append((start, start + dur,
                                f"{stats.get('hlo_module', '?')}/{name}"))
    return {"CPU:0": sorted(ops)} if ops else {}


def union(intervals) -> list:
    """Sorted (start, end) pairs merged where they touch or overlap."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which at least one interval was open."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of every stretch of [lo, hi) with no interval open."""
    out, at = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most of [lo, hi)."""
    total: dict = {}
    for start, end, name in ops:
        if end > lo and start < hi:
            total[name] = total.get(name, 0.0) + \
                (min(end, hi) - max(start, lo))
    return [[name, ns / 1e9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def open_spans(spans: list, wall_s: float) -> str:
    """The program's host spans open at ``wall_s``, innermost of each
    thread, joined by '+'; '-' when none was."""
    inner: dict = {}
    for sp in spans:
        t0 = sp["start_ts"]
        if t0 <= wall_s < t0 + sp["duration_s"]:
            cur = inner.get(sp["thread_id"])
            if cur is None or t0 >= cur["start_ts"]:
                inner[sp["thread_id"]] = sp
    return "+".join(sorted({sp["name"] for sp in inner.values()})) or "-"


def label_gaps(gap_list, spans: list, offset_ns: float,
               n: int = 10) -> list:
    """[[label, seconds]] of the longest idle gaps, each labelled with the
    host spans open at its middle."""
    return [[open_spans(spans, ((s + e) / 2 + offset_ns) / 1e9),
             (e - s) / 1e9]
            for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:n]]


def reduce(planes: list, spans: list) -> dict:
    """Everything the benchmark reads from one trace, over its ``WINDOW``:
    busy seconds averaged over the devices that ran anything, the window's
    length, the top operations and the longest idle gaps of the busiest
    device."""
    lo_ns, hi_ns, offset_ns = window(planes)
    per_device = device_ops(planes)
    if not per_device:
        return {"devices": 0, "busy_s": 0.0,
                "window_s": (hi_ns - lo_ns) / 1e9,
                "device_ops": [], "idle_gaps": []}
    busy = {dev: busy_ns([(s, e) for s, e, _ in ops], lo_ns, hi_ns)
            for dev, ops in per_device.items()}
    busiest = max(busy, key=busy.get)
    ops = per_device[busiest]
    return {
        "devices": len(per_device),
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "window_s": (hi_ns - lo_ns) / 1e9,
        "device_ops": top_ops(ops, lo_ns, hi_ns),
        "idle_gaps": label_gaps(
            gaps([(s, e) for s, e, _ in ops], lo_ns, hi_ns), spans,
            offset_ns),
    }

"""What every cell's run shares: the device check and stamp, the work
directory, the compile counter, the profiler trace, and the one line of
JSON a run ends with."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys
import time

from benchmark import readers, roofline, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoDevice(RuntimeError):
    pass


class Run:
    def __init__(self, cell, seed: int, seconds: float, traced: bool,
                 rehearse: bool, t0: float):
        self.cell = cell
        self.seed = seed
        self.seconds = float(seconds)
        self.traced = traced
        self.rehearse = rehearse
        self.t0 = t0  # time.monotonic() at process start
        # fixed paths inside the checkout: one cell's runs overwrite each
        # other's scratch files, so the directory never grows
        self.work = os.path.join(HERE, ".cache", cell.name)
        os.makedirs(self.work, exist_ok=True)
        self.trace_dir = os.path.join(self.work, "trace")
        # time.monotonic() of each executable XLA was asked for, and of
        # each the persistent cache answered (the rest compiled)
        self.compiles: list = []
        self.cache_hits: list = []
        self.children: list = []  # Popen of every child still running
        # (time.monotonic() at its end, seconds) of each full collection of
        # the interpreter's garbage collector, once watch_gc() was called
        self.full_gcs: list = []
        self.device = None
        # the device's peak where a run reads it before its result is made
        # (what it sweeps after its window is no part of the cell)
        self.memory_peak = None
        self.peaks = None
        self.marks: dict = {}  # set-up phase -> seconds since the last mark
        self._marked = t0

    def mark(self, phase: str) -> None:
        """Where set-up's time went: a note for PERF.md, not a metric."""
        now = time.monotonic()
        self.marks[phase] = now - self._marked
        self._marked = now

    # --- the device --------------------------------------------------------
    def require_device(self) -> dict:
        """Touch JAX, and refuse to go on without the chips the cell asks
        for.  ``--rehearse`` alone runs on whatever JAX finds, and says
        so.  The C columnizers build first: their compiler is a child, and
        children come before JAX."""
        from gatekeeper_tpu.ops import native

        if native.load() is None or native.load_json() is None:
            raise RuntimeError("the native columnizers failed to build")
        import jax
        import jax.monitoring

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if not self.rehearse:
            if devs[0].platform != "tpu":
                raise NoDevice(f"JAX found platform {devs[0].platform!r}, "
                               "not a TPU")
            if len(devs) < self.cell.chips:
                raise NoDevice(f"the cell asks for {self.cell.chips} chips, "
                               f"JAX found {len(devs)}")
            self.peaks = roofline.peaks_for(devs[0].device_kind)
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        jax.monitoring.register_event_listener(self._cache_hit)
        return self.device

    def _compiled(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(time.monotonic())

    def _cache_hit(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits.append(time.monotonic())

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.compiles if lo <= t < hi)

    def memory_peak_bytes(self) -> int:
        import jax

        peak = 0
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    # --- the interpreter's garbage collector ---------------------------------
    def watch_gc(self) -> None:
        """Time every full (generation 2) collection from here on.  The
        program runs CPython's collector at its defaults, and a full
        collection walks every container object the process holds."""
        import gc

        began = [0.0]

        def timed(phase: str, info: dict) -> None:
            if info["generation"] != 2:
                return
            now = time.monotonic()
            if phase == "start":
                began[0] = now
            else:
                self.full_gcs.append((now, now - began[0]))

        gc.callbacks.append(timed)

    def full_gc_s_between(self, lo: float, hi: float) -> float:
        return sum(s for t, s in self.full_gcs if lo <= t < hi)

    # --- children ------------------------------------------------------------
    def spawn(self, argv: list, **kw) -> subprocess.Popen:
        """A JAX-free child.  It is told to keep to the CPU all the same:
        a chip belongs to one process."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.Popen([sys.executable, *argv], env=env, **kw)
        self.children.append(p)
        return p

    def reap(self) -> None:
        """No process outlives the run."""
        for p in self.children:
            if p.poll() is None:
                p.kill()
            p.wait()
        self.children.clear()

    # --- the profiler ----------------------------------------------------------
    @contextlib.contextmanager
    def device_trace(self):
        """A ``jax.profiler`` trace around a stretch of the window.  The
        stretch itself is the ``xplane.WINDOW`` annotation inside it."""
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # a Python call trace is most of a file
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW,
                                              wall_ns=time.time_ns()):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce_trace(self, spans: list) -> dict:
        return xplane.reduce(xplane.load(xplane.find(self.trace_dir)), spans)

    # --- the result ------------------------------------------------------------
    def result(self, correct: bool, attempted: int, failed: int,
               end_to_end: dict, obs: dict, notes: dict,
               compared: dict | None = None) -> dict:
        """The run's one line.  ``--trace 0`` carries the cell's end-to-end
        metrics, ``--trace 1`` its per-layer metrics, the device's busy
        seconds and the breakdown.  ``compared``: {name: {"value",
        "limit"}} of every number that decided ``correct``; it comes last
        in the line and is the last that standard error says."""
        device = dict(self.device, memory_peak_bytes=(
            self.memory_peak_bytes() if self.memory_peak is None
            else self.memory_peak))
        out = {"correct": bool(correct), "attempted": int(attempted),
               "failed": int(failed)}
        if not self.traced:
            units = {e["name"]: e["unit"] for e in self.cell.end_to_end}
            out["metrics"] = {name: {"value": float(v), "unit": units[name]}
                              for name, v in end_to_end.items()
                              if name in units}
            missing = set(units) - set(out["metrics"])
            if missing:
                raise RuntimeError(f"the run measured no {sorted(missing)}")
        else:
            obs["peaks"] = self.peaks
            out["metrics"] = readers.read_all(self.cell.per_layer, obs)
            trace = obs["trace"]
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            out["breakdown"] = {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]}
        out["device"] = device
        if self.rehearse:
            out["rehearsal"] = ("toy sizes on platform "
                                f"{self.device['platform']}: no number of "
                                "this line is a measurement")
        # what a reader wants beside the numbers goes to stderr and to a
        # file of the work directory; the result line stays as specified
        notes = dict(notes, setup_phases_s=self.marks,
                     xla_cache_hits=len(self.cache_hits),
                     xla_executables=len(self.compiles),
                     workload=self.cell.name,
                     seed=self.seed, traced=self.traced, result=out)
        with open(os.path.join(self.work, "notes.json"), "w") as f:
            json.dump(notes, f)
        print("benchmark: notes: " + json.dumps(notes), file=sys.stderr)
        if compared is not None:
            out["compared"] = compared
            print(f"benchmark: correct={out['correct']}, compared: "
                  + json.dumps(compared), file=sys.stderr)
        return out

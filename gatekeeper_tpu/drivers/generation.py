"""Generations: background compile, executable swap, on-disk compile cache.

A **generation** is an immutable compilation unit — the ``(template set,
union schema, vocab snapshot)`` the serving paths evaluate with.  Today a
``ConstraintTemplate`` add/edit recompiles on the serving path (lowering +
union-schema reshape + jit retrace all land inside ``add_template``), so a
template-churn storm stalls admissions.  With ``--generation-swap on`` the
:class:`GenerationCoordinator` moves that work off the hot path:

- template/constraint mutations *stage* (cheap synchronous validation only
  — parse + interpreter/CEL compile, so reconcile status and readiness
  semantics are unchanged) and enqueue a background build;
- the background thread lowers the changed templates against the *current*
  vocab (the vocab is append-only, so programs of the old generation stay
  valid while the new one builds), reuses unchanged programs by source
  digest, warms the changed kernels with one ``warm_pass``-shaped
  dispatch, then **atomically swaps** the serving dicts;
- the webhook, audit sweep and mutation lane keep serving the old
  generation until the swap, and in-flight batches finish on the
  generation they started on (they capture the program dict once — swap
  replaces dict objects, never mutates them).

The :class:`CompileCache` persists lowering results to disk, keyed by
``(template digest, engine, jax/jaxlib version,``
``ops.flatten.FLATTEN_SCHEMA_VERSION, cache format)``.  Each entry also
records the full vocab string snapshot at lowering completion: loading
replays the snapshot (append-only interning), and an entry whose snapshot
is not reachable from the current vocab state (different template order, a
process that already interned conflicting strings) is a miss — baked sids
can never silently point at the wrong strings.  Corrupted or
version-drifted entries are rejected (and deleted) on load, never served.
XLA executables persist separately, in JAX's own compilation cache
(utils/xla_cache.py places it; ``--compile-cache`` does not move it).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Optional

from gatekeeper_tpu.ops.flatten import FLATTEN_SCHEMA_VERSION

# bump when the on-disk payload layout changes
CACHE_FORMAT = 1

# miss reasons for gatekeeper_generation_cache_miss_count{reason}
MISS_COLD = "cold"          # no entry on disk
MISS_CORRUPT = "corrupt"    # unreadable meta / payload hash or pickle fail
MISS_DIGEST = "digest"      # entry's recorded key fields disagree
MISS_SCHEMA = "schema"      # program schema digest != recorded
MISS_VOCAB = "vocab"        # vocab snapshot not replayable here


def template_digest(template) -> str:
    """Content digest of one template — the per-kind cache/reuse key.
    Canonical JSON over the raw object's spec (the compilation input);
    programmatically-built templates without a raw doc fall back to the
    parsed fields."""
    raw = getattr(template, "raw", None) or {}
    doc: Any = raw.get("spec") if isinstance(raw, dict) else None
    if not doc:
        doc = {
            "name": template.name,
            "kind": template.kind,
            "schema": template.parameters_schema,
            "targets": [getattr(t, "raw", None) or repr(t)
                        for t in template.targets],
        }
    blob = json.dumps(doc, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


def template_set_digest(digests) -> str:
    """Digest of a whole template set (order-independent) — the
    generation identity exported on the ``compile.generation`` span."""
    blob = "\n".join(sorted(digests))
    return hashlib.sha256(blob.encode()).hexdigest()


def schema_digest(schema) -> str:
    """Stable digest of a lowered program's (or a union) Schema — a
    load-time integrity check on cached entries: a payload whose
    unpickled schema does not reproduce the digest recorded at store
    time is rejected."""
    if schema is None:
        return "none"
    parts = (schema.scalars, schema.raggeds, schema.keysets,
             getattr(schema, "ragged_keysets", []),
             getattr(schema, "map_keys", []),
             getattr(schema, "parent_idx", []),
             getattr(schema, "canons", []),
             getattr(schema, "extra_axes", []))
    return hashlib.sha256(repr(parts).encode()).hexdigest()


class CompileCache:
    """On-disk lowering cache (one entry per template content digest).

    Key anatomy (all baked into the entry file name, so any drift is a
    clean miss, and re-validated from the meta on load, so a tampered or
    hash-collided entry is rejected):

    ``sha256(template digest | engine | jax version | jaxlib version |``
    ``flatten-schema version | cache format)``

    Entry = ``<key>.json`` (meta: key fields, payload sha256, schema
    digest) + ``<key>.pkl`` (pickled program-or-error + the vocab string
    snapshot).  Writes are tmp-file + rename, so a crashed writer leaves
    no half entry.
    """

    def __init__(self, root: str, metrics=None):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.metrics = metrics
        self.hits = 0
        self.misses = 0
        self.miss_reasons: dict = {}
        self.stores = 0

    # --- keys ----------------------------------------------------------
    @staticmethod
    def _versions() -> tuple:
        import jax

        try:
            import jaxlib

            jl = getattr(jaxlib, "__version__", "?")
        except Exception:
            jl = "?"
        return jax.__version__, jl

    def entry_key(self, tdigest: str, engine: str) -> str:
        jv, jlv = self._versions()
        blob = "|".join([tdigest, engine, jv, jlv,
                         str(FLATTEN_SCHEMA_VERSION), str(CACHE_FORMAT)])
        return hashlib.sha256(blob.encode()).hexdigest()[:40]

    def _paths(self, key: str) -> tuple:
        return (os.path.join(self.root, key + ".json"),
                os.path.join(self.root, key + ".pkl"))

    # --- accounting ----------------------------------------------------
    def _count(self, hit: bool, reason: str = "") -> None:
        if hit:
            self.hits += 1
        else:
            self.misses += 1
            self.miss_reasons[reason] = \
                self.miss_reasons.get(reason, 0) + 1
        if self.metrics is not None:
            from gatekeeper_tpu.metrics import registry as M

            if hit:
                self.metrics.inc_counter(M.GENERATION_CACHE_HIT)
            else:
                self.metrics.inc_counter(M.GENERATION_CACHE_MISS,
                                         {"reason": reason})

    def _reject(self, key: str, reason: str) -> None:
        """A corrupted/stale entry is deleted so the rebuild can replace
        it — it must never be served."""
        self._count(False, reason)
        for p in self._paths(key):
            try:
                os.remove(p)
            except OSError:
                pass

    # --- load / store ---------------------------------------------------
    def get(self, tdigest: str, engine: str, vocab):
        """``("program", Program) | ("error", msg) | None``.

        A hit replays the entry's vocab snapshot into ``vocab`` (the
        current vocab state must be a prefix of the snapshot — identical
        template load order from a cold start always is), so every sid
        the cached program baked points at the same string here."""
        key = self.entry_key(tdigest, engine)
        meta_p, payload_p = self._paths(key)
        if not (os.path.exists(meta_p) and os.path.exists(payload_p)):
            self._count(False, MISS_COLD)
            return None
        try:
            with open(meta_p) as f:
                meta = json.load(f)
            with open(payload_p, "rb") as f:
                raw = f.read()
        except Exception:
            self._reject(key, MISS_CORRUPT)
            return None
        jv, jlv = self._versions()
        want = {"template_digest": tdigest, "engine": engine,
                "jax": jv, "jaxlib": jlv,
                "flatten_schema_version": FLATTEN_SCHEMA_VERSION,
                "format": CACHE_FORMAT}
        if any(meta.get(k) != v for k, v in want.items()):
            self._reject(key, MISS_DIGEST)
            return None
        if hashlib.sha256(raw).hexdigest() != meta.get("payload_sha256"):
            self._reject(key, MISS_CORRUPT)
            return None
        try:
            payload = pickle.loads(raw)
            program = payload["program"]
            error = payload["error"]
            snap = payload["vocab"]
        except Exception:
            self._reject(key, MISS_CORRUPT)
            return None
        if program is not None and \
                schema_digest(program.schema) != meta.get("schema_digest"):
            self._reject(key, MISS_SCHEMA)
            return None
        # vocab replay: current interned strings must be the snapshot's
        # prefix (same ids for everything already interned); then the
        # tail interns in recorded order, reproducing every baked sid
        cur = vocab._to_str
        if len(cur) > len(snap) or snap[: len(cur)] != cur:
            self._count(False, MISS_VOCAB)  # entry itself is fine
            return None
        for s in snap[len(cur):]:
            vocab.intern(s)
        self._count(True)
        if error is not None:
            return ("error", error)
        return ("program", program)

    def put(self, tdigest: str, engine: str, program, error: Optional[str],
            vocab) -> None:
        """Persist one lowering result (or its LowerError message) with
        the vocab snapshot at completion.  Best-effort: cache write
        failures never fail the compile."""
        key = self.entry_key(tdigest, engine)
        meta_p, payload_p = self._paths(key)
        jv, jlv = self._versions()
        try:
            raw = pickle.dumps({"program": program, "error": error,
                                "vocab": list(vocab._to_str)})
            meta = {"template_digest": tdigest, "engine": engine,
                    "jax": jv, "jaxlib": jlv,
                    "flatten_schema_version": FLATTEN_SCHEMA_VERSION,
                    "format": CACHE_FORMAT,
                    "payload_sha256": hashlib.sha256(raw).hexdigest(),
                    "schema_digest": (schema_digest(program.schema)
                                      if program is not None else "none"),
                    "stored_at": time.time()}
            tmp = payload_p + ".tmp"
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, payload_p)
            tmp = meta_p + ".tmp"
            with open(tmp, "w") as f:
                json.dump(meta, f)
            os.replace(tmp, meta_p)
            self.stores += 1
        except Exception:
            pass

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "miss_reasons": dict(self.miss_reasons),
                "stores": self.stores}


def warm_yield_s(cpu_count: Optional[int] = None) -> float:
    """Per-kernel cooperative-yield gap for the pre-swap warm.

    Tracing is GIL-held Python: on a 1-core host, back-to-back kernel
    traces starve the serving thread for the whole warm, so each trace
    leaves a bounded 5ms gap (``tests/test_generation.py`` pins the
    1-core value).  Hosts with spare
    cores need (almost) none: the serving thread runs on another core
    while the warm traces, and every gap only stretches the warm —
    which delays the swap the serving path is waiting on.  Few-core
    (2-3) hosts keep a token 1ms: the GIL is still shared even when
    the cores are not saturated."""
    n = cpu_count if cpu_count is not None else (os.cpu_count() or 1)
    if n <= 1:
        return 0.005
    if n < 4:
        return 0.001
    return 0.0


def programs_digest(driver) -> str:
    """Digest of the installed compiled plan (kind -> program schema) —
    the warm-state cache key: recorded executable layouts only replay
    against the exact program schemas they were traced with."""
    parts = sorted((k, schema_digest(p.program.schema))
                   for k, p in driver._programs.items())
    return hashlib.sha256(repr(parts).encode()).hexdigest()


# bump when the warm-state payload layout changes
WARM_FORMAT = 1


def library_warm_dir(root: str, library_digest: str) -> str:
    """Per-library :class:`WarmStateCache` directory under one shared
    compile-cache root (fleet mode).  The lowering entries are
    template-digest-keyed, so N libraries SHARE the root — but warm
    state is one file per directory, validated against the
    installed-programs digest, so libraries sharing a root would
    overwrite each other's.  One subdir per template-set digest keeps
    every library's warm state resident beside the shared lowerings."""
    return os.path.join(root, "warm", (library_digest or "default")[:16])


class WarmStateCache:
    """Persisted warm execution state under the compile-cache dir.

    The compile cache (above) removes restart LOWERING; this removes the
    restart RETRACE: the fused sweep executables' trace descriptors +
    input avals (``ShardedEvaluator.warm_state``: recorded keys, corpus
    column stats, width targets, hit-buffer state) and the admission
    path's warm reference batch (``TpuDriver._warm_ref`` — the latest
    real admission batch, the only thing that traces kernels at the true
    serving shapes).  On boot, :meth:`replay` re-lands every trace off
    the serving path — with the persistent XLA cache answering the
    compiles — so a restarted process retraces nothing on its first
    tick or admission burst.

    Integrity mirrors :class:`CompileCache`: payload sha256 + format /
    jax / flatten-schema fields + the installed-programs digest in the
    meta; corrupt or drifted state is deleted and simply not replayed
    (the process falls back to lazy tracing — never wrong, just cold).
    """

    def __init__(self, root: str, metrics=None):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.metrics = metrics
        self.saves = 0
        self.loads = 0
        self.misses = 0

    def _paths(self) -> tuple:
        return (os.path.join(self.root, "warm_state.json"),
                os.path.join(self.root, "warm_state.pkl"))

    def save(self, driver, evaluator=None) -> bool:
        """Best-effort: a failed save never fails the caller (drain)."""
        meta_p, payload_p = self._paths()
        try:
            payload = {
                "sweeps": (evaluator.warm_state()
                           if evaluator is not None else None),
                "warm_ref": getattr(driver, "_warm_ref", None),
            }
            raw = pickle.dumps(payload)
            jv, jlv = CompileCache._versions()
            meta = {"format": WARM_FORMAT,
                    "flatten_schema_version": FLATTEN_SCHEMA_VERSION,
                    "jax": jv, "jaxlib": jlv,
                    "programs": programs_digest(driver),
                    "payload_sha256": hashlib.sha256(raw).hexdigest(),
                    "saved_at": time.time()}
            tmp = payload_p + ".tmp"
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, payload_p)
            tmp = meta_p + ".tmp"
            with open(tmp, "w") as f:
                json.dump(meta, f)
            os.replace(tmp, meta_p)
            self.saves += 1
            return True
        except Exception:
            return False

    def _reject(self) -> None:
        self.misses += 1
        for p in self._paths():
            try:
                os.remove(p)
            except OSError:
                pass

    def load(self, driver):
        """The validated payload, or None (corrupt/drifted state is
        deleted, never replayed)."""
        meta_p, payload_p = self._paths()
        if not (os.path.exists(meta_p) and os.path.exists(payload_p)):
            self.misses += 1
            return None
        try:
            with open(meta_p) as f:
                meta = json.load(f)
            with open(payload_p, "rb") as f:
                raw = f.read()
        except Exception:
            self._reject()
            return None
        jv, jlv = CompileCache._versions()
        want = {"format": WARM_FORMAT,
                "flatten_schema_version": FLATTEN_SCHEMA_VERSION,
                "jax": jv, "jaxlib": jlv,
                "programs": programs_digest(driver)}
        if any(meta.get(k) != v for k, v in want.items()):
            self._reject()
            return None
        if hashlib.sha256(raw).hexdigest() != meta.get("payload_sha256"):
            self._reject()
            return None
        try:
            payload = pickle.loads(raw)
        except Exception:
            self._reject()
            return None
        self.loads += 1
        return payload

    def replay(self, driver, evaluator=None) -> dict:
        """Load + re-land: sweep traces through
        ``ShardedEvaluator.replay_warm`` and — when a generation
        coordinator exists — the admission kernels through a
        ``warm_serving`` pass over the restored ``_warm_ref``."""
        payload = self.load(driver)
        if payload is None:
            return {"hit": False, "sweep_traces": 0}
        landed = 0
        if payload.get("sweeps") is not None and evaluator is not None:
            evaluator.restore_warm_state(payload["sweeps"])
            landed = evaluator.replay_warm()
        ref = payload.get("warm_ref")
        if ref is not None:
            driver._warm_ref = tuple(ref)
            if driver.gen_coord is not None:
                driver.gen_coord.warm_serving()
        return {"hit": True, "sweep_traces": landed}


class _Staged:
    """One staged template: synchronously-validated artifacts waiting for
    the next generation build."""

    __slots__ = ("template", "engine", "artifact", "digest")

    def __init__(self, template, engine: str, artifact, digest: str):
        self.template = template
        self.engine = engine  # "rego" | "cel"
        self.artifact = artifact  # interp/CEL compiled template
        self.digest = digest


class Generation:
    """One built (not necessarily yet swapped-in) generation."""

    __slots__ = ("gen_id", "programs", "lower_errors", "cel_kinds",
                 "interp_templates", "cel_templates", "set_digest",
                 "compile_seconds", "reused", "lowered_fresh",
                 "cache_hits")

    def __init__(self, gen_id: int):
        self.gen_id = gen_id
        self.programs: dict = {}       # kind -> CompiledProgram
        self.lower_errors: dict = {}   # kind -> why fallback
        self.cel_kinds: set = set()
        self.interp_templates: dict = {}  # kind -> rego _CompiledTemplate
        self.cel_templates: dict = {}     # kind -> _CompiledCELTemplate
        self.set_digest = ""
        self.compile_seconds = 0.0
        self.reused = 0         # programs carried over unchanged
        self.lowered_fresh = 0  # kinds actually lowered this build
        self.cache_hits = 0     # kinds answered by the disk cache


class GenerationCoordinator:
    """Owns the desired template set and the background compile thread.

    Until :meth:`start` is called (boot, --once runs, in-process tests)
    every mutation builds-and-swaps *inline* on the caller thread —
    byte-for-byte today's behavior, just routed through the generation
    build (so the compile cache serves boot loads too).  After
    :meth:`start`, mutations stage + notify and the thread coalesces a
    churn burst into one build."""

    def __init__(self, driver, cache: Optional[CompileCache] = None,
                 metrics=None, warm: bool = True):
        self.driver = driver
        self.cache = cache
        self.metrics = metrics
        self.warm = warm
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._desired: dict = {}   # kind -> _Staged (insertion order)
        self._installed_digests: dict = {}  # kind -> digest (serving gen)
        self._dirty = False
        self._building = False
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self.gen_id = 0
        self.swap_count = 0
        self.last_error: Optional[str] = None
        self.compile_count = 0
        # optional live-constraint source (e.g. Client.constraints): the
        # pre-swap warm then traces each changed kernel at the REAL
        # serving shape (param-table rows = that kind's constraint
        # count), so the first post-swap batch reuses the warm trace
        # instead of retracing on the serving thread
        self.constraints_fn = None
        # auxiliary compile units (the mutation lane's revision-keyed
        # programs ride the same background machinery):
        # name -> (current_key_fn, build_fn, install_fn, installed_key)
        self._aux: dict = {}
        # DeviceResidency instances to evict at swap (stale-HBM release;
        # see snapshot/device_residency.py)
        self._residencies: list = []

    def attach_residency(self, residency) -> None:
        """Register a snapshot DeviceResidency for proactive eviction at
        every generation swap (its mirrors were packed under the old
        programs' schemas)."""
        with self._lock:
            self._residencies.append(residency)

    # --- lifecycle ------------------------------------------------------
    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> "GenerationCoordinator":
        """Go asynchronous: post-boot mutations compile off the serving
        path.  Also arms the vocab intern lock — the background thread
        interns against the live vocab."""
        with self._lock:
            if self.running:
                return self
            vocab = self.driver.vocab
            if getattr(vocab, "_lock", None) is None:
                vocab._lock = threading.RLock()
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name="generation-compile", daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        with self._lock:
            self._stop = True
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until no build is pending or in flight (tests/benches:
        'quiesce, then assert verdicts')."""
        end = time.monotonic() + timeout
        with self._cv:
            while (self._dirty or self._building
                   or self._aux_dirty_locked()):
                remaining = end - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(min(remaining, 0.05))
        return True

    # --- aux compile units (mutlane) ------------------------------------
    def register_aux(self, name: str, current_key_fn, build_fn,
                     install_fn) -> None:
        with self._lock:
            self._aux[name] = [current_key_fn, build_fn, install_fn, None]

    def note_aux_dirty(self, name: str) -> None:
        with self._lock:
            self._cv.notify_all()

    def _aux_dirty_locked(self) -> bool:
        for key_fn, _b, _i, installed in self._aux.values():
            try:
                if key_fn() != installed:
                    return True
            except Exception:
                pass
        return False

    # --- staging (driver-facing) ----------------------------------------
    def submit_add(self, template) -> None:
        """Validate synchronously (parse/compile errors raise HERE, so
        reconcile status + readiness behave exactly as inline compile),
        stage, and either notify the background thread or — when it is
        not running — build + swap inline."""
        driver = self.driver
        if not driver._interp.has_source_for(template) and \
                driver._cel is not None and \
                driver._cel.has_source_for(template):
            engine = "cel"
            artifact = driver._cel.compile_template(template)
        else:
            engine = "rego"
            artifact = driver._interp.compile_template(template)
        staged = _Staged(template, engine, artifact,
                         template_digest(template))
        with self._lock:
            self._desired.pop(template.kind, None)
            self._desired[template.kind] = staged
            self._dirty = True
            if self.running:
                self._cv.notify_all()
                return
        self._build_and_swap()

    def submit_remove(self, kind: str) -> None:
        with self._lock:
            self._desired.pop(kind, None)
            self._dirty = True
            if self.running:
                self._cv.notify_all()
                return
        self._build_and_swap()

    def is_staged(self, kind: str) -> bool:
        """True when the kind is in the desired set (serving or pending
        swap) — constraint adds for a staged-not-yet-swapped template
        must be accepted, not rejected as unknown."""
        with self._lock:
            return kind in self._desired

    # --- the background loop --------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                while not (self._dirty or self._stop
                           or self._aux_dirty_locked()):
                    self._cv.wait(0.5)
                if self._stop:
                    return
            try:
                self._build_and_swap()
            except Exception as e:
                # a failed build leaves the serving generation untouched;
                # the next churn event retries
                with self._lock:
                    self.last_error = str(e)

    def _build_and_swap(self) -> None:
        from gatekeeper_tpu.observability import tracing

        with self._lock:
            desired = dict(self._desired)
            template_dirty = self._dirty
            self._dirty = False
            self._building = True
            aux_work = [(name, entry) for name, entry in self._aux.items()]
        try:
            if template_dirty:
                t0 = time.perf_counter()
                with tracing.span("compile.generation",
                                  templates=len(desired)) as sp:
                    gen = self._build(desired)
                    gen.compile_seconds = time.perf_counter() - t0
                    sp.set_attribute("gen_id", gen.gen_id)
                    sp.set_attribute("reused", gen.reused)
                    sp.set_attribute("lowered", gen.lowered_fresh)
                    sp.set_attribute("cache_hits", gen.cache_hits)
                # warm only on the BACKGROUND lane: the point is that the
                # swap lands pre-traced executables while the old
                # generation still serves; an inline (pre-start) caller
                # is already on the serving path and boot warms anyway
                if self.warm and self.running:
                    self._warm(gen)
                self._swap(gen, desired)
            # aux units (mutlane): rebuild whichever drifted
            for name, entry in aux_work:
                key_fn, build_fn, install_fn = entry[0], entry[1], entry[2]
                try:
                    key = key_fn()
                except Exception:
                    continue
                if key == entry[3]:
                    continue
                with tracing.span("compile.generation", unit=name):
                    built = build_fn()
                install_fn(built)
                with self._lock:
                    entry[3] = key
        finally:
            with self._cv:
                self._building = False
                self._cv.notify_all()

    def _build(self, desired: dict) -> Generation:
        """Compile the next generation: reuse unchanged programs by
        source digest, answer changed kinds from the disk cache when the
        vocab snapshot replays, lower the rest.  The chaos seam
        ``compile.generation`` lets tests kill a build mid-flight and
        assert the serving generation survives."""
        from gatekeeper_tpu.resilience.faults import fault_point

        fault_point("compile.generation", n=len(desired))
        driver = self.driver
        with self._lock:
            gen = Generation(self.gen_id + 1)
        self.compile_count += 1
        serving_programs = driver._programs
        serving_errors = driver._lower_errors
        for kind, staged in desired.items():
            if staged.engine == "cel":
                gen.cel_kinds.add(kind)
                gen.cel_templates[kind] = staged.artifact
            else:
                gen.interp_templates[kind] = staged.artifact
            if self._installed_digests.get(kind) == staged.digest:
                # unchanged template: the serving program object (or its
                # recorded lowering error) carries over — the vocab is
                # append-only, so old programs stay valid forever
                if kind in serving_programs:
                    gen.programs[kind] = serving_programs[kind]
                    gen.reused += 1
                    continue
                if kind in serving_errors:
                    gen.lower_errors[kind] = serving_errors[kind]
                    gen.reused += 1
                    continue
            program, err, from_cache = driver._lower_staged(staged)
            if from_cache:
                gen.cache_hits += 1
            else:
                gen.lowered_fresh += 1
            if program is not None:
                gen.programs[kind] = program
            elif err is not None:
                gen.lower_errors[kind] = err
        gen.set_digest = template_set_digest(
            s.digest for s in desired.values())
        return gen

    def _warm(self, gen: Generation) -> None:
        """One warm_pass-shaped dispatch over the WHOLE next generation.

        Why every kind, not just the changed ones: the serving batch
        flattens under the union schema of all lowered kinds, and the
        flattener's prefix-axis dedup re-pads SHARED ragged columns
        when any template joins or leaves the union — so one edit can
        reshape every program's input avals (measured: one
        library-template removal retraced all 45 remaining kernels,
        ~4s on the serving thread).  Tracing happens here, on the
        compile thread, against the new union + the real constraint
        counts (``constraints_fn``); the post-swap serving burst then
        reuses these traces.  Param tables build for ALL kinds before
        any run so string-pred matrices bake their final row count
        (the warm_pass ordering rule).  Best-effort: warm failures
        must never block the swap."""
        from gatekeeper_tpu.apis.constraints import Constraint
        from gatekeeper_tpu.ir.program import build_param_table
        from gatekeeper_tpu.ops.flatten import Flattener, Schema

        driver = self.driver
        kinds = sorted(gen.programs)
        if not kinds:
            return
        try:
            cons_by_kind: dict = {}
            if self.constraints_fn is not None:
                try:
                    for c in self.constraints_fn():
                        cons_by_kind.setdefault(c.kind, []).append(c)
                except Exception:
                    cons_by_kind = {}
            schema = Schema()
            for kind in kinds:
                schema.merge(gen.programs[kind].program.schema)
            fl = Flattener(schema, driver.vocab)
            ref = getattr(driver, "_warm_ref", None)
            if ref is not None:
                # replay the latest REAL admission batch through the new
                # union: ragged pad widths are data-dependent, so only
                # real objects land the traces at the serving shapes
                objects, review_docs, pad_n = ref
                batch = fl.flatten(objects, pad_n=pad_n,
                                   reviews=review_docs)
            else:
                batch = fl.flatten([dict(_WARM_OBJ)],
                                   pad_n=driver.batch_bucket)
            tables = {}
            for kind in kinds:  # register every needle row before runs
                prog = gen.programs[kind]
                cons = cons_by_kind.get(kind) or [
                    Constraint(kind=kind, name="__gen_warm__", match={},
                               parameters={}, enforcement_action="deny")]
                tables[kind] = build_param_table(prog.program, cons,
                                                 driver.vocab)
            gap = warm_yield_s()
            for kind in kinds:
                prog = gen.programs[kind]
                prog.run(batch, tables[kind], vocab=driver.vocab,
                         extra_cols=driver.inventory_cols(
                             kind, programs=gen.programs)[0])
                # cooperative yield between kernel traces: tracing is
                # GIL-held Python, and on few-core hosts back-to-back
                # traces would otherwise starve the serving thread for
                # the whole warm — one bounded gap per kernel keeps the
                # storm P99 near one trace, not the sum of all of them.
                # Sized from the host's core count (warm_yield_s): on a
                # many-core host the serving thread runs on its own
                # core, so the gap only stretches the warm for nothing
                if gap:
                    time.sleep(gap)
        except Exception as e:
            with self._lock:
                self.last_error = f"warm: {e}"

    def warm_serving(self) -> None:
        """Warm the CURRENT serving generation at the persisted
        ``_warm_ref`` shapes — the WarmStateCache boot replay's
        admission-side half.  Runs :meth:`_warm` over a pseudo
        generation holding the serving programs; traces land on the
        caller (boot) thread before any traffic, so the first real
        admission burst retraces nothing."""
        gen = Generation(self.gen_id)
        gen.programs = dict(self.driver._programs)
        self._warm(gen)

    def _swap(self, gen: Generation, desired: dict) -> None:
        self.driver._install_generation(gen)
        with self._lock:
            self.gen_id = gen.gen_id
            self.swap_count += 1
            self.last_error = None
            self._installed_digests = {
                k: s.digest for k, s in desired.items()}
            residencies = list(self._residencies)
        # device-resident snapshot mirrors were packed under the OLD
        # generation's schemas: correctness is already covered (each
        # mirror's program-uid signature misses on next prepare), this
        # eviction just frees the stale HBM now instead of one tick later
        for res in residencies:
            try:
                res.invalidate()
            except Exception:
                pass
        if self.metrics is not None:
            from gatekeeper_tpu.metrics import registry as M

            self.metrics.set_gauge(M.GENERATION_ID, gen.gen_id)
            self.metrics.set_gauge(M.GENERATION_COMPILE_SECONDS,
                                   gen.compile_seconds)
            self.metrics.inc_counter(M.GENERATION_SWAP_COUNT)

    # --- introspection ---------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "gen_id": self.gen_id,
                "swap_count": self.swap_count,
                "pending": self._dirty or self._building,
                "templates": len(self._desired),
                "last_error": self.last_error,
                "background": self.running,
            }
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out


# the warm object: a plausible small Pod — ragged container axes get a
# non-empty width so the warm flatten pads shared axes the way a real
# admission burst does (width buckets make wider bursts share the shape)
_WARM_OBJ = {
    "apiVersion": "v1", "kind": "Pod",
    "metadata": {"name": "generation-warm", "namespace": "default",
                 "labels": {"app": "warm"}},
    "spec": {"containers": [{"name": "c", "image": "warm:latest"}]},
}

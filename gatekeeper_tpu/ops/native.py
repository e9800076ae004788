"""Lazy build + load of the native flattener (native/flattenmod.c).

Builds with the in-image toolchain (g++/cc via setuptools, no network); on
any failure the Python flattener in ops/flatten.py remains authoritative.
"""

from __future__ import annotations

import os
import subprocess
import sys
import sysconfig
import threading
from typing import Optional

_BUILD_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native",
                          "build")
_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")

_mods: dict = {}
_tried: set = set()
# two flatten workers ask for one module at once on a process's first
# chunk: the second waits for the first's build instead of reading "tried,
# none there" and sending its chunk down the dict lane
_load_lock = threading.Lock()


def _load_named(name: str, src_file: str) -> Optional[object]:
    if name in _mods:
        return _mods[name]
    with _load_lock:
        return _load_named_locked(name, src_file)


def _load_named_locked(name: str, src_file: str) -> Optional[object]:
    if name in _mods or name in _tried:
        return _mods.get(name)
    _tried.add(name)
    if not os.path.exists(os.path.join(_NATIVE_DIR, src_file)):
        # no source (installed wheel): the prebuilt module is the only
        # option.  When the source IS present, go through _build so the
        # binary is the one keyed on this source's content even if a
        # sibling module already put a build dir on sys.path (an edited
        # .c must not silently run as the previous binary)
        try:
            import importlib

            _mods[name] = importlib.import_module(name)
            return _mods[name]
        except ImportError:
            pass
    try:
        _mods[name] = _build(name, src_file)
    except subprocess.CalledProcessError as e:
        sys.stderr.write(
            f"{name} build failed ({e}):\n{e.stderr}\n"
            "using Python flattener\n"
        )
        _mods[name] = None
    except Exception as e:  # build env problems -> Python fallback
        sys.stderr.write(f"{name} build failed ({e}); "
                         "using Python flattener\n")
        _mods[name] = None
    return _mods[name]


def load() -> Optional[object]:
    """The dict-walking columnizer (native/flattenmod.c)."""
    return _load_named("gtpu_flatten", "flattenmod.c")


def load_json() -> Optional[object]:
    """The threaded JSON columnizer (native/flattenjsonmod.c)."""
    return _load_named("gtpu_flattenjson", "flattenjsonmod.c")


def load_listroute() -> Optional[object]:
    """The audit lister's per-object routing (native/listroutemod.c),
    bound to the RawJSON class whose slots it reads."""
    if "gtpu_listroute" not in _tried:
        mod = _load_named("gtpu_listroute", "listroutemod.c")
        if mod is not None:
            from gatekeeper_tpu.utils import rawjson

            # what route() takes off the collector's lists a load puts
            # back: in place before bind(), without which route() refuses
            rawjson._gc_track = mod.track
            try:
                mod.bind(rawjson.RawJSON)
            except TypeError as e:  # not the class this was written for
                sys.stderr.write(f"gtpu_listroute unusable ({e}); "
                                 "using the per-object loop\n")
                _mods["gtpu_listroute"] = None
    return _mods.get("gtpu_listroute")


def load_wirepack() -> Optional[object]:
    """The wire pack of a sweep chunk (native/wirepackmod.c)."""
    return _load_named("gtpu_wirepack", "wirepackmod.c")


# the modules that release the GIL, and so keep a released clock
_RELEASERS = ("gtpu_flattenjson", "gtpu_wirepack")


def released_thread_time() -> float:
    """CPU seconds the calling thread has burnt with the GIL released
    inside this repository's own C (the columnizer's three phases, the
    wire pack), on the clock of ``time.thread_time()``: a thread's
    ``cpu - released`` over a stretch is the CPU it ran holding the lock,
    or inside someone else's C that released it (numpy, XLA), which only
    that C could tell apart.  Sums the modules already loaded; it builds
    and loads nothing, and reads 0.0 where none built."""
    total = 0.0
    for name in _RELEASERS:
        mod = _mods.get(name)
        if mod is not None:
            total += mod.released_cpu()
    return total


def _build_flags() -> list:
    """The full compiler invocation prefix (compiler + every flag).
    ``GTPU_NATIVE_CFLAGS`` appends extra flags (sanitizer builds, the
    lint harness, tests)."""
    cc = sysconfig.get_config_var("CC") or "cc"
    cflags = (sysconfig.get_config_var("CFLAGS") or "").split()
    extra = os.environ.get("GTPU_NATIVE_CFLAGS", "").split()
    return (
        cc.split()
        + ["-O3", "-shared", "-fPIC", "-pthread"]
        + [f for f in cflags if f.startswith("-f") or f.startswith("-m")]
        + extra
    )


def _build_digest(flags: list, src: str) -> str:
    """Build-directory key: the compiler invocation AND the source
    bytes.  A flag change (edited CFLAGS, GTPU_NATIVE_CFLAGS, another
    compiler) or an edited ``.c`` lands in a fresh directory, so a
    binary left on disk by another revision of the tree (``native/build``
    is git-ignored but travels with a copied checkout) is never picked
    up — mtimes say nothing about which source a binary came from."""
    import hashlib

    h = hashlib.sha256(" ".join(flags).encode())
    with open(src, "rb") as f:
        h.update(b"\0" + f.read())
    return h.hexdigest()[:12]


def _build(name: str, src_file: str):
    import numpy as np

    src = os.path.abspath(os.path.join(_NATIVE_DIR, src_file))
    flags = _build_flags()
    out_dir = os.path.abspath(
        os.path.join(_BUILD_DIR, _build_digest(flags, src)))
    os.makedirs(out_dir, exist_ok=True)
    ext = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    out = os.path.join(out_dir, name + ext)
    if not os.path.exists(out):
        include = sysconfig.get_path("include")
        np_include = np.get_include()
        # compile to a temp name and rename: a crashed or concurrent
        # build never leaves a half-written module under the keyed name
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = flags + [src, "-o", tmp, f"-I{include}", f"-I{np_include}"]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
            os.replace(tmp, out)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    if out_dir not in sys.path:
        sys.path.insert(0, out_dir)
    import importlib

    return importlib.import_module(name)

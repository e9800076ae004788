"""Native-module lint: warning-clean and sanitizer-clean C kernels.

Two gates over ``native/flattenmod.c``, ``native/flattenjsonmod.c``,
``native/listroutemod.c`` and ``native/wirepackmod.c``:

- **strict compile** — every module must build with
  ``-Wall -Wextra -Werror`` (a warning in kernel code is a bug
  waiting for a compiler upgrade to find it);
- **sanitizer corpus run** (slow) — rebuild the modules with
  ``-fsanitize=address,undefined`` through the normal
  ``ops/native.py`` build (the flag set hashes into the output dir,
  so the sanitized build can never be satisfied by a stale plain
  binary) and run the flatten, list-routing and wire-pack unit corpus
  (the router's untrack and ``track()`` cases and the identity scan's,
  ``tests/test_rawjson_identity.py``, with it) under it in
  a subprocess with libasan preloaded.  Memory errors or UB in the threaded
  kernel abort the run.

Run standalone (``python tools/lint_native.py [--asan]``) or via
tier-1 (``tests/test_native_lint.py``; the sanitizer gate is
slow-marked).
"""

from __future__ import annotations

import os
import subprocess
import sys
import sysconfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOURCES = ("flattenmod.c", "flattenjsonmod.c", "listroutemod.c",
           "wirepackmod.c")
STRICT_FLAGS = ["-Wall", "-Wextra", "-Werror"]


def _cc() -> list:
    return (sysconfig.get_config_var("CC") or "cc").split()


def _includes() -> list:
    import numpy as np

    return [f"-I{sysconfig.get_path('include')}", f"-I{np.get_include()}"]


def compile_strict(src_file: str) -> tuple:
    """(ok, compiler output) for one source under -Wall -Wextra -Werror."""
    src = os.path.join(REPO, "native", src_file)
    cmd = (_cc() + ["-c", "-O2", "-fPIC", "-pthread"] + STRICT_FLAGS
           + [src, "-o", os.devnull] + _includes())
    proc = subprocess.run(cmd, capture_output=True, text=True)
    return proc.returncode == 0, (proc.stderr or proc.stdout)


def find_libasan() -> str:
    """Path to libasan for LD_PRELOAD, or "" when the toolchain has
    none (the sanitizer gate skips)."""
    try:
        proc = subprocess.run(_cc() + ["-print-file-name=libasan.so"],
                              capture_output=True, text=True)
    except OSError:
        return ""
    path = (proc.stdout or "").strip()
    return path if path and os.path.sep in path and os.path.exists(path) \
        else ""


def asan_corpus_run(timeout_s: float = 600.0) -> tuple:
    """(ok, output): run the flatten unit corpus against an
    ASan+UBSan build of both native modules in a subprocess."""
    libasan = find_libasan()
    if not libasan:
        return True, "skipped: libasan not found in the toolchain"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # the build-dir key (ops/native._build) includes this:
        # the sanitized build lands beside, never instead of, the
        # production binary
        "GTPU_NATIVE_CFLAGS":
            "-fsanitize=address,undefined -fno-sanitize-recover=all "
            "-fno-omit-frame-pointer",
        "LD_PRELOAD": libasan,
        # leak checking is off: the interpreter itself "leaks" at exit
        # and the context pool/vocab mirror intentionally persist
        "ASAN_OPTIONS": "detect_leaks=0,abort_on_error=1",
    })
    cmd = [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
           os.path.join(REPO, "tests", "test_native_flatten_json.py"),
           os.path.join(REPO, "tests", "test_native_flatten.py"),
           os.path.join(REPO, "tests", "test_list_routing.py"),
           os.path.join(REPO, "tests", "test_rawjson_untracked.py"),
           os.path.join(REPO, "tests", "test_rawjson_identity.py")]
    # the wire pack's cases that compile nothing: XLA's compiler does not
    # run under the preloaded libasan
    cmd += [os.path.join(REPO, "tests", "test_transfer_pack.py") + "::" + t
            for t in ("test_row_counts_around_the_native_block",
                      "test_stats_written_by_hand_never_give_another_answer",
                      "test_the_steps_that_failed_are_named")]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, cwd=REPO, env=env)
    except subprocess.TimeoutExpired:
        return False, f"sanitizer corpus run timed out after {timeout_s}s"
    out = (proc.stdout or "") + (proc.stderr or "")
    return proc.returncode == 0, out[-4000:]


def main() -> int:
    rc = 0
    for src in SOURCES:
        ok, out = compile_strict(src)
        if ok:
            print(f"strict compile clean: native/{src}")
        else:
            print(f"lint: native/{src} fails -Wall -Wextra -Werror:\n{out}",
                  file=sys.stderr)
            rc = 1
    if "--asan" in sys.argv[1:]:
        ok, out = asan_corpus_run()
        if ok:
            print(f"sanitizer corpus run: {out if 'skipped' in out else 'clean'}")
        else:
            print(f"lint: sanitizer corpus run failed:\n{out}",
                  file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())

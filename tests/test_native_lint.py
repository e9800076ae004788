"""Native-kernel gates: warning-clean strict compiles in tier-1, the
ASan/UBSan corpus run slow-marked, and the ops/native.py build-key
rebuild semantics (a compile-flag change must never silently reuse the
previous binary)."""

import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import lint_native  # noqa: E402
from gatekeeper_tpu.ops import native  # noqa: E402


@pytest.mark.parametrize("src", lint_native.SOURCES)
def test_native_warning_clean(src):
    ok, out = lint_native.compile_strict(src)
    assert ok, f"native/{src} fails -Wall -Wextra -Werror:\n{out}"


@pytest.mark.slow
def test_native_asan_corpus():
    """The flatten unit corpus under an ASan+UBSan build of both
    modules: memory errors / UB in the threaded kernel fail here
    before they can corrupt a sweep."""
    ok, out = lint_native.asan_corpus_run()
    assert ok, f"sanitizer corpus run failed:\n{out}"


# --- build-key rebuild semantics (ops/native._build) -------------------

_TRIVIAL_MOD = textwrap.dedent("""\
    #define PY_SSIZE_T_CLEAN
    #include <Python.h>
    static struct PyModuleDef d = {
        PyModuleDef_HEAD_INIT, "%(name)s", NULL, -1, NULL,
        NULL, NULL, NULL, NULL,
    };
    PyMODINIT_FUNC
    PyInit_%(name)s(void)
    {
        return PyModule_Create(&d);
    }
""")


def _expected_out(name):
    import sysconfig

    ext = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    src = os.path.join(native._NATIVE_DIR, name + ".c")
    return os.path.join(
        os.path.abspath(native._BUILD_DIR),
        native._build_digest(native._build_flags(), src), name + ext)


@pytest.fixture
def build_env(tmp_path, monkeypatch):
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path / "src"))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    os.makedirs(tmp_path / "src")
    monkeypatch.delenv("GTPU_NATIVE_CFLAGS", raising=False)

    def write_mod(name):
        path = tmp_path / "src" / f"{name}.c"
        path.write_text(_TRIVIAL_MOD % {"name": name})
        return f"{name}.c"

    return write_mod


def test_build_reuses_fresh_binary(build_env):
    src = build_env("gtpu_lint_t1")
    native._build("gtpu_lint_t1", src)
    out = _expected_out("gtpu_lint_t1")
    assert os.path.exists(out)
    mtime = os.path.getmtime(out)
    native._build("gtpu_lint_t1", src)  # unchanged source + flags
    assert os.path.getmtime(out) == mtime, "fresh binary was recompiled"


def test_build_flag_drift_lands_in_new_dir(build_env, monkeypatch):
    """An edited flag set must not reuse the binary built under the old
    one: the flags are part of the build key — drift compiles fresh."""
    src = build_env("gtpu_lint_t2")
    native._build("gtpu_lint_t2", src)
    plain_out = _expected_out("gtpu_lint_t2")
    assert os.path.exists(plain_out)
    monkeypatch.setenv("GTPU_NATIVE_CFLAGS", "-DGTPU_LINT_DRIFT=1")
    drift_out = _expected_out("gtpu_lint_t2")
    assert os.path.dirname(drift_out) != os.path.dirname(plain_out)
    assert not os.path.exists(drift_out)
    native._build("gtpu_lint_t2", src)
    assert os.path.exists(drift_out), "flag drift did not rebuild"
    assert os.path.exists(plain_out), "drift build clobbered the original"


def test_edited_source_lands_in_new_dir(build_env):
    """The build key hashes the source CONTENT: an edited .c compiles
    into a fresh directory, and a binary that is merely newer on disk
    (a git-ignored build dir copied along with another revision of the
    tree) can never stand in for it."""
    src = build_env("gtpu_lint_t3")
    native._build("gtpu_lint_t3", src)
    old_out = _expected_out("gtpu_lint_t3")
    assert os.path.exists(old_out)
    path = os.path.join(native._NATIVE_DIR, src)
    with open(path, "a") as f:
        f.write("/* edited */\n")
    # make the OLD binary look newer than the edited source: an mtime
    # comparison would have kept it
    st = os.stat(path)
    os.utime(old_out, (st.st_atime + 3600, st.st_mtime + 3600))
    new_out = _expected_out("gtpu_lint_t3")
    assert os.path.dirname(new_out) != os.path.dirname(old_out)
    assert not os.path.exists(new_out)
    native._build("gtpu_lint_t3", src)
    assert os.path.exists(new_out), "edited source did not rebuild"


def test_build_digest_depends_on_flags_and_source(tmp_path):
    a_src, b_src = tmp_path / "a.c", tmp_path / "b.c"
    a_src.write_text("int a;\n")
    b_src.write_text("int b;\n")
    a = native._build_digest(["cc", "-O3"], str(a_src))
    assert native._build_digest(["cc", "-O3", "-DX"], str(a_src)) != a
    assert native._build_digest(["cc", "-O3"], str(b_src)) != a
    assert native._build_digest(["cc", "-O3"], str(a_src)) == a

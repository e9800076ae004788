"""chip_smoke.py off the chip, and the placement of JAX's compile cache.

The smoke is the proof that the system starts on the TPU; here, where
there is none, it must REFUSE (never fall back to the CPU on its own),
its only CPU mode is the explicit ``--tiny`` pre-flight, and the
persistent XLA cache sits where the environment says — or at one fixed
path inside the checkout — whatever ``--compile-cache`` says.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    """No ``--tiny``: non-zero in seconds, says why, prints no result
    and builds nothing (no record file)."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=_env())
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "found no TPU" in proc.stderr and "--tiny" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert not (tmp_path / "out").exists()


@pytest.mark.slow
def test_chip_smoke_tiny_preflight_passes(tmp_path):
    """The pre-flight walks every phase end to end on the CPU (on the
    8 virtual devices of conftest's XLA_FLAGS: the mesh phase too), with
    Pallas interpreted only because ``--tiny`` asked — and is not a
    chip pass."""
    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny", "--out", str(out)],
        capture_output=True, text=True, timeout=900, cwd=REPO,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert "platform=cpu" in lines[0]
    assert "not a chip pass" in lines[-1] and '"ok"' not in proc.stdout
    records = [json.loads(ln) for ln in lines if ln.startswith("{")]
    assert all(r["platform"] == "cpu" and r["device_count"] >= 1
               and "device_kind" in r for r in records)
    summary = records[-1]
    assert summary["phase"] == "summary" and summary["chip_pass"] is False
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    want = {"kernels", "library_and_corpus", "audit",
            "verdicts_vs_interpreter", "admission", "residency"}
    assert want <= set(summary["phases"])
    assert set(summary["phases"].values()) == {"pass"}
    by_phase = {r["phase"]: r for r in records}
    assert by_phase["kernels"]["compiled_by"] == "interpreter"
    assert by_phase["audit"]["new_traces"] == 0
    assert by_phase["admission"]["max_grid_batch"] > 8
    # every record also landed in the one output directory
    (written,) = list(out.iterdir())
    assert len(written.read_text().splitlines()) == len(records)


# --- compile cache placed from outside (utils/xla_cache.py) ---------------

def test_cache_helper_env_set_touches_no_cache_dir(monkeypatch, tmp_path):
    import jax

    from gatekeeper_tpu.utils import xla_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv(xla_cache.ENV_VAR, str(tmp_path))
    assert xla_cache.configure_xla_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in [k for k, _v in calls]
    assert ("jax_persistent_cache_min_compile_time_secs", 0) in calls

    calls.clear()
    monkeypatch.delenv(xla_cache.ENV_VAR)
    assert xla_cache.configure_xla_cache() == os.path.join(REPO,
                                                           ".jax_cache")
    assert ("jax_compilation_cache_dir",
            os.path.join(REPO, ".jax_cache")) in calls


_BOOT = ("import sys, jax\n"
         "from gatekeeper_tpu.__main__ import main\n"
         "rc = main(['--once', '--compile-cache', sys.argv[1]])\n"
         "print('XLA_DIR=' + str(jax.config.jax_compilation_cache_dir))\n"
         "sys.exit(rc)\n")


def _boot_dir(env, lowering_dir) -> str:
    proc = subprocess.run([sys.executable, "-c", _BOOT, str(lowering_dir)],
                          capture_output=True, text=True, timeout=180,
                          cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("XLA_DIR=")]
    return line[len("XLA_DIR="):]


def test_compile_cache_flag_does_not_move_the_xla_cache(tmp_path):
    """Through the real entry point: unset, two processes agree on the
    one in-checkout path; set, the environment's directory stands — and
    ``--compile-cache DIR`` moves neither (no ``DIR/xla``)."""
    lowering = tmp_path / "lowering"
    first = _boot_dir(_env(), lowering)
    second = _boot_dir(_env(), tmp_path / "elsewhere")
    assert first == second == os.path.join(REPO, ".jax_cache")
    outside = tmp_path / "outside"
    assert _boot_dir(_env(JAX_COMPILATION_CACHE_DIR=str(outside)),
                     lowering) == str(outside)
    assert not (lowering / "xla").exists()

"""chip_smoke.py's contract on a machine with no chip, and the compile-cache
helper. CPU only; the native library is not needed."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# with a TPU attached the command below would take the chip and run in full
_tpu_attached = any(Path("/dev").glob("accel*")) or \
    any(Path("/dev/vfio").glob("[0-9]*"))


@pytest.mark.skipif(_tpu_attached, reason="a TPU is attached")
def test_chip_smoke_refuses_to_run_without_a_tpu():
    """The environment says cpu (as this sandbox's does): the script demands
    the TPU in code, so it must fail fast, name the missing TPU and print no
    result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


_HELPER = """
import jax
real_update = jax.config.update
def update(name, value):
    assert name != "jax_compilation_cache_dir" or {may_set}, "helper set the dir"
    real_update(name, value)
jax.config.update = update
from pccl_tpu.utils.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def _run_helper(may_set: bool, **env_extra) -> list:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c",
                        _HELPER.format(may_set=may_set)],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_compile_cache_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing in code and the
    cache is where the variable says (jax reads it itself)."""
    where = str(tmp_path / "cache")
    assert _run_helper(False, JAX_COMPILATION_CACHE_DIR=where) == [where] * 2


def test_compile_cache_defaults_into_the_checkout():
    """Unset: <checkout>/.jax_cache, the same path from every process (the
    directory is part of the cache key)."""
    first, second = _run_helper(True), _run_helper(True)
    assert first == second == [str(REPO / ".jax_cache")] * 2


def test_verdict_line_has_exactly_ok_and_device():
    """The driver parses the last stdout line and refuses any other key; the
    per-phase detail belongs on the line before."""
    import json

    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.verdict_line(
        {"ok": True, "device": device, "phases": {"native": {"ok": True}},
         "compile": {"count": 3}, "total_s": 1.0})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}

"""chip_smoke.py refuses to report without a TPU; the compile cache helper."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """On the CPU, and in a directory holding nothing of the repo but the
    script, it exits non-zero and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0, out.stdout + out.stderr
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            assert json.loads(line).get("ok") is not True, line


FOUR_DEVICES = """
import sys
import jax
from repro.launch.mesh import force_host_device_count
force_host_device_count(4)
sys.path.insert(0, sys.argv[1])
import chip_smoke
failures = []
chip_smoke.four_chips(jax.devices()[:4], failures, width=4)
assert not failures, failures
print("FOUR_OK")
"""


def test_four_chip_phase_on_host_devices():
    """The --chips 4 comparison on four host devices at width 4: the
    sharded step passes it, with the client state spread over all four."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout
    assert "w_n on [4] devices" in out.stdout


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

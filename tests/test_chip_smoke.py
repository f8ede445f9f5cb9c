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


METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"


@pytest.fixture
def cache_config():
    """Put back the cache settings ``use_compile_cache`` changes."""
    before = (jax.config.jax_compilation_cache_dir,
              getattr(jax.config, METADATA_KEY))
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update(METADATA_KEY, before[1])


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env
    assert getattr(jax.config, METADATA_KEY)


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch,
                                                        cache_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert getattr(jax.config, METADATA_KEY)


SCOPED_PROGRAM = """
import sys
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.cache import use_compile_cache
from repro.telemetry.tracing import compiles
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2.0 + 1.0

text = jax.jit(f).lower(np.ones(5, np.float32)).compile().as_text()
t = compiles.totals()
print(t["cache_hits"], t["cache_misses"], sys.argv[1] in text)
"""


def test_compile_cache_key_holds_the_scopes(tmp_path):
    """A program compiled again in a new process loads from the cache; the
    same program under another scope compiles afresh, so its executable
    names its ops by its own scopes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env[cache.ENV] = str(tmp_path)

    def run(scope):
        out = subprocess.run([sys.executable, "-c", SCOPED_PROGRAM, scope],
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
        return out.stdout.split()[-3:]

    assert run("afl.grads") == ["0", "1", "True"]
    assert run("afl.grads") == ["1", "0", "True"]
    assert run("afl.state") == ["0", "1", "True"]

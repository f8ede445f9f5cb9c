"""Compiled experiment engine: scan/loop equivalence, grids, seed-vmap."""
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, get_config
from repro.core import baselines as BL
from repro.core.afl import afl_init
from repro.core.runner import build_provider, run_afl, sample_budgets
from repro.data import DeviceLoader
from repro.experiments import (
    DataShard,
    ExperimentGrid,
    GridCell,
    ResultsStore,
    mean_ci,
    run_afl_scanned,
    run_seed_batch,
)
from repro.experiments.grid import engine_policy
from repro.experiments.scan_engine import eval_points, make_run_fn
from repro.launch.train import build_device_data
from repro.models.registry import build_model

ROUNDS, EVERY = 8, 4
ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def federation():
    cfg = get_config("resnet9-cifar10").replace(d_model=4)
    model = build_model(cfg)
    fl = FLConfig(
        num_devices=4, rounds=ROUNDS, batch_size=8, learning_rate=0.02,
        mean_contact=6.0, mean_intercontact=30.0, energy_budget=(40.0, 80.0),
    )
    dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=64, seed=0)
    return cfg, model, fl, dev, ev


def _assert_hist_close(a: dict, b: dict):
    assert a["round"] == b["round"]
    for k in a:
        np.testing.assert_allclose(
            np.asarray(a[k]), np.asarray(b[k]), rtol=2e-4, atol=1e-5,
            err_msg=f"history key {k!r} diverged",
        )


# ---------------------------------------------------------------------------
# scan-vs-loop metric equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["mads", "afl"])
def test_scanned_matches_loop(federation, policy):
    """Same seeds, same DeviceLoader draws: identical history (float tol)."""
    cfg, model, fl, dev, ev = federation
    loop = run_afl(model, cfg, fl, policy, DeviceLoader(dev, fl.batch_size, 0),
                   ev, rounds=ROUNDS, eval_every=EVERY)
    scan = run_afl_scanned(model, cfg, fl, policy,
                           DeviceLoader(dev, fl.batch_size, 0), ev,
                           rounds=ROUNDS, eval_every=EVERY)
    _assert_hist_close(loop.history, scan.history)


def test_runner_engine_delegation(federation):
    """run_afl(engine="scan") routes through the compiled engine."""
    cfg, model, fl, dev, ev = federation
    shard = DataShard(dev, fl.batch_size, seed=0)
    a = run_afl(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                eval_every=EVERY, engine="scan")
    b = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                        eval_every=EVERY)
    _assert_hist_close(a.history, b.history)
    with pytest.raises(ValueError):
        run_afl(model, cfg, fl, "mads", shard, ev, engine="warp")


@pytest.mark.slow
def test_scanned_matches_loop_shard_long(federation):
    """Long-horizon equivalence through the in-scan DataShard sampler —
    the loop runner draws the identical fold_in(key, r) batches."""
    cfg, model, fl, dev, ev = federation
    shard = DataShard(dev, fl.batch_size, seed=0)
    loop = run_afl(model, cfg, fl, "mads", shard, ev, rounds=30,
                   eval_every=10, seed=3)
    scan = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=30,
                           eval_every=10, seed=3)
    _assert_hist_close(loop.history, scan.history)


def test_theta_mean_accumulates(federation):
    """hist theta_mean is the cumulative staleness mean, not the last
    round's snapshot: with sparse contacts it must exceed the round-1
    value (staleness grows between contacts)."""
    cfg, model, fl, dev, ev = federation
    res = run_afl(model, cfg, fl, "mads", DeviceLoader(dev, fl.batch_size, 0),
                  ev, rounds=ROUNDS, eval_every=EVERY)
    tm = res.history["theta_mean"]
    assert all(t >= 1.0 for t in tm)  # theta starts at r - kappa >= 1
    assert tm[-1] >= tm[0]


# ---------------------------------------------------------------------------
# seed-axis vmap
# ---------------------------------------------------------------------------


def test_seed_vmap_matches_independent(federation):
    cfg, model, fl, dev, ev = federation
    shard = DataShard(dev, fl.batch_size, seed=0)
    batch = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                           rounds=ROUNDS, eval_every=EVERY)
    assert len(batch) == 2
    for res, seed in zip(batch, (0, 1)):
        ind = run_afl_scanned(model, cfg, fl, "mads", shard, ev,
                              rounds=ROUNDS, eval_every=EVERY, seed=seed)
        _assert_hist_close(ind.history, res.history)
    # different seeds actually ran different scenarios
    assert batch[0].history["uploads"] != batch[1].history["uploads"]


# ---------------------------------------------------------------------------
# DataShard: the data are arguments of the program, not constants
# ---------------------------------------------------------------------------


def _segment_lowered(cfg, model, fl, seed: int, rounds: int = 2):
    """The segment program of a federation whose data come from ``seed``,
    lowered as the benchmark builds it (``make_run_fn`` sampling from a
    ``DataShard``), and that shard's data."""
    dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=16, seed=seed)
    shard = DataShard(dev, fl.batch_size, seed=seed)
    policy = BL.ALL["mads"](model.num_params(), fl)
    run = jax.jit(make_run_fn(model, cfg, fl, policy, rounds=rounds,
                              eval_every=rounds, sampler=shard.traced_batch))
    zeta, tau, h2 = build_provider(fl, "mads", None, rounds, seed).schedule()
    lowered = run.lower(
        afl_init(model, cfg, fl, jax.random.key(seed)), jnp.asarray(zeta),
        jnp.asarray(tau, jnp.float32), jnp.asarray(h2, jnp.float32),
        sample_budgets(fl, seed), {k: jnp.asarray(v) for k, v in ev.items()},
        shard.seed_key(seed), {}, {})
    return lowered.as_text(debug_info=False), shard


def test_segment_program_does_not_depend_on_the_data(federation):
    """Two seeds' data lower to the same program text, which holds no
    constant as large as the smallest data leaf (the labels)."""
    cfg, model, fl, _, _ = federation
    a, shard = _segment_lowered(cfg, model, fl, seed=12)
    b, _ = _segment_lowered(cfg, model, fl, seed=21)
    assert a == b
    smallest = min(v.nbytes for v in shard.data.values())
    literals = re.findall(r"dense<[^>]*>", a)
    assert max(map(len, literals)) < 2 * smallest


def test_traced_batch_matches_numpy_gather(federation):
    """Eager and jitted draws equal a NumPy gather of the padded per-device
    rows at the same indices; the eager draw is not committed to a device."""
    cfg, model, fl, dev, ev = federation
    shard = DataShard(dev, fl.batch_size, seed=0)
    key, r = shard.seed_key(3), 5
    counts = np.array([len(d["labels"]) for d in dev], np.int32)
    idx = np.asarray(jax.random.randint(
        jax.random.fold_in(key, r), (len(dev), fl.batch_size), 0,
        counts[:, None]))
    assert np.all(idx < counts[:, None])  # padding rows are never drawn
    m = int(counts.max())
    want = {k: np.stack([np.resize(d[k], (m,) + d[k].shape[1:])[i]
                         for d, i in zip(dev, idx)]) for k in dev[0]}
    eager = shard.traced_batch(key, r)
    jitted = jax.jit(shard.traced_batch)(key, r)
    for k in want:
        np.testing.assert_array_equal(np.asarray(eager[k]), want[k])
        np.testing.assert_array_equal(np.asarray(jitted[k]), want[k])
        assert not eager[k].committed


ENGAGED = r"""
import sys
import jax
import jax.numpy as jnp
from repro.launch.cache import use_compile_cache
from repro.telemetry.tracing import compiles
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.configs import FLConfig, get_config
from repro.core import baselines as BL
from repro.core.afl import afl_init
from repro.core.runner import build_provider, sample_budgets
from repro.experiments import DataShard
from repro.experiments.scan_engine import make_run_fn
from repro.launch.train import build_device_data
from repro.models.registry import build_model

seed = int(sys.argv[1])
cfg = get_config("resnet9-cifar10").replace(d_model=4)
model = build_model(cfg)
fl = FLConfig(num_devices=4, rounds=2, batch_size=8, seed=seed)
dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=16, seed=seed)
shard = DataShard(dev, fl.batch_size, seed=seed)
policy = BL.ALL["mads"](model.num_params(), fl)
run = jax.jit(make_run_fn(model, cfg, fl, policy, rounds=2, eval_every=2,
                          sampler=shard.traced_batch))
zeta, tau, h2 = build_provider(fl, "mads", None, 2, seed).schedule()
state = afl_init(model, cfg, fl, jax.random.key(seed))
jax.block_until_ready(run(
    state, jnp.asarray(zeta), jnp.asarray(tau, jnp.float32),
    jnp.asarray(h2, jnp.float32), sample_budgets(fl, seed),
    {k: jnp.asarray(v) for k, v in ev.items()}, shard.seed_key(seed), {}, {}))
t = compiles.totals()
segment = sum(s[0] == "compile" and s[3] == "jit(run)" for s in compiles.spans)
print(t["cache_hits"], t["cache_misses"], segment)
"""


def test_fresh_seed_loads_the_segment_program_from_the_cache(tmp_path):
    """A second process with another seed's data compiles nothing afresh:
    the segment program, among every other, is a hit in the persistent
    cache that the first process filled."""
    from repro.launch import cache

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env[cache.ENV] = str(tmp_path)

    def run(seed):
        out = subprocess.run([sys.executable, "-c", ENGAGED, str(seed)],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        return [int(v) for v in out.stdout.split()[-3:]]

    hits, misses, segment = run(12)
    assert (hits, segment) == (0, 1) and misses > 0
    assert run(21) == [misses, 0, 1]


HOST_MESH = r"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import FLConfig, get_config
from repro.experiments import DataShard, run_afl_scanned, run_seed_batch
from repro.launch.mesh import make_seed_mesh
from repro.launch.train import build_device_data
from repro.models.registry import build_model

assert jax.device_count() == 4, jax.devices()
cfg = get_config("resnet9-cifar10").replace(d_model=4)
model = build_model(cfg)
fl = FLConfig(num_devices=4, rounds=4, batch_size=8, learning_rate=0.02,
              mean_contact=6.0, mean_intercontact=30.0,
              energy_budget=(40.0, 80.0))
dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=64, seed=0)
shard = DataShard(dev, fl.batch_size, seed=0)
mesh = make_seed_mesh(4)
assert mesh is not None and mesh.devices.size == 4
seeds = [0, 1, 2, 3]
batch = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=seeds,
                       rounds=4, eval_every=2, mesh=mesh)
for res, seed in zip(batch, seeds):
    ind = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=4,
                          eval_every=2, seed=seed)
    for k in ind.history:
        np.testing.assert_allclose(np.asarray(res.history[k]),
                                   np.asarray(ind.history[k]), rtol=2e-4,
                                   atol=1e-5, err_msg=f"seed {seed} {k}")
# an eager draw joins arrays placed on the mesh
on_mesh = jax.device_put(jnp.ones(4), NamedSharding(mesh, P("seed")))
rows = shard.traced_batch(shard.seed_key(0), 0)["labels"]
assert float(jnp.sum(on_mesh * rows[:, 0])) == float(jnp.sum(rows[:, 0]))
print("HOST_MESH_OK")
"""


def test_seed_batch_on_host_mesh_matches_independent():
    """With the shard's data held in refs, seeds sharded over a 4-device
    host mesh still equal independent one-device runs of each seed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", HOST_MESH], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "HOST_MESH_OK" in out.stdout


# ---------------------------------------------------------------------------
# grid + results store
# ---------------------------------------------------------------------------


def test_grid_cells_groups_and_engine_key():
    grid = ExperimentGrid(policies=("mads", "afl", "fedmobile"),
                          speeds=(5.0, 20.0), seeds=(0, 1, 2), rounds=10)
    assert grid.size() == 3 * 2 * 3 == len(grid.cells())
    groups = grid.groups()
    assert len(groups) == 6
    for policy, mobility, speed, dropout, cells in groups:
        assert [c.seed for c in cells] == [0, 1, 2]
        assert all(c.policy == policy and c.speed == speed for c in cells)
        assert dropout == 0.0  # default heterogeneity axis is collapsed
    # legacy store keys are unchanged while the dropout axis is collapsed
    assert groups[0][4][0].key.count("__d") == 0
    fl = grid.fl_for("rwp", 20.0)
    assert fl.mobility_model == "rwp" and fl.speed == 20.0
    # FedAsync and FedMobile share engine flags -> one compiled program
    s = 1000
    base = FLConfig()
    assert engine_policy(BL.ALL["afl"](s, base)) == engine_policy(
        BL.ALL["fedmobile"](s, base))
    assert engine_policy(BL.ALL["afl"](s, base)) != engine_policy(
        BL.ALL["mads"](s, base))
    with pytest.raises(KeyError):
        ExperimentGrid(policies=("nope",))


def test_results_store_resume(tmp_path):
    grid = ExperimentGrid(policies=("mads",), speeds=(5.0,), seeds=(0, 1),
                          rounds=4, eval_every=2)
    store = ResultsStore(str(tmp_path))
    cells = grid.cells()
    hist = {"round": [2, 4], "eval": [0.5, 0.7], "uploads": [1.0, 3.0],
            "k_mean": [10.0, 12.0], "energy": [1.0, 2.0],
            "theta_mean": [1.0, 1.5], "power_mean": [0.1, 0.1]}
    store.save(cells[0], hist, meta={"arch": "tiny"})
    # completed cell is skipped; the other seed is still pending
    assert store.done(cells[0]) and not store.done(cells[1])
    assert store.pending(cells) == [cells[1]]
    assert store.load(cells[0])["eval"] == [0.5, 0.7]
    agg = store.aggregate(grid)
    m, ci, n = agg[("mads", "exponential", 5.0, 0.0)]
    assert m == pytest.approx(0.7) and n == 1
    assert "mads" in store.table(grid)
    # jsonl index got one line
    assert len((tmp_path / "results.jsonl").read_text().splitlines()) == 1


def test_mean_ci():
    m, ci = mean_ci([1.0, 1.0, 1.0])
    assert m == 1.0 and ci == 0.0
    m, ci = mean_ci([0.0, 1.0])
    assert m == 0.5 and ci > 0
    assert mean_ci([2.0]) == (2.0, 0.0)


def test_eval_points():
    assert eval_points(8, 4) == [4, 8]
    assert eval_points(10, 4) == [4, 8, 10]
    assert eval_points(3, 20) == [3]

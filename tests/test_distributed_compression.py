"""Parity suite: the pjit distributed AFL step vs the single-host engines,
with every repro/compression codec riding both.

The distributed step (core/distributed.py) invokes codecs through the SAME
``core.afl.compress_uploads`` call as ``afl_round``, with an identical PRNG
carry (``DistAflState.ckey``) — so its uploads must be *bit-identical* to
the single-host engines for the deterministic codecs (topk, joint) and for
qsgd too (the dither is counter-based, not stateful).  The fast tests pin
this round-by-round on one device; the slow tests re-run it on a mesh of 2
simulated host devices (``launch.mesh.force_host_device_count`` shim, in a
subprocess so the backend initialises with the forced count) and drive the
``--codec joint --per-layer --mesh 2`` sweep end to end.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, get_config
from repro.core import baselines as BL
from repro.core import mads as M
from repro.core.afl import afl_init, afl_round
from repro.core.distributed import (
    DistConfig,
    init_state,
    make_afl_train_step,
    run_afl_rounds,
)
from repro.core.runner import build_provider, run_afl, sample_budgets
from repro.experiments import DataShard
from repro.experiments.scan_engine import eval_points
from repro.launch.train import build_device_data
from repro.models.registry import build_model

CODEC_POLICIES = ("mads-topk", "mads-joint", "qsgd", "fixed-kb")
ROUNDS = 6
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def federation():
    cfg = get_config("resnet9-cifar10").replace(d_model=4)
    model = build_model(cfg)
    fl = FLConfig(
        num_devices=4, rounds=ROUNDS, batch_size=8, learning_rate=0.02,
        mean_contact=6.0, mean_intercontact=30.0, energy_budget=(40.0, 80.0),
    )
    dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=64, seed=0)
    shard = DataShard(dev, fl.batch_size, seed=0)
    return cfg, model, fl, shard, ev


def _dist_step(model, cfg, fl, policy):
    dcfg = DistConfig(
        num_clients=fl.num_devices, learning_rate=fl.learning_rate,
        rounds=fl.rounds, state_dtype="float32", upload_dtype="float32",
    )
    step = make_afl_train_step(model, cfg, dcfg, policy.controller,
                               compressor=policy.compressor)
    return dcfg, jax.jit(step)


def _flatten(batch):
    """(N, B, ...) stacked minibatch -> the (N*B, ...) global batch the
    distributed step re-splits identically."""
    return jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]), batch)


def _run_dist(model, cfg, fl, policy_name, shard, rounds, seed=0):
    policy = BL.ALL[policy_name](model.num_params(), fl)
    dcfg, step = _dist_step(model, cfg, fl, policy)
    provider = build_provider(fl, policy_name, None, rounds, seed)
    budgets = sample_budgets(fl, seed)
    state = init_state(model, dcfg, jax.random.key(seed))
    key = shard.seed_key(seed)
    state, hist = run_afl_rounds(
        step, state, provider,
        lambda r: _flatten(shard.traced_batch(key, r)), budgets,
        rounds=rounds,
    )
    return state, hist


@pytest.mark.parametrize("policy_name", CODEC_POLICIES)
def test_dist_step_bitwise_matches_afl_round(federation, policy_name):
    """Round-by-round: identical inputs -> bit-identical uploads (equal
    bits/k/b metrics AND an exactly equal aggregated global model)."""
    cfg, model, fl, shard, ev = federation
    policy = BL.ALL[policy_name](model.num_params(), fl)
    dcfg, step = _dist_step(model, cfg, fl, policy)
    provider = build_provider(fl, policy_name, None, ROUNDS, 0)
    budgets = sample_budgets(fl, 0)
    ds = init_state(model, dcfg, jax.random.key(0))
    ss = afl_init(model, cfg, fl, jax.random.key(0))
    key = shard.seed_key(0)
    shipped = 0.0
    for r in range(4):
        batch = shard.traced_batch(key, r)
        z, t, h2 = provider.round(r)
        z = jnp.asarray(z, jnp.float32)
        t = jnp.asarray(t, jnp.float32)
        h2 = jnp.asarray(h2, jnp.float32)
        ds, md = step(ds, _flatten(batch), z, t, h2, budgets)
        ss, ms = afl_round(ss, batch, z, t, h2, budgets,
                           model=model, cfg=cfg, fl=fl, policy=policy)
        for kk in ("bits", "k", "b"):
            np.testing.assert_array_equal(
                np.asarray(md[kk]), np.asarray(ms[kk]),
                err_msg=f"{policy_name} r={r} {kk}")
        for a, b in zip(jax.tree.leaves(ds.w), jax.tree.leaves(ss.w)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        shipped += float(jnp.sum(md["bits"]))
    assert shipped > 0  # the parity is not vacuous


@pytest.mark.parametrize("per_layer", [False, True],
                         ids=["global-split", "per-layer"])
def test_dist_codec_bits_within_contact_budget(federation, per_layer):
    """Acceptance: in the distributed step, every upload's realised bits
    satisfy bits <= tau * A(p) — including under per-layer budgets."""
    import dataclasses

    cfg, model, fl, shard, ev = federation
    fl = dataclasses.replace(fl, per_layer_budget=per_layer)
    policy = BL.ALL["mads-joint"](model.num_params(), fl)
    ctl = policy.controller
    _, hist = _run_dist(model, cfg, fl, "mads-joint", shard, ROUNDS)
    provider = build_provider(fl, "mads-joint", None, ROUNDS, 0)
    total = 0.0
    for r, m in enumerate(hist):
        _, tau, h2 = provider.round(r)
        cap = np.asarray(tau, np.float64) * np.asarray(M.rate_bps(
            jnp.asarray(m["power"]), jnp.asarray(h2, jnp.float32),
            ctl.bandwidth, ctl.noise_w_hz))
        bits = np.asarray(m["bits"], np.float64)
        assert np.all(bits <= cap * (1 + 1e-5) + 1e-3), (r, bits, cap)
    total = sum(float(np.sum(np.asarray(m["bits"]))) for m in hist)
    assert total > 0  # something actually shipped


@pytest.mark.parametrize("policy_name", ("mads-topk", "mads-joint", "qsgd"))
def test_dist_history_matches_scan_engine(federation, policy_name):
    """theta_mean / bits_mean histories of the distributed rounds equal the
    scan engine's (same provider, same DataShard stream, same seed)."""
    cfg, model, fl, shard, ev = federation
    _, hist = _run_dist(model, cfg, fl, policy_name, shard, ROUNDS)
    scan = run_afl(model, cfg, fl, policy_name, shard, ev, rounds=ROUNDS,
                   eval_every=3, engine="scan")
    n = fl.num_devices
    pts = eval_points(ROUNDS, 3)
    assert scan.history["round"] == pts
    # aggregate the dist metrics exactly like the engines do (f32 sums)
    theta = np.float32(0.0)
    bits = np.float32(0.0)
    ups = np.float32(0.0)
    theta_mean, bits_mean = [], []
    for r, m in enumerate(hist):
        theta += np.float32(np.sum(np.asarray(m["theta"], np.float32)))
        bits += np.float32(np.sum(np.asarray(m["bits"], np.float32)))
        ups += np.float32(np.sum(np.asarray(m["success"], np.float32)))
        if (r + 1) in pts:
            theta_mean.append(theta / np.float32((r + 1) * n))
            bits_mean.append(bits / max(ups, np.float32(1.0)))
    np.testing.assert_allclose(theta_mean, scan.history["theta_mean"],
                               rtol=1e-6, err_msg=policy_name)
    np.testing.assert_allclose(bits_mean, scan.history["bits_mean"],
                               rtol=1e-6, err_msg=policy_name)
    assert bits_mean[-1] > 0


# ---------------------------------------------------------------------------
# 2 simulated host devices (subprocess: the forced count must precede
# backend initialisation)
# ---------------------------------------------------------------------------


MESH_SCRIPT = r"""
import jax, numpy as np
from repro.launch.mesh import force_host_device_count
force_host_device_count(2)
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compression.base import strict_threshold
from repro.compression.quant import tree_amax
from repro.configs import FLConfig, get_config
from repro.core import baselines as BL
from repro.core.distributed import (
    DistConfig, client_state_shardings, init_state, make_afl_train_step,
    run_afl_rounds,
)
from repro.core.runner import build_provider, sample_budgets
from repro.experiments import DataShard
from repro.launch.train import build_device_data
from repro.models.registry import build_model

assert jax.device_count() == 2, jax.devices()

# --- 1. shard_map threshold/amax agreement (the axis-aware contract) -----
rng = np.random.default_rng(0)
x = rng.normal(0, 1, 1 << 16).astype(np.float32)
mesh1d = Mesh(np.asarray(jax.devices()), ("data",))
k = 3000.0

def body(xl):
    t = strict_threshold(xl, k, method="sampled", sample=4096,
                         axis="data", s=x.size)
    return t[None], tree_amax(xl, axis="data")[None]

ts, ams = jax.jit(jax.shard_map(
    body, mesh=mesh1d, in_specs=P("data"), out_specs=P("data")
))(jnp.asarray(x))
ts, ams = np.asarray(ts), np.asarray(ams)
assert ts[0] == ts[1], ts          # every device agrees on the threshold
assert ams[0] == ams[1] == np.abs(x).max(), ams  # ...and on amax (exact)
count = float(np.sum(np.abs(x) > ts[0]))
se = np.sqrt(k * x.size / 8192)    # documented quantile error model
assert abs(count - k) <= 4 * se, (count, k, se)

# --- 2. sharded vs single-host AFL rounds: bit-identical bits history ----
cfg = get_config("resnet9-cifar10").replace(d_model=4)
model = build_model(cfg)
ROUNDS = 3
fl = FLConfig(num_devices=4, rounds=ROUNDS, batch_size=8,
              learning_rate=0.02, mean_contact=6.0, mean_intercontact=30.0,
              energy_budget=(40.0, 80.0))
dev, _ = build_device_data(cfg, fl, train_n=160, eval_n=32, seed=0)
shard = DataShard(dev, fl.batch_size, seed=0)
key = shard.seed_key(0)
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 1), ("data", "model"))

def batch_fn(r):
    return jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]),
                        shard.traced_batch(key, r))

def run(policy_name, fl, sharded):
    policy = BL.ALL[policy_name](model.num_params(), fl)
    dcfg = DistConfig(num_clients=fl.num_devices, rounds=ROUNDS,
                      learning_rate=fl.learning_rate, state_dtype="float32",
                      upload_dtype="float32")
    # sharded: the codec pass runs per device under shard_map (as it must
    # for the Pallas kernels on a TPU mesh)
    step = jax.jit(make_afl_train_step(model, cfg, dcfg, policy.controller,
                                       compressor=policy.compressor,
                                       mesh=mesh if sharded else None))
    state = init_state(model, dcfg, jax.random.key(0))
    if sharded:  # commit the client axis to the 2-device data axis
        state = jax.device_put(state, client_state_shardings(state, mesh))
    provider = build_provider(fl, policy_name, None, ROUNDS, 0)
    budgets = sample_budgets(fl, 0)
    _, hist = run_afl_rounds(step, state, provider, batch_fn, budgets,
                             rounds=ROUNDS)
    return np.stack([np.asarray(m["bits"]) for m in hist])

import dataclasses
for policy_name, flv in (
    ("mads-topk", fl),
    ("mads-joint", fl),
    ("mads-joint", dataclasses.replace(fl, per_layer_budget=True)),
    ("qsgd", fl),
    ("fixed-kb", fl),
):
    b1 = run(policy_name, flv, sharded=False)
    b2 = run(policy_name, flv, sharded=True)
    tag = policy_name + ("+pl" if flv.per_layer_budget else "")
    assert np.array_equal(b1, b2), (tag, b1, b2)
    print("PARITY", tag, "bits_total", float(b1.sum()))
print("MESH_OK")
"""


@pytest.mark.slow
def test_two_device_mesh_parity():
    """Mesh of 2 simulated host devices: the sharded step's realised bits
    are bit-identical to the single-host run for all four codecs (and the
    per-layer joint codec), and the axis-aware threshold/amax agree across
    shards."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout


SWEEP_ARGS = [
    "--arch", "resnet9-cifar10", "--width", "4", "--codec", "joint",
    "--per-layer", "--mesh", "2", "--seeds", "2", "--rounds", "4",
    "--eval-every", "2", "--devices", "4", "--train-n", "160",
]


@pytest.mark.slow
def test_sweep_per_layer_mesh_resumable(tmp_path):
    """Acceptance: ``launch/sweep.py --codec joint --per-layer --mesh 2``
    completes and resumes (the per-upload bits <= tau*A invariant of the
    same codec/step is pinned by the fast tests above)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    cmd = [sys.executable, "-m", "repro.launch.sweep",
           *SWEEP_ARGS, "--out", str(tmp_path)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "mads-joint" in out.stdout
    index = tmp_path / "results.jsonl"
    cells = [json.loads(l) for l in index.read_text().splitlines()]
    assert len(cells) == 2  # 1 policy x 1 speed x 2 seeds
    assert all(c["policy"] == "mads-joint" for c in cells)
    # resume: nothing re-runs, no duplicate index rows
    out2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1200)
    assert out2.returncode == 0, out2.stderr[-3000:]
    assert len(index.read_text().splitlines()) == 2

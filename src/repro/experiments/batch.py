"""Seed-axis vmapping + mesh sharding of the compiled AFL run.

One grid group = one (policy, mobility, speed) point replicated over S
seeds.  Everything that varies per seed — scenario tensors, budgets, the
initial federation state, the minibatch-sampling key — is stacked on a
leading seed axis and the whole-run function from ``scan_engine.make_run_fn``
is vmapped over it: S runs execute as ONE program with batched linear
algebra instead of S sequential loops.  On a multi-device mesh the seed
axis is sharded (``launch.mesh.make_seed_mesh``) so seeds spread across
chips; on one CPU the vmap alone already amortises dispatch overhead.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import baselines as BL
from repro.core.afl import afl_init
from repro.core.runner import RunResult, build_provider, sample_budgets
from repro.experiments.grid import engine_fl, engine_policy
from repro.experiments.scan_engine import eval_points, make_run_fn
from repro.utils import get_logger

log = get_logger("repro.batch")


@lru_cache(maxsize=16)
def _compiled_vrun(model, cfg, fl, policy, rounds: int, eval_every: int,
                   sampler, telemetry=None, mesh=None):
    """vmapped whole-run program, cached per (model, engine-flags) group.

    With a seed ``mesh`` each device runs its own seeds under
    ``shard_map``: seeds never communicate, and GSPMD cannot partition
    the Pallas codec kernels inside the run."""
    run = make_run_fn(model, cfg, fl, policy, rounds=rounds,
                      eval_every=eval_every, sampler=sampler,
                      telemetry=telemetry)
    # batched: state0, zeta, tau, h2, budgets, sample_ctx, telemetry state,
    # heterogeneity aux masks; shared: eval_batch
    vrun = jax.vmap(run, in_axes=(0, 0, 0, 0, 0, None, 0, 0, 0))
    if mesh is not None:
        seed = P(mesh.axis_names[0])
        # check_vma=False: the kernels' out_shape carries no varying-axes
        # type; every output varies over the seed axis, as out_specs say
        vrun = jax.shard_map(vrun, mesh=mesh,
                             in_specs=(seed,) * 5 + (P(),) + (seed,) * 3,
                             out_specs=seed, check_vma=False)
    return jax.jit(vrun)


@lru_cache(maxsize=64)
def _compiled_vinit(model, cfg, fl):
    """Jitted per-seed federation init: (seeds,) int32 -> batched state +
    PRNG keys.  Unjitted vmap would re-trace afl_init on every group."""
    def init(seeds):
        keys = jax.vmap(jax.random.key)(seeds.astype(jnp.uint32))
        return jax.vmap(lambda k: afl_init(model, cfg, fl, k))(keys)

    return jax.jit(init)


@lru_cache(maxsize=16)
def _compiled_seed_keys(seed_key_fn):
    return jax.jit(jax.vmap(seed_key_fn))


def _usable_mesh(mesh, num_seeds: int):
    """The mesh if it evenly divides the seed axis, else None (unsharded).

    Both the batched inputs and the replicated eval batch must follow the
    same decision — mixing mesh-committed and uncommitted arguments makes
    the jitted run fail with incompatible devices."""
    if mesh is None:
        return None
    size = int(np.prod(mesh.devices.shape))
    if num_seeds % size != 0:
        log.warning("seeds=%d not divisible by mesh size %d; running "
                    "unsharded", num_seeds, size)
        return None
    return mesh


def run_seed_batch(
    model,
    cfg,
    fl,
    policy_name: str,
    shard,
    eval_batch,
    seeds: Sequence[int],
    rounds: Optional[int] = None,
    eval_every: int = 20,
    mesh=None,
    telemetry=None,
) -> list[RunResult]:
    """All ``seeds`` of one grid group in a single compiled execution.

    Scenario schedules and budgets are built host-side per seed (numpy
    mobility traces), stacked to (S, rounds, N) device tensors, and the
    vmapped scan consumes them.  Returns one ``RunResult`` per seed whose
    history matches an independent ``run_afl_scanned`` of that seed.

    ``telemetry``: a ``MetricRegistry`` whose state batches over the seed
    axis (sharded with the rest when a mesh is given); each RunResult
    carries its seed's fetched snapshot — merge them with
    ``repro.telemetry.merge_fetched`` (or on device via
    ``registry.merge_stacked``).
    """
    rounds = rounds or fl.rounds
    from repro.core.runner import resolve_telemetry

    telemetry = resolve_telemetry(fl, telemetry, s=model.num_params())
    policy = BL.ALL[policy_name](model.num_params(), fl)
    epolicy = engine_policy(policy)

    providers = [
        build_provider(fl, policy_name, None, rounds, int(s)) for s in seeds
    ]
    scheds = [p.schedule() for p in providers]
    zeta = jnp.asarray(np.stack([np.asarray(z) for z, _, _ in scheds]))
    tau = jnp.asarray(np.stack([np.asarray(t) for _, t, _ in scheds]),
                      jnp.float32)
    h2 = jnp.asarray(np.stack([np.asarray(h) for _, _, h in scheds]),
                     jnp.float32)
    # heterogeneity loss masks: (S, rounds, N) per key, {} when disabled
    # (aux presence is a property of fl, so it is uniform across seeds)
    het = ({} if providers[0].aux is None else {
        k: jnp.asarray(np.stack([np.asarray(p.aux[k]) for p in providers]),
                       jnp.float32)
        for k in providers[0].aux
    })
    budgets = jnp.stack([sample_budgets(fl, int(s)) for s in seeds])

    efl = engine_fl(fl)
    seed_arr = jnp.asarray(seeds, jnp.int32)
    state0 = _compiled_vinit(model, cfg, efl)(seed_arr)
    sample_keys = _compiled_seed_keys(shard.seed_key)(seed_arr)
    eval_b = jax.device_put({k: jnp.asarray(v) for k, v in eval_batch.items()})
    ns = len(seeds)
    tstate0 = (
        jax.tree.map(lambda l: jnp.zeros((ns,) + l.shape, l.dtype),
                     telemetry.init_state())
        if telemetry is not None else {}
    )

    mesh = _usable_mesh(mesh, ns)
    if mesh is not None:
        batched = (state0, zeta, tau, h2, budgets, sample_keys, tstate0, het)
        batched = jax.device_put(
            batched, NamedSharding(mesh, P(mesh.axis_names[0]))
        )
        state0, zeta, tau, h2, budgets, sample_keys, tstate0, het = batched
        eval_b = jax.device_put(eval_b, NamedSharding(mesh, P()))

    vrun = _compiled_vrun(model, cfg, efl, epolicy, rounds, eval_every,
                          shard.traced_batch, telemetry, mesh)
    states, hist_dev, tstates = vrun(state0, zeta, tau, h2, budgets, eval_b,
                                     sample_keys, tstate0, het)

    pts = eval_points(rounds, eval_every)
    hist_np = {k: np.asarray(v) for k, v in hist_dev.items()}  # (S, E)
    out = []
    for i, s in enumerate(seeds):
        hist = {"round": list(pts)}
        hist.update({k: [float(x) for x in v[i]] for k, v in hist_np.items()})
        snap = (
            telemetry.fetch(jax.tree.map(lambda l: l[i], tstates))
            if telemetry is not None else None
        )
        out.append(RunResult(
            policy_name, hist, hist["eval"][-1],
            jax.tree.map(lambda l: l[i], states),
            telemetry=snap,
        ))
    return out

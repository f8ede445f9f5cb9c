"""Whole-run AFL lowering: the round loop folded into ``lax.scan``.

``core/runner.py::run_afl`` dispatches one jitted ``afl_round`` per round
from Python, re-hosting minibatches and scenario rows every round.  Here the
entire R-round run is ONE compiled XLA program:

* the scenario schedule (rounds x N zeta/tau/h2 from
  ``ScenarioProvider.schedule()``) lives on device and is consumed as scan
  inputs;
* minibatches are sampled *inside* the scan from a device-resident
  ``DataShard`` (``fold_in(key, r)`` so round r's batch is a pure function
  of the key), or gathered from a prestacked (rounds, N, B, ...) tensor
  when exact ``DeviceLoader`` parity is required;
* periodic eval is buffered: the scan is segmented at the eval rounds, and
  each segment boundary computes the eval metric and the windowed
  aggregates (uploads, k_mean, theta_mean, power_mean) from carried totals
  — the history comes back as (num_evals,) device arrays, fetched once.

``run_afl_scanned`` is metric-equivalent to the loop runner on the same
seeds (tests/test_experiments.py) and is the unit the grid engine
(``batch.py``) vmaps over seeds.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as BL
from repro.core.afl import afl_init, afl_round
from repro.core.runner import (
    RunResult,
    build_provider,
    make_eval_fn,
    resolve_telemetry,
    sample_budgets,
)
from repro.telemetry import HIST_KEYS, record_het, record_round
from repro.telemetry.tracing import phase
from repro.utils import get_logger

log = get_logger("repro.scan_engine")


# ---------------------------------------------------------------------------
# Batch sources
# ---------------------------------------------------------------------------


class DataShard:
    """Device-resident federation data with in-scan minibatch sampling.

    Per-device arrays are wrap-padded to a rectangular (N, M, ...) block and
    pushed to device ONCE; round r's stacked (N, B, ...) minibatch is
    ``fold_in(key, r)`` + a per-device gather, so sampling is traceable and
    runs inside the scan (and identically outside it — the loop runner
    calls ``round_batch(r)`` for engine-equivalence tests).

    Sampling is uniform-with-replacement over each device's true row count
    (padding rows are never drawn), unlike ``DeviceLoader``'s
    epoch-permutation semantics — both are unbiased samplers of D_n.

    The data and the per-device row counts are held in JAX array
    references (``jax.new_ref``, one per leaf) and read with ``ref[...]``.
    ``jax.jit`` embeds closed-over arrays in the program as constants, so
    a program that sampled from plain arrays would differ with every
    dataset, and so would its persistent-cache key; closed-over refs are
    passed to the program as arguments instead, so one compiled program
    serves every seed's data.  Nothing ever writes the refs.

    A ref is committed to the one device that holds it.  Under a mesh
    (``shard_map``, ``jax.set_mesh``), whose inputs live on other devices
    too, ``traced_batch`` reads the refs' values as constants instead;
    called outside any trace it returns uncommitted arrays, as it did when
    the data were plain arrays.
    """

    def __init__(self, device_arrays: list[dict], batch_size: int,
                 seed: int = 0):
        counts = np.array(
            [len(next(iter(d.values()))) for d in device_arrays], np.int32
        )
        m = int(counts.max())
        self._data = {
            k: jax.new_ref(jnp.asarray(np.stack([
                np.resize(d[k], (m,) + d[k].shape[1:]) for d in device_arrays
            ])))
            for k in device_arrays[0]
        }
        self._counts = jax.new_ref(jnp.asarray(counts))
        self.num_devices = len(device_arrays)
        self.batch_size = batch_size
        self.key = jax.random.key(seed)

    @property
    def data(self) -> dict:
        """The (N, M, ...) padded arrays, read from their refs."""
        return {k: r[...] for k, r in self._data.items()}

    @property
    def counts(self):
        """(N,) true row count of each device, read from its ref."""
        return self._counts[...]

    def __len__(self):
        return self.num_devices

    def seed_key(self, seed: int):
        """Independent sampling stream for one grid seed."""
        return jax.random.fold_in(self.key, seed)

    def traced_batch(self, key, r):
        """(N, B, ...) minibatch for round r — jnp-traceable."""
        if jax.sharding.get_abstract_mesh().empty:
            data, counts = self.data, self.counts
        else:
            with jax.ensure_compile_time_eval():
                data, counts = jax.device_get((self.data, self.counts))
        kr = jax.random.fold_in(key, r)
        idx = jax.random.randint(
            kr, (self.num_devices, self.batch_size), 0, counts[:, None]
        )
        batch = jax.tree.map(
            lambda a: jax.vmap(lambda rows, ii: rows[ii])(a, idx), data
        )
        if isinstance(idx, jax.core.Tracer):
            return batch
        return jax.tree.map(lambda v: jnp.asarray(jax.device_get(v)), batch)


def prestack_batches(loader, rounds: int):
    """Materialise ``rounds`` DeviceLoader draws as (rounds, N, B, ...) device
    arrays — exact loader parity for scanned-vs-loop equivalence."""
    rows = [loader.sample_all() for _ in range(rounds)]
    return {
        k: jnp.asarray(np.stack([row[k] for row in rows])) for k in rows[0]
    }


def _prestacked_sampler(ctx, r):
    return jax.tree.map(lambda v: v[r], ctx)


# ---------------------------------------------------------------------------
# The compiled run
# ---------------------------------------------------------------------------


def eval_points(rounds: int, eval_every: int) -> list[int]:
    """1-based round indices at which the loop runner evaluates."""
    pts = [r for r in range(eval_every, rounds + 1, eval_every)]
    if not pts or pts[-1] != rounds:
        pts.append(rounds)
    return pts


def make_run_fn(model, cfg, fl, policy, *, rounds: int, eval_every: int,
                sampler: Callable, telemetry=None):
    """Pure function running a whole AFL experiment in one trace.

    Returns ``run(state0, zeta, tau, h2, budgets, eval_batch, sample_ctx,
    tstate0, het) -> (final_state, hist, tstate)`` where ``hist`` maps the
    loop runner's history keys (except "round") to (num_evals,) arrays.
    ``sampler(sample_ctx, r)`` yields round r's stacked minibatch:
    ``DataShard.traced_batch`` with a key context, or
    ``_prestacked_sampler`` with a (rounds, ...) tensor.

    ``het`` is the scenario's heterogeneity aux dict — (rounds, N) loss
    masks from ``ScenarioProvider.aux`` — or ``{}`` when the layer is
    disabled; it rides the scan inputs and folds into the per-device
    telemetry table each round (``record_het``).  An empty dict keeps the
    arity (and the vmap in_axes of ``batch.py``) uniform across runs.

    ``telemetry`` (a ``repro.telemetry.MetricRegistry``) threads its
    accumulation pytree ``tstate0`` through the scan carry —
    device-resident histograms/counters with no mid-run host sync.  With
    ``telemetry=None``, pass ``{}`` and the carry slot is empty.

    The function is jit- and vmap-friendly: scenario tensors, budgets, the
    initial state, the sample context, and the telemetry state batch over
    a leading seed axis; eval_batch broadcasts.
    """
    n = fl.num_devices
    eval_fn = make_eval_fn(model, cfg)
    pts = eval_points(rounds, eval_every)
    bounds = list(zip([0] + pts[:-1], pts))

    def run(state0, zeta, tau, h2, budgets, eval_batch, sample_ctx,
            tstate0, het):
        def body(carry, xs):
            state, tot, ts = carry
            r, zeta_r, tau_r, h2_r, het_r = xs
            with phase("sample"):
                batch = sampler(sample_ctx, r)
            state, m = afl_round(
                state, batch, zeta_r, tau_r, h2_r, budgets,
                model=model, cfg=cfg, fl=fl, policy=policy,
            )
            if telemetry is not None:
                ts = record_round(telemetry, ts, m, tau_r)
                ts = record_het(telemetry, ts, het_r if het_r else None)
            tot = {
                "uploads": tot["uploads"] + jnp.sum(m["success"]),
                "k": tot["k"] + jnp.sum(m["k"]),
                "power": tot["power"] + jnp.sum(m["power"]),
                "theta": tot["theta"] + jnp.sum(m["theta"]),
                "bits": tot["bits"] + jnp.sum(m["bits"]),
            }
            return (state, tot, ts), None

        state = state0
        ts = tstate0
        tot = {k: jnp.zeros((), jnp.float32)
               for k in ("uploads", "k", "power", "theta", "bits")}
        hist = {k: [] for k in HIST_KEYS if k != "round"}
        for start, stop in bounds:
            xs = (
                jnp.arange(start, stop, dtype=jnp.int32),
                zeta[start:stop], tau[start:stop], h2[start:stop],
                {k: v[start:stop] for k, v in het.items()},
            )
            (state, tot, ts), _ = jax.lax.scan(body, (state, tot, ts), xs)
            up = jnp.maximum(tot["uploads"], 1.0)
            with phase("eval"):
                hist["eval"].append(eval_fn(state.w, eval_batch))
            hist["uploads"].append(tot["uploads"])
            hist["k_mean"].append(tot["k"] / up)
            hist["energy"].append(jnp.sum(state.energy))
            hist["theta_mean"].append(tot["theta"] / (stop * n))
            hist["power_mean"].append(tot["power"] / up)
            hist["bits_mean"].append(tot["bits"] / up)
        return state, {k: jnp.stack(v) for k, v in hist.items()}, ts

    return run


@lru_cache(maxsize=16)
def _compiled_run(model, cfg, fl, policy, rounds: int, eval_every: int,
                  sampler, telemetry=None):
    """One jitted program per (model, engine-flags, shapes) group — grid
    cells that share these reuse the compilation (policy *names* are
    stripped by the grid; see ``grid.engine_policy``).  The telemetry
    registry is part of the key: runs with and without instrumentation
    are different XLA programs.

    Note: a DataShard sampler key pins that shard's device data for the
    cache entry's lifetime — bounded by the maxsize, but long-lived
    processes cycling many large datasets should prefer fresh processes
    per sweep."""
    run = make_run_fn(model, cfg, fl, policy, rounds=rounds,
                      eval_every=eval_every, sampler=sampler,
                      telemetry=telemetry)
    return jax.jit(run)


def run_afl_scanned(
    model,
    cfg,
    fl,
    policy_name: str,
    loader,
    eval_batch,
    rounds: Optional[int] = None,
    eval_every: int = 20,
    seed: Optional[int] = None,
    schedule=None,
    log_progress: bool = False,
    batch_mode: str = "auto",
    telemetry=None,
    tracer=None,
) -> RunResult:
    """Drop-in replacement for ``runner.run_afl`` running the whole
    experiment as one compiled program.

    ``batch_mode``: "shard" samples in-scan from a ``DataShard``;
    "prestack" materialises the DeviceLoader's exact draw sequence up
    front; "auto" picks by loader type.  ``telemetry`` threads a
    ``MetricRegistry`` state through the scan (fetched once at run end
    into ``RunResult.telemetry``); ``tracer`` records run/fetch spans.
    """
    rounds = rounds or fl.rounds
    seed = fl.seed if seed is None else seed
    telemetry = resolve_telemetry(fl, telemetry, s=model.num_params())
    policy = BL.ALL[policy_name](model.num_params(), fl)

    provider = build_provider(fl, policy_name, schedule, rounds, seed)
    zeta, tau, h2 = provider.schedule()
    zeta = jnp.asarray(zeta)
    tau = jnp.asarray(tau, jnp.float32)
    h2 = jnp.asarray(h2, jnp.float32)
    aux = provider.aux
    het = ({} if aux is None
           else {k: jnp.asarray(v, jnp.float32) for k, v in aux.items()})
    budgets = sample_budgets(fl, seed)

    if batch_mode == "auto":
        batch_mode = "shard" if isinstance(loader, DataShard) else "prestack"
    if batch_mode == "shard":
        sampler, sample_ctx = loader.traced_batch, loader.seed_key(seed)
    elif batch_mode == "prestack":
        sampler = _prestacked_sampler
        sample_ctx = (
            loader if isinstance(loader, dict)
            else prestack_batches(loader, rounds)
        )
    else:
        raise ValueError(f"unknown batch_mode {batch_mode!r}")

    from contextlib import nullcontext

    from repro.experiments.grid import engine_fl, engine_policy

    span = tracer.span if tracer is not None else (
        lambda name, **kw: nullcontext())
    run = _compiled_run(model, cfg, engine_fl(fl), engine_policy(policy),
                        rounds, eval_every, sampler, telemetry)
    state0 = afl_init(model, cfg, fl, jax.random.key(seed))
    eval_b = jax.device_put({k: jnp.asarray(v) for k, v in eval_batch.items()})
    tstate0 = telemetry.init_state() if telemetry is not None else {}
    with span("run"):  # first call per program traces + compiles
        state, hist_dev, tstate = run(state0, zeta, tau, h2, budgets,
                                      eval_b, sample_ctx, tstate0, het)
        if tracer is not None:
            tracer.fence(hist_dev)

    hist: dict = {"round": eval_points(rounds, eval_every)}
    with span("fetch"):
        for k, v in hist_dev.items():
            hist[k] = [float(x) for x in np.asarray(v)]
        snapshot = telemetry.fetch(tstate) if telemetry is not None else None
    if log_progress:
        for i, r in enumerate(hist["round"]):
            log.info(
                "policy=%s r=%d eval=%.4f uploads=%.0f k=%.0f E=%.0fJ",
                policy_name, r, hist["eval"][i], hist["uploads"][i],
                hist["k_mean"][i], hist["energy"][i],
            )
    return RunResult(policy_name, hist, hist["eval"][-1], state,
                     telemetry=snapshot)

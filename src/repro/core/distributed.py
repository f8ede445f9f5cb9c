"""Distributed AFL train step (pjit) for the assigned architectures.

The federated mapping at pod scale (DESIGN.md §3/§5):

* clients = mesh slices along the (``pod`` x) ``data`` axes — N = 16 per pod
  (32 at two pods).  The global batch is split evenly among clients.
* per-client state (w_n, g_n, e_n) is stacked on a leading ``client`` axis
  sharded over (``pod``, ``data``); parameter dims are tensor-parallel over
  ``model``.
* the MES global model ``w`` is replicated over (``pod``, ``data``); the
  aggregation  w <- w - (1/N) sum_n zeta_n S(x_n)  contracts the client
  axis, which GSPMD lowers to the hierarchical reduce (within-pod reduce +
  cross-pod all-reduce) — the multi-pod MES synchronisation.
* MADS control (Propositions 1-2) runs per client on scalar contact inputs;
  S(.) is the sampled-quantile threshold mask (static shapes; DESIGN.md §3),
  through the ``sparsify_ef`` fused kernel path on TPU.
* any ``repro.compression`` codec rides the same step: pass ``compressor``
  and the round spends ``tau * A(p)`` through it instead of the fixed-u
  sparsify path, with the error-feedback memory ``e_n`` and a PRNG carry
  (``DistAflState.ckey``) threading the ``CompressorState`` as sharded
  pytrees.  Shard-safety of the codec's threshold/amax is the sampled
  strided-sample contract (core/README.md): construct codecs with
  ``method="sampled"`` at scale so GSPMD never all-gathers the model.
  The invocation is ``core.afl.compress_uploads`` — the SAME function the
  single-host engines call — so uploads are bit-identical across paths
  (tests/test_distributed_compression.py).

``make_afl_train_system`` returns everything the launcher/dry-run needs:
the step fn, state/input shardings, and an abstract state initialiser.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compression.base import Compressor
from repro.core import mads as M
from repro.core import sparsify as SP
from repro.core.afl import compress_uploads
from repro.core.mads import MadsController
from repro.sharding import rules as R
from repro.telemetry.tracing import phase


class DistAflState(NamedTuple):
    w: Any
    w_n: Any
    g_n: Any
    e_n: Any
    kappa: jax.Array  # (N,)
    q: jax.Array  # (N,)
    energy: jax.Array  # (N,)
    rnd: jax.Array
    ckey: jax.Array  # PRNG carry for stochastic codecs (repro/compression)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    num_clients: int
    learning_rate: float = 0.01
    rounds: int = 1000
    sample_size: int = 65536
    value_bits: int = 32
    state_dtype: str = "bfloat16"  # dtype of w_n/g_n/e_n client states
    upload_dtype: str = "float32"  # accumulation dtype of the MES reduce
    accum_dtype: str = "float32"  # local g_n/w_n update arithmetic; "bfloat16"
    # keeps the within-client gradient all-reduce in bf16 (halves its ICI
    # bytes; measured §Perf A3) at ~3-digit accumulate precision — the
    # error-feedback memory absorbs the rounding


def _client_axes(axes):
    return R.prepend_axis(axes, "client")


def mesh_num_clients(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def state_shardings(model, mesh: Mesh, dcfg: DistConfig, rules=None):
    rules = rules or dict(R.RULES_TRAIN, client=[("pod", "data"), ("data",)])
    axes = model.param_axes()
    shapes = R.shapes_tree(model.specs)
    w_sh = R.sharding_tree(axes, shapes, rules, mesh)
    cl_axes = _client_axes(axes)
    cl_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((dcfg.num_clients,) + s.shape, s.dtype), shapes
    )
    cl_sh = R.sharding_tree(cl_axes, cl_shapes, rules, mesh)
    rep = NamedSharding(mesh, P())
    return DistAflState(
        w=w_sh, w_n=cl_sh, g_n=cl_sh, e_n=cl_sh,
        kappa=rep, q=rep, energy=rep, rnd=rep, ckey=rep,
    )


def client_state_shardings(state: DistAflState, mesh: Mesh) -> DistAflState:
    """Leading-client-axis sharding spec for host-device parity runs.

    The global model and scalars replicate; the client-stacked trees take
    the mesh's ``data`` axis on their leading dim.  This is the spec the
    parity suite and ``bench_compression --mesh`` ``device_put`` with —
    production parameter sharding is ``state_shardings`` above.
    """
    rep = NamedSharding(mesh, P())
    cl = NamedSharding(mesh, P("data"))
    return DistAflState(
        w=jax.tree.map(lambda l: rep, state.w),
        w_n=jax.tree.map(lambda l: cl, state.w_n),
        g_n=jax.tree.map(lambda l: cl, state.g_n),
        e_n=jax.tree.map(lambda l: cl, state.e_n),
        kappa=rep, q=rep, energy=rep, rnd=rep, ckey=rep,
    )


def _key_struct():
    """ShapeDtypeStruct of a typed PRNG key without touching devices."""
    return jax.eval_shape(lambda: jax.random.key(0))


def abstract_state(model, dcfg: DistConfig):
    """ShapeDtypeStruct pytree of the distributed state (dry-run input)."""
    sdt = jnp.dtype(dcfg.state_dtype)
    shapes = R.shapes_tree(model.specs)
    w = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), shapes)
    cl = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((dcfg.num_clients,) + s.shape, sdt), shapes
    )
    n = dcfg.num_clients
    f32, i32 = jnp.float32, jnp.int32
    return DistAflState(
        w=w, w_n=cl, g_n=cl, e_n=cl,
        kappa=jax.ShapeDtypeStruct((n,), i32),
        q=jax.ShapeDtypeStruct((n,), f32),
        energy=jax.ShapeDtypeStruct((n,), f32),
        rnd=jax.ShapeDtypeStruct((), i32),
        ckey=_key_struct(),
    )


def init_state(model, dcfg: DistConfig, rng) -> DistAflState:
    w = model.init(rng)
    sdt = jnp.dtype(dcfg.state_dtype)
    n = dcfg.num_clients
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None].astype(sdt), (n,) + x.shape), t
    )
    zeros = lambda t: jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, sdt), t)
    return DistAflState(
        w=w, w_n=stack(w), g_n=zeros(w), e_n=zeros(w),
        kappa=jnp.zeros((n,), jnp.int32), q=jnp.zeros((n,), jnp.float32),
        energy=jnp.zeros((n,), jnp.float32), rnd=jnp.zeros((), jnp.int32),
        # same derivation as afl.afl_init so the two engines' codecs draw
        # identical dither streams from the same seed
        ckey=jax.random.fold_in(rng, 0x5EED),
    )


def _split_clients(batch, n: int):
    """(B, ...) -> (N, B/N, ...) on every leaf."""
    def f(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(f, batch)


def make_afl_train_step(model, cfg, dcfg: DistConfig, controller: MadsController,
                        compressor: Compressor | None = None,
                        telemetry=None, staleness=None, mesh: Mesh | None = None):
    """Builds the jittable distributed AFL round.

    ``compressor``: optional ``repro.compression`` codec; when given, the
    upload stage is the codec spending the realised contact capacity
    ``tau * A(p)`` (Proposition 1's left-hand side) with error feedback and
    the PRNG carry threaded through ``DistAflState`` — the same
    ``compress_uploads`` call as the single-host engines, so metrics and
    payloads match.  When None, the legacy fixed-u sampled-threshold path
    runs.

    ``telemetry``: optional ``repro.telemetry.MetricRegistry``.  When
    given, the step takes an extra trailing telemetry-state pytree and
    returns ``(state, metrics, tstate)`` — the accumulation rides the
    pjit program (replicated; histogram counts are exact integers, so the
    sharded client-axis reduce is bit-identical to single host).

    ``staleness``: optional ``core.afl.StalenessWeight`` — the FedAsync
    ``alpha * s(delta_tau)`` aggregation discount applied to the client-
    axis contraction, identical to the single-host ``afl_round`` mixing
    (None or the identity family keeps the paper's constant rule).

    ``mesh``: the mesh whose ``pod``/``data`` axes carry the client axis;
    the codec pass then runs per device under ``shard_map``, because GSPMD
    cannot partition the Pallas codec kernels (``compress_uploads``).
    Parameter dims must be whole within a client on that path.
    """
    n = dcfg.num_clients
    eta = dcfg.learning_rate
    sw = None if (staleness is None or staleness.is_identity) else staleness

    def step(state: DistAflState, batch, zeta, tau, h2, budgets,
             tstate=None):
        # the round index and the staleness the upload decision reads
        with phase("select"):
            r = state.rnd + 1
            theta = (r - state.kappa).astype(jnp.float32)

        with phase("grads"):
            cl_batch = _split_clients(batch, n)
            grad_fn = jax.vmap(jax.grad(lambda p, b: model.loss_fn(p, cfg, b)))
            grads = grad_fn(state.w_n, cl_batch)

            at = jnp.dtype(dcfg.accum_dtype)
            g_new = jax.tree.map(
                lambda g, d: (g.astype(at) + eta * d.astype(at)).astype(g.dtype),
                state.g_n, grads,
            )

        with phase("select"):
            x = jax.tree.map(lambda e, g: e + g, state.e_n, g_new)
            x_norm2 = sum(
                jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
                for l in jax.tree.leaves(x)
            )

            zf = zeta.astype(jnp.float32)
            k, p, energy = controller.select(zf, theta, x_norm2, state.q, tau, h2)
            ok = zf > 0
            okf = ok.astype(jnp.float32)
            k = k * okf
            energy = energy * okf

        with phase("compress"):
            if compressor is not None:
                rate = M.rate_bps(p, h2, controller.bandwidth,
                                  controller.noise_w_hz)
                budget_bits = tau * rate * okf
                upload, e_after, cstats, ckey = compress_uploads(
                    compressor, g_new, state.e_n, state.ckey, budget_bits, n,
                    mesh=mesh,
                )
                k_actual = cstats["k"]
                bits = cstats["bits"] * okf
                b_used = cstats["b"] * okf
            else:
                ckey = state.ckey
                upload, e_after, k_actual = jax.vmap(
                    lambda t, kk: SP.sparsify_tree(t, kk, method="sampled",
                                                   sample=dcfg.sample_size)
                )(x, k)
                bits = SP.bits_for_k(k_actual, controller.s, controller.u) * okf
                b_used = jnp.full_like(k_actual, float(controller.u)) * okf

        # MES aggregation: contract the client axis (hierarchical all-reduce)
        # with the optional alpha * s(delta_tau) staleness discount — the
        # same mixing weights as afl_round and the serve-path fused ingest
        with phase("aggregate"):
            udt = jnp.dtype(dcfg.upload_dtype)
            mix = okf if sw is None else okf * sw.weight(theta)
            w_new = jax.tree.map(
                lambda w, up: (
                    w.astype(udt)
                    - jnp.tensordot(mix.astype(udt), up.astype(udt), axes=(0, 0)) / n
                ).astype(w.dtype),
                state.w, upload,
            )

        with phase("state"):
            bcast = lambda l: jnp.broadcast_to(l[None], (n,) + l.shape)
            cond = lambda c, leaf: c.reshape(c.shape + (1,) * (leaf.ndim - 1))
            sdt = jnp.dtype(dcfg.state_dtype)
            w_n_new = jax.tree.map(
                lambda wn, wg, d: jnp.where(
                    cond(ok, wn), bcast(wg).astype(sdt),
                    (wn.astype(at) - eta * d.astype(at)).astype(sdt),
                ),
                state.w_n, w_new, grads,
            )
            e_n_new = jax.tree.map(
                lambda new, old: jnp.where(cond(ok, new), new.astype(sdt), old),
                e_after, state.e_n,
            )
            g_n_new = jax.tree.map(
                lambda g: jnp.where(cond(ok, g), jnp.zeros_like(g), g), g_new
            )
            kappa_new = jnp.where(ok, r, state.kappa)
            q_new = controller.queue_update(state.q, energy, budgets, dcfg.rounds)

            # same leaf-order reduction as the single-host afl_round so the
            # per-device table / probe accumulators stay engine-comparable
            e_norm2 = sum(
                jnp.sum(jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(1, l.ndim)))
                for l in jax.tree.leaves(e_n_new)
            )
        metrics = {
            "k": k_actual * okf,
            "success": (k_actual > 0).astype(jnp.float32) * okf,
            "power": p * okf,
            "energy": energy,
            "theta": theta,
            "uploads": okf,
            "x_norm2": x_norm2,
            "e_norm2": e_norm2,
            "bits": bits,  # realised payload (<= tau*A budget; eq. 7c)
            "b": b_used,  # value bit-width on the wire (u, or the codec's b*)
            "upload_bits": bits,  # legacy alias (pre-codec dashboards)
        }
        new_state = DistAflState(
            w=w_new, w_n=w_n_new, g_n=g_n_new, e_n=e_n_new,
            kappa=kappa_new, q=q_new, energy=state.energy + energy, rnd=r,
            ckey=ckey,
        )
        if telemetry is not None:
            from repro.telemetry import record_round

            return new_state, metrics, record_round(telemetry, tstate,
                                                    metrics, tau)
        return new_state, metrics

    return step


def run_afl_rounds(step, state, provider, batch_fn, budgets,
                   rounds: int | None = None, telemetry=None, tstate=None):
    """Drive a distributed AFL step from a ScenarioProvider.

    ``provider`` is anything yielding per-round (zeta, tau, h2) triples —
    normally ``repro.scenarios.ScenarioProvider`` — and ``batch_fn(r)``
    returns the round's global batch.  Returns (state, metrics history);
    with ``telemetry`` (the registry the step was built with) the
    device-resident telemetry state is threaded through every step and
    returned as a third element (fetch it once with ``telemetry.fetch``).
    """
    # budgets are round-invariant: wrap/transfer ONCE, not per round (the
    # same host->device churn bug fixed in core/runner.py in PR 2)
    budgets = budgets if isinstance(budgets, jax.Array) else jnp.asarray(
        budgets, jnp.float32)
    if telemetry is not None and tstate is None:
        tstate = telemetry.init_state()
    # heterogeneity loss masks (when the provider carries the layer) fold
    # into a suite's per-device table alongside each round's metrics
    aux_round = getattr(provider, "aux_round", lambda r: None)
    history = []
    for r, (zeta, tau, h2) in enumerate(provider):
        if rounds is not None and r >= rounds:
            break
        args = (
            state, batch_fn(r), jnp.asarray(zeta, jnp.float32),
            jnp.asarray(tau, jnp.float32), jnp.asarray(h2, jnp.float32),
            budgets,
        )
        if telemetry is not None:
            state, m, tstate = step(*args, tstate)
            from repro.telemetry import record_het

            tstate = record_het(telemetry, tstate, aux_round(r))
        else:
            state, m = step(*args)
        history.append(m)
    if telemetry is not None:
        return state, history, tstate
    return state, history


def scenario_shardings(mesh: Mesh):
    """Sharding specs for device-resident scenario arrays on ``mesh``.

    The (rounds, N) schedule tensors (zeta / tau / h2, and the
    heterogeneity aux masks and (N,) availability state) shard their
    CLIENT axis over the mesh's ``data`` dimension — every downstream
    consumer (the pjit step's client-stacked trees, the per-device
    telemetry rows) is elementwise on that axis, so a client-sharded
    schedule feeds the step with no resharding collectives.  Returns
    ``{"schedule": (rounds, N) spec, "state": (N,) spec}``.
    """
    return {
        "schedule": NamedSharding(mesh, P(None, "data")),
        "state": NamedSharding(mesh, P("data")),
    }


def telemetry_shardings(telemetry, mesh: Mesh):
    """Sharding pytree for a telemetry accumulation state on ``mesh``.

    Registry counters/histograms and probe scalars replicate (their
    updates are full reductions over the client axis, committed
    identically on every shard — integer-exact for the counts).  A
    ``TelemetrySuite``'s per-device table instead takes the mesh's
    ``data`` axis on its (N,) rows: every table update is elementwise per
    client, so each shard accumulates ONLY its own clients' rows and
    GSPMD inserts no mid-run collectives — the rows merge once, at fetch.
    """
    rep = NamedSharding(mesh, P())
    if telemetry is None:
        return rep
    from repro.telemetry import TelemetrySuite

    state = jax.eval_shape(telemetry.init_state)
    if isinstance(telemetry, TelemetrySuite) and telemetry.device is not None:
        cl = NamedSharding(mesh, P("data"))
        out = {k: jax.tree.map(lambda _: rep, v) for k, v in state.items()}
        out["device"] = {f: (cl if s.ndim else rep)
                         for f, s in state["device"].items()}
        return out
    return jax.tree.map(lambda _: rep, state)


def ingest_shardings(mesh: Mesh):
    """Sharding specs for the serve-path fused ingest op on ``mesh``.

    A packed wire batch (``repro.compression.wire.pack_batch``) shards its
    leading BATCH axis over the mesh's ``data`` dimension — decode and the
    per-upload scatter are elementwise on that axis, and the weighted
    client contraction of the aggregation is the only collective (GSPMD
    lowers it to the hierarchical all-reduce, exactly like the train
    step's client-axis reduce).  The global model replicates.  Returns
    ``{"batch": spec for (B, ...) arrays, "w": replicated spec}``.
    """
    return {
        "batch": NamedSharding(mesh, P("data")),
        "w": NamedSharding(mesh, P()),
    }


def make_afl_train_system(model, cfg, mesh: Mesh, dcfg: DistConfig | None = None,
                          rules=None, controller: MadsController | None = None,
                          compressor: Compressor | None = None,
                          telemetry=None, staleness=None):
    """Step + shardings bundle for the launcher / dry-run."""
    dcfg = dcfg or DistConfig(num_clients=mesh_num_clients(mesh))
    controller = controller or MadsController(s=model.num_params())
    step = make_afl_train_step(model, cfg, dcfg, controller,
                               compressor=compressor, telemetry=telemetry,
                               staleness=staleness, mesh=mesh)
    st_sh = state_shardings(model, mesh, dcfg, rules)
    rep = NamedSharding(mesh, P())
    return {
        "step": step,
        "dcfg": dcfg,
        "controller": controller,
        "compressor": compressor,
        "telemetry": telemetry,
        "state_shardings": st_sh,
        "scalar_sharding": rep,
        # registry state replicates (integer-exact histogram counts commit
        # the same value on every shard); a suite's per-device rows shard
        # over the client mesh — see telemetry_shardings
        "telemetry_sharding": telemetry_shardings(telemetry, mesh),
        "abstract_state": lambda: abstract_state(model, dcfg),
        "init_state": lambda rng: init_state(model, dcfg, rng),
    }

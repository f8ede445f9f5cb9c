"""Algorithm 1 — the AFL training process (simulation mode).

One jitted ``afl_round`` advances the whole federation by one round:
all N devices compute stochastic gradients (vmapped), the contacted subset
uploads sparsified cumulative gradients with error feedback, the MES
aggregates, and staleness / virtual-energy-queue bookkeeping advances.

The upload policy (who sends what, at which k and p) is pluggable — MADS
and every §VI-B baseline are policies over the same engine, so benchmark
comparisons differ only in the policy, exactly like the paper's setup.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compression.base import Compressor, CompressorState
from repro.core import mads as M
from repro.core import sparsify as SP
from repro.core.mads import MadsController
from repro.telemetry.tracing import phase


class AflState(NamedTuple):
    w: Any  # global model pytree
    w_n: Any  # per-device models, leaves stacked on leading N
    g_n: Any  # cumulative gradients (eta-scaled), stacked
    e_n: Any  # error memory, stacked
    kappa: jax.Array  # (N,) last global-model reception round
    q: jax.Array  # (N,) virtual energy queues
    energy: jax.Array  # (N,) cumulative energy spent
    rnd: jax.Array  # scalar round index r
    ckey: jax.Array  # PRNG key for stochastic codecs (repro/compression)


@dataclasses.dataclass(frozen=True)
class StalenessWeight:
    """The FedAsync ``alpha * s(delta_tau)`` staleness-discount family.

    The paper's MES mixes every upload at a constant weight; Xie et al.'s
    asynchronous-optimization line generalises the rule to a staleness-
    dependent discount ``alpha * s(delta_tau)`` with ``s`` drawn from:

    * ``constant``: ``s = 1``            (the paper's rule at ``alpha``)
    * ``hinge``:    ``s = 1`` while ``delta_tau <= hinge_b``, then
                    ``1 / (hinge_a * (delta_tau - hinge_b))``
    * ``poly``:     ``s = (delta_tau + 1) ** -poly_a``

    Frozen/hashable so it rides ``Policy`` (and the serve-path ingest op)
    as a jit static argument.  The default — constant at ``alpha = 1`` —
    is the identity: engines skip the multiply entirely (``is_identity``
    is a compile-time branch), so existing programs are unchanged.
    """

    family: str = "constant"  # constant | hinge | poly
    alpha: float = 1.0
    hinge_a: float = 10.0
    hinge_b: float = 4.0
    poly_a: float = 0.5

    FAMILIES = ("constant", "hinge", "poly")

    @property
    def is_identity(self) -> bool:
        return self.family == "constant" and self.alpha == 1.0

    def s(self, delta_tau):
        """The undiscounted ``s(delta_tau)`` term (jnp-traceable)."""
        dt = jnp.asarray(delta_tau, jnp.float32)
        if self.family == "constant":
            return jnp.ones_like(dt)
        if self.family == "hinge":
            return jnp.where(
                dt <= self.hinge_b, 1.0,
                1.0 / (self.hinge_a * jnp.maximum(dt - self.hinge_b, 1e-9)),
            )
        if self.family == "poly":
            return (dt + 1.0) ** (-self.poly_a)
        raise ValueError(
            f"unknown staleness family {self.family!r}; "
            f"known: {self.FAMILIES}")

    def weight(self, delta_tau):
        """``alpha * s(delta_tau)`` — the aggregation mixing weight."""
        return self.alpha * self.s(delta_tau)


@dataclasses.dataclass(frozen=True)
class Policy:
    """Engine flags + (k, p) selection strategy."""

    name: str = "mads"
    controller: MadsController | None = None
    sparsify: bool = True  # False -> all-or-nothing full upload
    error_feedback: bool = True
    local_updates: bool = True  # SGD during inter-contact (False: SFL)
    train_every_round: bool = True  # False: gradient only at contact (SFL)
    energy_capped: bool = False  # hard stop when budget exhausted (AFL/AFL-Spar)
    fixed_power: float = 0.0  # >0: transmit at this power (non-MADS baselines)
    # None -> the seed top-k-at-32-bit path below; a repro.compression codec
    # replaces the sparsify/quantize stage and spends tau*A(p) bits itself
    compressor: Compressor | None = None
    # staleness-discounted aggregation weight alpha * s(delta_tau) shared
    # by every engine AND the streaming ingestion server (repro/serve) —
    # the default is the identity (the paper's constant rule at alpha=1)
    staleness: StalenessWeight = StalenessWeight()
    # True -> afl_round also returns the dense upload payloads under
    # metrics["upload"] (N-stacked tree).  Test/serve plumbing only: the
    # serve parity suite feeds the SAME uploads through the wire format
    # and the fused ingest op.  Engines leave this False (the scan engine
    # would otherwise buffer (rounds, N, s) payloads)
    expose_uploads: bool = False

    def select(self, ctl: MadsController, zeta, theta, x_norm2, q, tau, h2):
        if self.controller is not None and self.fixed_power <= 0:
            return self.controller.select(zeta, theta, x_norm2, q, tau, h2)
        # fixed-power policies: k fills the contact window at power p_fix
        p = jnp.full_like(tau, self.fixed_power) * zeta
        k = M.mads_k(p, tau, h2, ctl.s, ctl.u, ctl.bandwidth, ctl.noise_w_hz) * zeta
        if not self.sparsify:
            # full upload or nothing: feasible iff s fits in tau * A
            feasible = k >= ctl.s
            k = jnp.where(feasible, float(ctl.s), 0.0)
            bits = SP.bits_for_k(k, ctl.s, ctl.u)
            a = M.rate_bps(p, h2, ctl.bandwidth, ctl.noise_w_hz)
            energy = jnp.where(feasible, p * bits / jnp.maximum(a, 1e-9), 0.0)
            return k, p * feasible, energy
        energy = p * tau
        return k, p, energy


def compress_uploads(comp: Compressor, g_n, e_n, ckey, budget_bits, n: int,
                     mesh=None):
    """One codec pass over the federation — shared by BOTH engines.

    The single-host ``afl_round`` below and the pjit distributed step
    (``core/distributed.py``) call this same function, so the key
    splitting, per-device vmap, and ``CompressorState`` threading are
    identical — which is what makes their uploads bit-identical (the
    parity suite in tests/test_distributed_compression.py pins this).

    ``mesh``: the distributed step's mesh when the client axis is sharded
    over its ``pod``/``data`` axes.  GSPMD cannot partition a Pallas
    kernel, so the pass then runs under ``shard_map``: each device
    compresses its own clients, whose tensors are whole on it.

    Returns ``(upload, e_after, cstats, ckey)``: the dense dequantised
    payloads, the error-feedback memories, the per-device ``{"k", "bits",
    "b"}`` stats, and the advanced PRNG carry.
    """
    ckey, sub = jax.random.split(ckey)
    dev_keys = jax.random.split(sub, n)
    codec = jax.vmap(comp.compress)
    if mesh is not None:
        spec = P(tuple(a for a in ("pod", "data") if a in mesh.axis_names))
        # check_vma=False: the kernels' out_shape carries no varying-axes
        # type; every output varies over the client axes, as out_specs say
        codec = jax.shard_map(codec, mesh=mesh, in_specs=spec,
                              out_specs=spec, check_vma=False)
    upload, cstate, cstats = codec(
        g_n, budget_bits, CompressorState(error=e_n, key=dev_keys)
    )
    return upload, cstate.error, cstats, ckey


def _bcast_to(cond, leaf):
    return cond.reshape(cond.shape + (1,) * (leaf.ndim - 1))


def _select(cond, a, b):
    """Per-device select over stacked pytrees. cond: (N,) 0/1."""
    return jax.tree.map(lambda x, y: jnp.where(_bcast_to(cond, x) != 0, x, y), a, b)


def afl_init(model, cfg, fl, rng) -> AflState:
    w = model.init(rng)
    n = fl.num_devices
    stack = lambda t: jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
    zeros = lambda t: jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), t)
    return AflState(
        w=w,
        w_n=stack(w),
        g_n=zeros(w),
        e_n=zeros(w),
        kappa=jnp.zeros((n,), jnp.int32),
        q=jnp.zeros((n,), jnp.float32),
        energy=jnp.zeros((n,), jnp.float32),
        rnd=jnp.zeros((), jnp.int32),
        ckey=jax.random.fold_in(rng, 0x5EED),
    )


@partial(jax.jit, static_argnames=("model", "cfg", "fl", "policy"))
def afl_round(state: AflState, batch, zeta, tau, h2, energy_budget,
              *, model, cfg, fl, policy: Policy) -> tuple[AflState, dict]:
    """One round r of Algorithm 1.

    batch: stacked per-device minibatches (leading N); zeta (N,) 0/1;
    tau (N,) contact durations; h2 (N,) channel gains;
    energy_budget (N,) E_n^con.
    """
    n = fl.num_devices
    eta = fl.learning_rate
    ctl = policy.controller or MadsController(s=model.num_params())
    # the round index and the staleness the upload decision reads
    with phase("select"):
        r = state.rnd + 1
        theta = (r - state.kappa).astype(jnp.float32)

    # --- local stochastic gradients (all devices, vmapped) -----------------
    with phase("grads"):
        grad_fn = jax.vmap(jax.grad(lambda p, b: model.loss_fn(p, cfg, b)))
        grads = grad_fn(state.w_n, batch)
        if not policy.train_every_round:
            grads = jax.tree.map(lambda g: g * _bcast_to(zeta.astype(g.dtype), g), grads)

        g_new = jax.tree.map(lambda g, d: g + eta * d.astype(g.dtype), state.g_n, grads)

    # --- upload decision (MADS or baseline policy) --------------------------
    with phase("select"):
        x = jax.tree.map(jnp.add, state.e_n, g_new)
        x_norm2 = sum(
            jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
            for l in jax.tree.leaves(x)
        )
        zf = zeta.astype(jnp.float32)
        k, p, energy = policy.select(ctl, zf, theta, x_norm2, state.q, tau, h2)
        ok = zf > 0
        if policy.energy_capped:
            ok = ok & (state.energy + energy <= energy_budget)
        k = k * ok
        energy = energy * ok
        okf = ok.astype(jnp.float32)

    # --- compression with error feedback -----------------------------------
    with phase("compress"):
        if policy.compressor is not None:
            # codec path: the budget is the realised contact capacity tau*A(p)
            # (Proposition 1's left-hand side); the codec decides how to spend
            # it (k, b, or both) and returns the EF residual as its state
            rate = M.rate_bps(p, h2, ctl.bandwidth, ctl.noise_w_hz)
            budget_bits = tau * rate * okf
            upload, e_after, cstats, ckey = compress_uploads(
                policy.compressor, g_new, state.e_n, state.ckey, budget_bits, n
            )
            k_actual = cstats["k"]
            bits = cstats["bits"] * okf
            b_used = cstats["b"] * okf
        else:
            # seed path: top-k at fixed ctl.u-bit values (paper §III-D)
            ckey = state.ckey
            upload, e_after, k_actual = jax.vmap(
                lambda t, kk: SP.sparsify_tree(t, kk, method=fl.sparsifier, sample=fl.sample_size)
            )(x, k)
            if ctl.u < 32:  # quantized wire format: EF absorbs the residual too
                upload_q = jax.vmap(lambda t: SP.quantize_values(t, ctl.u))(upload)
                e_after = jax.tree.map(lambda e, u, uq: e + (u - uq), e_after, upload, upload_q)
                upload = upload_q
            bits = SP.bits_for_k(k_actual, ctl.s, ctl.u) * okf
            b_used = jnp.full_like(k_actual, float(ctl.u)) * okf
        if not policy.error_feedback:
            e_after = jax.tree.map(jnp.zeros_like, e_after)

    # --- MES aggregation: w <- w - (1/N) sum a s(theta) zeta S(x_n) ---------
    # mixing weight: the FedAsync alpha * s(delta_tau) staleness discount;
    # the default family is the identity (compile-time branch), keeping the
    # paper's constant rule — and the serve-path fused ingest op applies
    # the SAME weights, which is what makes the two paths bit-comparable
    with phase("aggregate"):
        mix = okf if policy.staleness.is_identity \
            else okf * policy.staleness.weight(theta)
        w_new = jax.tree.map(
            lambda w, up: (
                w - (jnp.tensordot(mix, up.astype(jnp.float32), axes=(0, 0)) / n).astype(w.dtype)
            ),
            state.w,
            upload,
        )

    # --- device-side state transitions --------------------------------------
    with phase("state"):
        w_local = (
            jax.tree.map(lambda wn, d: wn - eta * d.astype(wn.dtype), state.w_n, grads)
            if policy.local_updates
            else state.w_n
        )
        w_bcast = jax.tree.map(lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), w_new)
        w_n_new = _select(okf, w_bcast, w_local)
        e_n_new = _select(okf, e_after, state.e_n)
        g_n_new = _select(okf, jax.tree.map(jnp.zeros_like, g_new), g_new)
        kappa_new = jnp.where(ok, r, state.kappa)
        q_new = ctl.queue_update(state.q, energy, energy_budget, fl.rounds)

        # per-device EF-memory squared norm (Lemma 4's E||e_n||^2, observable):
        # same leaf-order reduction as x_norm2 so engines agree bit-for-bit
        e_norm2 = sum(
            jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
            for l in jax.tree.leaves(e_n_new)
        )
    metrics = {
        "k": k_actual * okf,
        "k_target": k,
        "success": (k_actual > 0).astype(jnp.float32) * okf,
        "power": p * okf,
        "energy": energy,
        "theta": theta,
        "uploads": okf,
        "x_norm2": x_norm2,
        "e_norm2": e_norm2,
        "queue": q_new,
        "bits": bits,  # realised upload payload (<= tau*A budget; eq. 7c)
        "b": b_used,  # value bit-width on the wire (u, or the codec's b*)
    }
    if policy.expose_uploads:
        # serve-parity plumbing: the dense payloads the MES just applied,
        # plus the quantisation step a wire encoder needs to turn them
        # back into grid codes (compression/wire.py; 1.0 = raw floats)
        metrics["upload"] = upload
        metrics["upload_step"] = (
            cstats["step"] if policy.compressor is not None
            else jnp.ones((n,), jnp.float32))
    new_state = AflState(
        w=w_new, w_n=w_n_new, g_n=g_n_new, e_n=e_n_new,
        kappa=kappa_new, q=q_new, energy=state.energy + energy, rnd=r,
        ckey=ckey,
    )
    return new_state, metrics

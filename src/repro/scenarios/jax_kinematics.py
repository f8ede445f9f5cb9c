"""Device-resident mobility kinematics: the JAX port of ``kinematics.py``.

The NumPy models in ``kinematics.py`` are the *oracle*: readable,
host-side, and statistically validated (tests/test_scenarios.py).  This
module re-implements the same four models as ``jit``/``vmap``-able JAX
programs so the whole scenario pipeline — trace -> in-range -> contact
intervals -> per-round (zeta, tau) -> position-coupled h2 — runs as ONE
compiled program on the accelerator, with zero host round-trips between
the PRNG draw and the finished (rounds, N) schedule.  That removes the
scenario wall between the compiled AFL engines (scan / pjit) and
million-device federations: generation cost scales with device FLOPs/
bandwidth, not with host Python (see benchmarks/bench_mobility.py).

Differences from the oracle, by construction:

* PRNG: ``jax.random`` (threefry) streams cannot reproduce
  ``np.random.default_rng`` draws, so JAX-vs-NumPy parity is *statistical*
  (distributional bounds + CI bands, tests/test_jax_scenarios.py).  The
  downstream contact extraction (``jax_contacts.py``) IS bit-comparable:
  on a shared in-range matrix it reproduces ``scenarios/contacts.py``
  intervals and ``mobility.contact.intervals_to_rounds`` cells exactly.
* Random waypoint draws a *static* leg budget (jit needs static shapes)
  instead of the oracle's redraw-until-covered loop.  The budget carries
  a 2.2x margin over the expected leg count plus 16 legs of slack; a
  device that exhausts it parks at its last waypoint (the same clamp
  ``np.interp`` applies past the final breakpoint).  At the oracle's
  1.8x + 8 budget a redraw is already rare; at 2.2x + 16 the parking
  probability is negligible for every tested horizon.
* Manhattan sizes its leg budget by the worst-case per-device speed
  (1.5 v) rather than the realised ``speeds.max()`` — a superset, never
  fewer legs than the oracle would allocate.

Every model is a frozen (hashable) dataclass satisfying the same
``MobilityModel`` protocol (``num_devices`` / ``area`` / ``mean_speed`` /
``trace``), so ``ScenarioProvider`` treats both backends uniformly.
Memory note: a trace materialises (steps, N, 2) f32 positions on device
(~0.8 GB at N=1e5, steps=1000); for N -> 1e6 keep the horizon short or
generate round-blocks per call — the models are pure functions of
(key, steps), so block-wise generation composes.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "JaxTrace",
    "JaxRandomWaypointModel",
    "JaxGaussMarkovModel",
    "JaxManhattanGridModel",
    "JaxHotspotClusterModel",
    "JAX_MODELS",
    "jax_gains_along_trace",
    "jax_schedule_from_model",
]


@dataclasses.dataclass
class JaxTrace:
    """Device-resident twin of ``kinematics.Trace`` (jnp arrays)."""

    pos: jax.Array  # (steps, num_devices, 2) f32, metres
    mes: jax.Array  # (steps, 2) MES position
    dt: float

    @property
    def steps(self) -> int:
        return self.pos.shape[0]

    @property
    def num_devices(self) -> int:
        return self.pos.shape[1]

    def distances(self) -> jax.Array:
        return jnp.linalg.norm(self.pos - self.mes[:, None, :], axis=-1)

    def in_range(self, comm_range: float) -> jax.Array:
        return self.distances() < comm_range

    def to_numpy(self):
        """Host materialisation as the oracle's ``Trace`` (tests only)."""
        from repro.scenarios.kinematics import Trace

        return Trace(pos=np.asarray(self.pos), mes=np.asarray(self.mes),
                     dt=self.dt)


def _reflect(x, hi: float):
    """Fold unbounded coordinates into [0, hi] by reflection at the walls."""
    y = jnp.mod(x, 2.0 * hi)
    return jnp.where(y > hi, 2.0 * hi - y, y)


def _static_mes(steps: int, area: float):
    return jnp.full((steps, 2), 0.5 * area, jnp.float32)


# ---------------------------------------------------------------------------
# Position kernels (pure, jittable; model dataclasses are static args)
# ---------------------------------------------------------------------------


def _rwp_positions(key, steps: int, dt: float, n: int, area: float,
                   mean_speed: float, pause_max: float):
    """Leg-based random waypoint, fully batched.

    The oracle's per-entity ``np.interp`` loop becomes one vmapped
    ``searchsorted`` + gather over the (n, 2m) breakpoint table — the
    O(N) Python loop that dominates NumPy generation at N >= 1e4
    disappears entirely.
    """
    duration = steps * dt
    est_leg = 0.5214 * area / max(mean_speed, 1e-9) + 0.5 * pause_max
    m = int(duration / max(est_leg, 1e-9) * 2.2) + 16  # static budget
    kn, ks, kp = jax.random.split(key, 3)
    nodes = jax.random.uniform(kn, (n, m + 1, 2), jnp.float32, 0.0, area)
    speeds = jax.random.uniform(ks, (n, m), jnp.float32,
                                0.5 * mean_speed, 1.5 * mean_speed)
    pauses = jax.random.uniform(kp, (n, m), jnp.float32, 0.0, pause_max)
    travel = (jnp.linalg.norm(jnp.diff(nodes, axis=1), axis=-1)
              / jnp.maximum(speeds, 1e-9))
    leg_start = jnp.cumsum(travel + pauses, axis=1) - (travel + pauses)

    # breakpoints: (depart, node_k) then (arrive, node_{k+1}) per leg —
    # renders motion and pause (flat segment) exactly like the oracle
    tp = jnp.stack([leg_start, leg_start + travel], axis=2).reshape(n, 2 * m)
    xs = jnp.stack([nodes[:, :-1], nodes[:, 1:]], axis=2).reshape(n, 2 * m, 2)

    tq = jnp.arange(steps, dtype=jnp.float32) * dt
    # bucketed lookup on the uniform query grid: a breakpoint at time t is
    # <= tq[j] exactly for j >= ceil(t/dt), so per-row bucket counts of
    # ceil(tp/dt) followed by a cumsum reproduce
    # searchsorted(tp, tq, side="right") in O(m + steps) work per device
    # instead of the vmapped O(steps log m) binary search (which left
    # jitted RWP barely ahead of the NumPy oracle).  An off-by-one at a
    # breakpoint sitting within one ulp of a grid point is positionally
    # harmless: adjacent segments share the breakpoint node, so both leg
    # choices interpolate to the same position
    q0 = jnp.clip(jnp.ceil(tp / dt).astype(jnp.int32), 0, steps)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    # int16 carries the running count (<= 2m « 32767) at half the cumsum
    # memory traffic — the scan is bandwidth-bound on CPU
    cnt = jnp.zeros((n, steps + 1), jnp.int16).at[rows, q0].add(
        jnp.int16(1))
    idx = jnp.cumsum(cnt[:, :steps], axis=1).astype(jnp.int32)
    i1 = jnp.clip(idx, 1, 2 * m - 1)
    i0 = i1 - 1
    t0 = jnp.take_along_axis(tp, i0, axis=1)  # (n, steps)
    t1 = jnp.take_along_axis(tp, i1, axis=1)
    x0 = jnp.take_along_axis(xs, i0[:, :, None], axis=1)  # (n, steps, 2)
    x1 = jnp.take_along_axis(xs, i1[:, :, None], axis=1)
    den = t1 - t0
    frac = jnp.clip(jnp.where(den > 0, (tq[None] - t0)
                              / jnp.maximum(den, 1e-12), 1.0), 0.0, 1.0)
    pos = x0 + frac[:, :, None] * (x1 - x0)
    return pos.transpose(1, 0, 2)  # (steps, n, 2)


def _gm_positions(key, steps: int, dt: float, n: int, area: float,
                  mean_speed: float, corr_dist: float):
    """AR(1) velocity with reflecting walls — ``lax.scan`` over steps on an
    (n, 2) carry, identical recurrence to the oracle."""
    alpha = float(np.exp(-dt * mean_speed / max(corr_dist, 1e-9)))
    sig_c = mean_speed / float(np.sqrt(np.pi / 2.0))
    scale = sig_c * float(np.sqrt(max(1.0 - alpha * alpha, 0.0)))
    kn, kv, kx = jax.random.split(key, 3)
    noise = jax.random.normal(kn, (steps, n, 2), jnp.float32)
    v0 = sig_c * jax.random.normal(kv, (n, 2), jnp.float32)
    x0 = jax.random.uniform(kx, (n, 2), jnp.float32, 0.0, area)

    # integrate displacement inside the scan carry: a separate
    # ``jnp.cumsum`` over the (steps, n, 2) velocity array is the single
    # most expensive op in the pipeline on CPU (XLA lowers it to log-depth
    # passes over the full array), while extending the carry is ~free
    def step(carry, eps):
        v, s = carry
        v = alpha * v + scale * eps
        s = s + v * dt
        return (v, s), s

    _, disp = jax.lax.scan(step, (v0, jnp.zeros_like(v0)), noise)
    return _reflect(x0[None] + disp, area)


# host constant: a jnp array here would start the JAX backend at import,
# before an entry point could set the host device count
_DIRS = np.asarray([[1.0, 0.0], [0.0, 1.0], [-1.0, 0.0], [0.0, -1.0]],
                   np.float32)


def _manhattan_positions(key, steps: int, dt: float, n: int, area: float,
                         mean_speed: float, block: float, p_turn: float):
    """Lattice streets, i.i.d. turns — the oracle is already closed-form
    (cumsum of turns + direct leg-index divide) and ports one-to-one."""
    grid_n = max(int(round(area / block)), 1)
    a = grid_n * block
    duration = steps * dt
    m = int(duration * 1.5 * mean_speed / block) + 2  # worst-case speed

    ks, kt, kh, kx = jax.random.split(key, 4)
    speeds = jnp.maximum(
        jax.random.uniform(ks, (n,), jnp.float32,
                           0.5 * mean_speed, 1.5 * mean_speed), 1e-9)
    u = jax.random.uniform(kt, (n, m), jnp.float32)
    turn = jnp.where(u < 0.5 * p_turn, 1, jnp.where(u < p_turn, -1, 0))
    head0 = jax.random.randint(kh, (n,), 0, 4)
    head = (head0[:, None] + jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int32), jnp.cumsum(turn, axis=1)[:, :-1]],
        axis=1)) % 4
    start = (jax.random.randint(kx, (n, 2), 0, grid_n + 1)
             .astype(jnp.float32) * block)
    nodes = start[:, None, :] + block * jnp.concatenate(
        [jnp.zeros((n, 1, 2), jnp.float32), jnp.cumsum(jnp.asarray(_DIRS)[head], axis=1)],
        axis=1)
    # reflection folds lattice points onto lattice points (block | area)
    nodes = _reflect(nodes, a)

    leg_dur = block / speeds  # (n,)
    tq = jnp.arange(steps, dtype=jnp.float32) * dt
    pos_t = tq[None, :] / leg_dur[:, None]
    idx = jnp.clip(pos_t.astype(jnp.int32), 0, m - 1)
    frac = jnp.clip(pos_t - idx, 0.0, 1.0)
    gather = jnp.broadcast_to(idx[:, :, None], (n, steps, 2))
    p0 = jnp.take_along_axis(nodes, gather, axis=1)
    p1 = jnp.take_along_axis(nodes, gather + 1, axis=1)
    pos = p0 + frac[:, :, None] * (p1 - p0)
    return pos.transpose(1, 0, 2), a


def _hotspot_positions(key, steps: int, dt: float, n: int, area: float,
                       mean_speed: float, num_hotspots: int, radius: float):
    """OU excursion around hotspot anchors; ``mean_speed == 0`` devolves to
    the static crowd (a compile-time branch — the model is a static arg)."""
    kc, ka, ko, kv, kn = jax.random.split(key, 5)
    centers = jax.random.uniform(kc, (num_hotspots, 2), jnp.float32,
                                 0.15 * area, 0.85 * area)
    anchor = centers[jax.random.randint(ka, (n,), 0, num_hotspots)]
    sig_c = radius / float(np.sqrt(2.0))
    off0 = sig_c * jax.random.normal(ko, (n, 2), jnp.float32)
    if mean_speed <= 0:  # static scenario
        pos = jnp.clip(anchor + off0, 0.0, area)
        return jnp.broadcast_to(pos[None], (steps, n, 2))

    rate = mean_speed / max(radius, 1e-9)
    alpha = float(np.exp(-dt * rate))
    vel_sig = mean_speed / float(np.sqrt(np.pi / 2.0))
    scale = vel_sig * float(np.sqrt(max(1.0 - alpha * alpha, 0.0)))
    vel0 = vel_sig * jax.random.normal(kv, (n, 2), jnp.float32)
    noise = jax.random.normal(kn, (steps, n, 2), jnp.float32)

    def step(carry, eps):
        off, vel = carry
        vel = alpha * vel - (1.0 - alpha) * rate * off + scale * eps
        off = off + vel * dt
        return (off, vel), off

    _, offs = jax.lax.scan(step, (off0, vel0), noise)
    return jnp.clip(anchor[None] + offs, 0.0, area)


# ---------------------------------------------------------------------------
# Models (frozen -> hashable -> usable as jit static args)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("model", "steps", "dt"))
def _model_positions(model, key, steps: int, dt: float):
    """One jit entry for every model: ``(pos, mes)`` device arrays."""
    return model._positions(key, steps, dt)


class _JaxModelBase:
    """Shared ``trace``/key plumbing for the four models below."""

    def key(self) -> jax.Array:
        return jax.random.key(self.seed)

    def trace(self, duration: float, dt: float = 1.0) -> JaxTrace:
        steps = int(duration / dt)
        pos, mes = _model_positions(self, self.key(), steps, float(dt))
        return JaxTrace(pos=pos, mes=mes, dt=float(dt))


@dataclasses.dataclass(frozen=True)
class JaxRandomWaypointModel(_JaxModelBase):
    num_devices: int = 20
    area: float = 1000.0
    mean_speed: float = 10.0  # m/s; per-leg speeds ~ U(0.5v, 1.5v)
    pause_max: float = 5.0
    seed: int = 0

    def _positions(self, key, steps: int, dt: float):
        pos = _rwp_positions(key, steps, dt, self.num_devices, self.area,
                             self.mean_speed, self.pause_max)
        return pos, _static_mes(steps, self.area)


@dataclasses.dataclass(frozen=True)
class JaxGaussMarkovModel(_JaxModelBase):
    num_devices: int = 20
    area: float = 1000.0
    mean_speed: float = 10.0
    corr_dist: float = 200.0  # inverse-speed law by construction (oracle)
    seed: int = 0

    def _positions(self, key, steps: int, dt: float):
        pos = _gm_positions(key, steps, dt, self.num_devices, self.area,
                            self.mean_speed, self.corr_dist)
        return pos, _static_mes(steps, self.area)


@dataclasses.dataclass(frozen=True)
class JaxManhattanGridModel(_JaxModelBase):
    num_devices: int = 20
    area: float = 1000.0
    mean_speed: float = 10.0
    block: float = 100.0
    p_turn: float = 0.5
    seed: int = 0

    def _positions(self, key, steps: int, dt: float):
        pos, a = _manhattan_positions(
            key, steps, dt, self.num_devices, self.area, self.mean_speed,
            self.block, self.p_turn)
        return pos, _static_mes(steps, a)


@dataclasses.dataclass(frozen=True)
class JaxHotspotClusterModel(_JaxModelBase):
    num_devices: int = 20
    area: float = 1000.0
    mean_speed: float = 10.0  # 0 -> perfectly static devices
    num_hotspots: int = 4
    hotspot_radius: float = 150.0
    seed: int = 0

    def _positions(self, key, steps: int, dt: float):
        pos = _hotspot_positions(
            key, steps, dt, self.num_devices, self.area, self.mean_speed,
            self.num_hotspots, self.hotspot_radius)
        return pos, _static_mes(steps, self.area)


JAX_MODELS = {
    "rwp": JaxRandomWaypointModel,
    "gauss_markov": JaxGaussMarkovModel,
    "manhattan": JaxManhattanGridModel,
    "hotspot": JaxHotspotClusterModel,
}


# ---------------------------------------------------------------------------
# Position-coupled channel gains (JAX port of scenarios/channel.py)
# ---------------------------------------------------------------------------


def jax_gains_along_trace(key, pos, mes, *, carrier_ghz: float = 3.5,
                          shadow_los_db: float = 4.0,
                          shadow_nlos_db: float = 8.2,
                          shadow_corr_dist: float = 25.0):
    """|h|^2 per (round, device) from per-round positions, on device.

    Same TR 38.901 UMi model as ``gains_along_trace``: distance path loss,
    Gudmundson AR(1) lognormal shadowing (round-to-round correlation
    ``exp(-displacement / shadow_corr_dist)``), and a persistent LOS state
    redrawn only when the device moves.  The O(rounds) host recurrence
    becomes a ``lax.scan`` carrying the (n,) LOS/shadowing state.
    Innovations come from ``jax.random``, so gains match the NumPy path in
    distribution, not bitwise.
    """
    d = jnp.linalg.norm(pos - mes[:, None, :], axis=-1)  # (R, n)
    r_total, n = d.shape
    dm = jnp.maximum(d, 1e-9)
    p_los = jnp.where(d <= 18.0, 1.0,
                      jnp.minimum(18.0 / dm + jnp.exp(-d / 36.0)
                                  * (1.0 - 18.0 / dm), 1.0))
    disp = jnp.concatenate(
        [jnp.zeros((1, n)), jnp.linalg.norm(pos[1:] - pos[:-1], axis=-1)]
    )
    rho = jnp.exp(-disp / max(shadow_corr_dist, 1e-9))
    # round 0 draws fresh LOS/shadowing state: zero correlation with the
    # (all-zeros) initial carry
    rho = rho.at[0].set(0.0)

    keys = jax.random.split(key, r_total)

    def step(carry, xs):
        los_p, z_p = carry
        k, rho_r, p_r = xs
        k1, k2, k3 = jax.random.split(k, 3)
        redraw = jax.random.uniform(k1, (n,)) >= rho_r
        los = jnp.where(redraw, jax.random.uniform(k2, (n,)) < p_r, los_p)
        z = rho_r * z_p + jnp.sqrt(jnp.maximum(1.0 - rho_r**2, 0.0)) \
            * jax.random.normal(k3, (n,))
        return (los, z), (los, z)

    init = (jnp.zeros((n,), bool), jnp.zeros((n,)))
    _, (los, z) = jax.lax.scan(step, init, (keys, rho, p_los))

    dcl = jnp.maximum(d, 1.0)
    pl = (32.4 + jnp.where(los, 21.0, 31.9) * jnp.log10(dcl)
          + 20.0 * float(np.log10(carrier_ghz)))
    sigma = jnp.where(los, shadow_los_db, shadow_nlos_db)
    return (10.0 ** (-(pl + sigma * z) / 10.0)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# End-to-end jitted schedule: trace -> contacts -> (zeta, tau, h2)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("model", "rounds", "round_duration",
                                   "dt", "comm_range", "shadow_corr_dist",
                                   "carrier_ghz", "drop_truncated"))
def _schedule(model, key, rounds: int, round_duration: float, dt: float,
              comm_range: float, shadow_corr_dist: float,
              carrier_ghz: float, drop_truncated: bool):
    from repro.scenarios.jax_contacts import rounds_from_in_range

    steps = int(rounds * round_duration / dt)
    kt, kc = jax.random.split(key)
    pos, mes = model._positions(kt, steps, dt)
    dist = jnp.linalg.norm(pos - mes[:, None, :], axis=-1)
    zeta, tau = rounds_from_in_range(
        dist < comm_range, dt, rounds, round_duration,
        drop_truncated=drop_truncated)
    # per-round sample index (same non-drifting derivation as the oracle)
    ridx = np.minimum(
        (np.arange(rounds) * (round_duration / dt)).astype(np.int64),
        steps - 1,
    )
    h2 = jax_gains_along_trace(
        kc, pos[ridx], mes[ridx], carrier_ghz=carrier_ghz,
        shadow_corr_dist=shadow_corr_dist)
    return zeta, tau, h2


def jax_schedule_from_model(model, rounds: int, round_duration: float,
                            *, dt: float = 1.0, comm_range: float = 100.0,
                            shadow_corr_dist: float = 25.0,
                            carrier_ghz: float = 3.5,
                            drop_truncated: bool = False, seed=None):
    """(zeta, tau, h2) device arrays from a JAX mobility model, one compile.

    The entire pipeline — PRNG draws, kinematics, in-range test, interval
    extraction, round mapping, channel gains — is a single jitted program:
    no intermediate ever crosses to the host (the acceptance criterion's
    "zero mid-trace host syncs").  ``drop_truncated`` drops contacts still
    open at the trace end instead of censoring them at the window (the
    ``measure_contact_stats`` truncation fix, mirrored on device).
    """
    key = model.key() if seed is None else jax.random.key(seed)
    return _schedule(model, key, int(rounds), float(round_duration),
                     float(dt), float(comm_range), float(shadow_corr_dist),
                     float(carrier_ghz), bool(drop_truncated))

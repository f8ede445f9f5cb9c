#!/usr/bin/env python3
"""Bring-up check: the federated trainer's main path on a TPU chip.

    python chip_smoke.py            # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4  # four chips: the client-sharded step only

One chip, one process (the chip belongs to it), the paper's ResNet-9 at full
width (d_model 64, 6,573,130 params) with N = 20 devices (Table I), batch 32,
random weights and synthetic data from ``SEED``:

  (a) the platform is a TPU; otherwise exit 2 with no result;
  (b) policy ``mads`` runs a few rounds through ``repro.launch.train``'s
      building blocks (``build_device_data`` -> ``DataShard`` ->
      ``run_afl(engine="scan")``) with one eval: uploads > 0, finite eval;
  (c) policy ``mads-joint`` the same way, so the Pallas
      ``sparsify_quantize_ef`` runs compiled; its compiled round holds
      ``tpu_custom_call``; both codec kernels at s match the jnp oracle on
      the chip (upload and count bit-equal, error within 1e-6);
  (d) a few batches of uploads at s through the ingest server in scatter
      mode agree with the parity mode.

``--chips 4`` runs ``core.distributed.make_afl_train_system`` on a (4, 1)
("data", "model") mesh, 5 clients per chip, under ``mads`` and
``mads-joint``, against the one-chip ``afl_round`` on the same inputs: the
same clients upload, bits agree within the sampled threshold's dispersion,
global weights within a small share of the round's update, and the client
state is spread over all four chips (``four_chips`` says why not bit-equal).

Lines before the last are information (times, peak HBM); none is a claim.
The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every check passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "resnet9-cifar10"
NUM_DEVICES = 20  # N, Table I
BATCH = 32
ROUNDS = 5
SEED = 0
TRAIN_N, EVAL_N = 2000, 512
# short inter-contact gaps so that uploads happen within ROUNDS rounds
MEAN_CONTACT, MEAN_INTERCONTACT = 6.0, 30.0
# kernel-vs-oracle check: |x| >= 1 keeps ~32% of a standard normal at b = 8
KERNEL_THRESHOLD, KERNEL_BITS = 1.0, 8
# the error tolerance of tests/test_kernels.py (one FMA rounding)
KERNEL_ERR_ATOL = 1e-6
INGEST_UPLOADS, INGEST_BATCH, INGEST_MAX_K = 32, 8, 65536
# float summation order: scatter vs parity ingest
SUM_ORDER_RTOL, SUM_ORDER_ATOL = 1e-6, 1e-7
FOUR_CHIP_ROUNDS = 3
# four chips vs one: per-client bits within this many standard errors of the
# sampled threshold's count; global weights within this share of the update
COUNT_SIGMAS, UPDATE_SHARE_TOL = 3.0, 0.1


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_hbm(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def federation(width: int = 0, rounds: int = ROUNDS):
    """ResNet-9 federation as ``repro.launch.train`` builds it.

    ``width`` > 0 overrides d_model (the CPU test of ``four_chips``)."""
    from repro.configs import FLConfig, get_config
    from repro.experiments import DataShard
    from repro.launch.train import build_device_data
    from repro.models.registry import build_model

    cfg = get_config(ARCH)
    if width:
        cfg = cfg.replace(d_model=width)
    model = build_model(cfg)
    fl = FLConfig(
        num_devices=NUM_DEVICES, rounds=rounds, batch_size=BATCH,
        mean_contact=MEAN_CONTACT, mean_intercontact=MEAN_INTERCONTACT,
        # what train.py picks above 2M params: strided-sample threshold
        sparsifier="sampled", seed=SEED,
    )
    dev, ev = build_device_data(cfg, fl, train_n=TRAIN_N, eval_n=EVAL_N,
                                seed=SEED)
    return cfg, model, fl, DataShard(dev, fl.batch_size, seed=SEED), ev


def train_phase(fed, policy: str, failures: list):
    """Two identical scan-engine runs: the first compiles, the second is
    steady state.  Returns the second run's result."""
    from repro.core.runner import run_afl

    cfg, model, fl, shard, ev = fed
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = run_afl(model, cfg, fl, policy, shard, ev, rounds=fl.rounds,
                      eval_every=fl.rounds, engine="scan")
        walls.append(time.perf_counter() - t0)
    uploads = res.history["uploads"][-1]
    log(f"[{policy}] rounds={fl.rounds} uploads={uploads:.0f} "
        f"eval={res.final_eval:.6f} k_mean={res.history['k_mean'][-1]:.0f} "
        f"first_call_s={walls[0]:.3f} steady_s={walls[1]:.3f} "
        f"compile_s~{walls[0] - walls[1]:.3f}")
    if not (math.isfinite(uploads) and uploads > 0):
        failures.append(f"{policy}: uploads={uploads}, want finite and > 0")
    if not math.isfinite(res.final_eval):
        failures.append(f"{policy}: eval={res.final_eval} is not finite")
    return res


def codec_round_holds_kernel(fed, state) -> bool:
    """Compile one ``mads-joint`` round and look for the Pallas kernel."""
    import jax.numpy as jnp

    from repro.core import baselines as BL
    from repro.core.afl import afl_round
    from repro.core.runner import sample_budgets

    cfg, model, fl, shard, _ = fed
    policy = BL.ALL["mads-joint"](model.num_params(), fl)
    ones = jnp.ones((fl.num_devices,), jnp.float32)
    t0 = time.perf_counter()
    compiled = afl_round.lower(
        state, shard.traced_batch(shard.seed_key(SEED), 0), ones,
        ones * MEAN_CONTACT, ones, sample_budgets(fl, SEED),
        model=model, cfg=cfg, fl=fl, policy=policy,
    ).compile()
    found = "tpu_custom_call" in compiled.as_text()
    log(f"[mads-joint] afl_round compile_s={time.perf_counter() - t0:.3f} "
        f"tpu_custom_call={found}")
    return found


def kernel_parity(s: int, failures: list) -> None:
    """Both codec kernels at size s against the jnp oracle, on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compression.quant import quant_levels, quant_step
    from repro.kernels import ops

    x = jax.random.normal(jax.random.key(SEED), (s,), jnp.float32)
    t = jnp.float32(KERNEL_THRESHOLD)
    levels = quant_levels(KERNEL_BITS)
    step = quant_step(jnp.max(jnp.abs(x)), levels)
    seed = jnp.int32(1234)
    out = {}
    for which in ("pallas", "ref"):
        out[which] = jax.device_get((
            ops.sparsify_quantize_ef(x, t, step, levels, seed, base=5,
                                     impl=which),
            ops.sparsify_ef(x, t, impl=which),
        ))
    (uq, eq, cq), (uf, ef, cf) = out["pallas"]
    (uqr, eqr, cqr), (ufr, efr, cfr) = out["ref"]
    checks = {
        "sparsify_quantize_ef upload": int(np.sum(uq != uqr)),
        "sparsify_quantize_ef error": int(np.sum(
            np.abs(eq - eqr) > KERNEL_ERR_ATOL)),
        "sparsify_ef upload": int(np.sum(uf != ufr)),
        "sparsify_ef error": int(np.sum(ef != efr)),
    }
    log(f"[kernels] s={s} count_quantize={float(cq):.0f}/{float(cqr):.0f} "
        f"count_sparsify={float(cf):.0f}/{float(cfr):.0f} mismatches="
        + json.dumps(checks))
    for name, bad in checks.items():
        if bad:
            failures.append(f"kernel vs oracle: {name}: {bad} elements differ")
    if float(cq) != float(cqr) or float(cf) != float(cfr):
        failures.append(f"kernel vs oracle: counts {float(cq)}/{float(cqr)}, "
                        f"{float(cf)}/{float(cfr)}")
    if not 0 < float(cq) < s:
        failures.append(f"kernel check selected {float(cq)} of {s}")


def ingest_phase(s: int, failures: list) -> None:
    """Uploads at s through the server in scatter mode vs parity mode."""
    import jax
    import numpy as np

    from repro.launch.soak import run_soak

    runs = {}
    for mode in ("scatter", "parity"):
        runs[mode] = run_soak(uploads=INGEST_UPLOADS, batch=INGEST_BATCH,
                              s=s, max_k=INGEST_MAX_K, codec="topk",
                              mode=mode, baseline=False, seed=SEED)
        res = runs[mode]
        ingested = res["snapshot"]["counters"]["ingested"]
        log(f"[ingest {mode}] uploads={INGEST_UPLOADS} batch={INGEST_BATCH} "
            f"max_k={INGEST_MAX_K} ingested={ingested:.0f} "
            f"wall_s={res['fused_wall_s']:.3f}")
        if ingested != INGEST_UPLOADS:
            failures.append(f"ingest {mode}: ingested {ingested} of "
                            f"{INGEST_UPLOADS}")
    got = jax.device_get(runs["scatter"]["server"].w)
    want = jax.device_get(runs["parity"]["server"].w)
    moved = 0.0
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        moved += float(np.sum(np.abs(b)))
        if not (np.all(np.isfinite(a)) and np.allclose(
                a, b, rtol=SUM_ORDER_RTOL, atol=SUM_ORDER_ATOL)):
            failures.append(f"ingest: scatter vs parity differ on {name}: "
                            f"max |diff| {float(np.max(np.abs(a - b)))}")
    if moved == 0.0:
        failures.append("ingest: the uploads left the model unchanged")


def one_chip(failures: list) -> None:
    import jax

    device = jax.devices()[0]
    fed = federation()
    s = fed[1].num_params()
    log(f"arch={ARCH} d_model={fed[0].d_model} params={s} "
        f"devices={fed[2].num_devices} batch={BATCH} rounds={ROUNDS}")
    train_phase(fed, "mads", failures)
    log(f"peak_hbm after mads: {peak_hbm(device)}")
    res = train_phase(fed, "mads-joint", failures)
    if not codec_round_holds_kernel(fed, res.state):
        failures.append("mads-joint round holds no tpu_custom_call: the "
                        "codec ran the jnp oracle, not the Pallas kernel")
    kernel_parity(s, failures)
    log(f"peak_hbm after mads-joint: {peak_hbm(device)}")
    ingest_phase(s, failures)
    log(f"peak_hbm after ingest: {peak_hbm(device)}")


def four_chips(devices, failures: list, width: int = 0) -> None:
    """The client-sharded AFL step on a (4, 1) mesh vs one-chip afl_round.

    Each round both paths start from the same one-chip state, so the check
    is the sharded step itself and not drift compounded over rounds.

    On the chip the two paths are not bit-equal: each compiles the client
    gradients for 5 or for 20 clients per program, which reduces in another
    order, and the sampled threshold turns last-bit gradient differences
    into a different realised count.  So both run at full f32 matmul
    precision (the one-chip phases run the default), the same clients must
    upload, each client's bits must agree within the sampled threshold's
    own dispersion, and the global weights within a small share of the
    round's update.  A sharding fault (a client on the wrong data, a shard
    left out of the sum) moves the update by a large share of itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import baselines as BL
    from repro.core.afl import afl_init, afl_round
    from repro.core.distributed import (
        DistAflState, DistConfig, client_state_shardings,
        make_afl_train_system,
    )
    from repro.core.runner import build_provider, sample_budgets

    def norm(tree):
        return math.sqrt(sum(float(np.sum(np.square(np.asarray(v))))
                             for v in jax.tree.leaves(tree)))

    mesh = Mesh(np.asarray(devices).reshape(4, 1), ("data", "model"))
    cfg, model, fl, shard, _ = federation(width=width,
                                          rounds=FOUR_CHIP_ROUNDS)
    s = model.num_params()
    log(f"arch={ARCH} d_model={cfg.d_model} params={s} "
        f"clients={fl.num_devices} ({fl.num_devices // 4} per chip) "
        f"rounds={fl.rounds}")
    budgets = sample_budgets(fl, SEED)
    key = shard.seed_key(SEED)
    batch_sh = NamedSharding(mesh, P("data"))
    for name in ("mads", "mads-joint"):
        policy = BL.ALL[name](s, fl)
        dcfg = DistConfig(
            num_clients=fl.num_devices, learning_rate=fl.learning_rate,
            rounds=fl.rounds, sample_size=fl.sample_size,
            state_dtype="float32", upload_dtype="float32",
        )
        system = make_afl_train_system(
            model, cfg, mesh, dcfg, controller=policy.controller,
            compressor=policy.compressor)
        step = jax.jit(system["step"])
        provider = build_provider(fl, name, None, fl.rounds, SEED)
        state = afl_init(model, cfg, fl, jax.random.key(SEED))
        shipped = 0.0
        t0 = time.perf_counter()
        for r in range(fl.rounds):
            batch = shard.traced_batch(key, r)
            zeta, tau, h2 = (jnp.asarray(v, jnp.float32)
                             for v in provider.round(r))
            dist_in = DistAflState(*state)
            dist_in = jax.device_put(
                dist_in, client_state_shardings(dist_in, mesh))
            flat = jax.tree.map(
                lambda v: jax.device_put(v.reshape((-1,) + v.shape[2:]),
                                         batch_sh), batch)
            w_prev = state.w
            with jax.default_matmul_precision("highest"):
                dist_out, md = step(dist_in, flat, zeta, tau, h2, budgets)
                state, ms = afl_round(state, batch, zeta, tau, h2, budgets,
                                      model=model, cfg=cfg, fl=fl,
                                      policy=policy)
            bits_d, bits_s = np.asarray(md["bits"]), np.asarray(ms["bits"])
            k_s = np.asarray(ms["k"])
            up = k_s > 0
            # the sampled threshold's count error, std ~ sqrt(k s / m)
            # (core/README.md), relative to k
            bits_tol = (COUNT_SIGMAS * np.sqrt(s / (fl.sample_size
                                                    * np.maximum(k_s, 1)))
                        * bits_s)
            bits_rel = float(np.max(np.abs(bits_d - bits_s)[up]
                                    / bits_s[up])) if up.any() else 0.0
            w_share = (norm(jax.tree.map(jnp.subtract, dist_out.w, state.w))
                       / max(norm(jax.tree.map(jnp.subtract, state.w,
                                               w_prev)), 1e-30))
            spread = {len(l.sharding.device_set)
                      for l in jax.tree.leaves(dist_out.w_n)}
            shipped += float(bits_s.sum())
            log(f"[4 chips {name}] r={r} uploads={float(ms['success'].sum()):.0f}"
                f" bits_equal={np.array_equal(bits_d, bits_s)} "
                f"max_bits_rel_diff={bits_rel:.3e} "
                f"|w diff|/|update|={w_share:.3e} "
                f"w_n on {sorted(spread)} devices")
            if not np.array_equal(np.asarray(md["success"]),
                                  np.asarray(ms["success"])):
                failures.append(f"4 chips {name} r={r}: uploading clients "
                                f"differ")
            if np.any(np.abs(bits_d - bits_s) > bits_tol):
                failures.append(f"4 chips {name} r={r}: bits differ beyond "
                                f"{COUNT_SIGMAS} sampled-count std: "
                                f"{bits_d.tolist()} vs {bits_s.tolist()}")
            if not w_share <= UPDATE_SHARE_TOL:
                failures.append(f"4 chips {name} r={r}: global weights differ "
                                f"by {w_share:.3e} of the round's update")
            if spread != {4}:
                failures.append(f"4 chips {name} r={r}: client state on "
                                f"{sorted(spread)} devices, want 4")
        log(f"[4 chips {name}] rounds_wall_s={time.perf_counter() - t0:.3f} "
            f"(compiles included)")
        if shipped <= 0:
            failures.append(f"4 chips {name}: nothing was uploaded, so the "
                            f"comparison is vacuous")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(d); 4: only the client-sharded "
                         "step against one-chip afl_round")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    log(f"device_kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    failures: list = []
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devices[:4], failures)
    else:
        one_chip(failures)
    log(f"total_wall_s={time.perf_counter() - t0:.3f}")
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

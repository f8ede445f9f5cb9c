"""A federation cell: simulated AFL rounds through the scan engine.

Set-up builds ONE object, the jitted segment program of
``repro.experiments.scan_engine.make_run_fn`` (``segment_rounds`` rounds of
``core.afl.afl_round`` in one ``lax.scan``, the eval at the segment's end),
with its state from ``core.afl.afl_init`` and the harness's weights.  It
drives that object through the first ``checked_segments`` segments, which
compile it and give the readings that decide ``correct``, then hands the
same object and state to the measured window.  Every segment gets the next
rows of the schedule (``schedule_rounds`` rows made from the seed, cycled)
and a fresh minibatch key; the state carries over.

After the window, with the peak memory read and the program's state freed,
the plain reference (``algorithm1.py``) follows the checked segments from
the same inputs and the readings are compared (``compare.py``).
"""
from __future__ import annotations

import gc
import importlib
import math
import os
import sys
import time

import numpy as np

from bench.harness import compare
from bench.harness.spans import Spans, lowered, seed32
from bench.harness.trace import WINDOW, profile, summarize

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the profiler's output, inside the checkout and listed in .gitignore
TRACE_DIR = os.path.join(os.path.dirname(BENCH), ".bench_trace")


def model_module(config: dict):
    """The configuration's plain reference, ``bench/configs/<model>.py``."""
    return importlib.import_module("bench.configs." + config["model"])


def consts(config: dict, traffic: dict, fl, s: int) -> dict:
    """What the reference needs to know of the run, from the configuration,
    the traffic and the federation's Table I parameters."""
    index_bits = int(math.ceil(math.log2(max(s, 2))))
    return {
        "policy": traffic["policy"], "s": s, "batch": traffic["batch_size"],
        "eta": fl.learning_rate, "rounds": fl.rounds,
        "bandwidth": fl.bandwidth, "n0": 10 ** (fl.noise_dbm_hz / 10) / 1000,
        "p_max": fl.max_power, "v": fl.lyapunov_v,
        "wire_bits": float(fl.value_bits + index_bits),
        "index_bits": index_bits, "method": fl.sparsifier,
        "sample": fl.sample_size,
        "b_grid": tuple(range(fl.compress_b_min, fl.compress_b_max + 1)),
    }


def build(config: dict, traffic: dict, seed: int, *, fault=None):
    """Set-up: data, schedule, weights, the program's state and its jitted
    segment program.  ``fault(model, policy) -> (model, policy, post)``
    plants a fault under the timed path, for the harness's own checks;
    ``post(old, new)`` then gives the state a segment hands on."""
    import jax
    import jax.numpy as jnp

    from repro.configs import FLConfig, get_config
    from repro.core import baselines as BL
    from repro.core.afl import afl_init
    from repro.core.runner import build_provider, sample_budgets
    from repro.experiments import DataShard
    from repro.experiments.scan_engine import make_run_fn
    from repro.launch.train import build_device_data
    from repro.models.registry import build_model

    s32 = seed32(seed)
    n, seg = traffic["num_devices"], traffic["segment_rounds"]
    cfg = get_config(config["arch"]).replace(**config["model_config"])
    model = build_model(cfg)
    s = model.num_params()
    if s != config["params"]:
        raise ValueError(f"{config['name']}: the program builds {s} "
                         f"parameters, the configuration states "
                         f"{config['params']}")
    fl = FLConfig(num_devices=n, rounds=traffic["schedule_rounds"],
                  batch_size=traffic["batch_size"],
                  sparsifier=traffic["sparsifier"], seed=s32,
                  **traffic.get("fl", {}))
    if fl.rounds % seg:
        raise ValueError("schedule_rounds must be a multiple of "
                         "segment_rounds")
    dev, ev = build_device_data(
        cfg, fl, train_n=n * traffic["samples_per_client"],
        eval_n=traffic["eval_samples"], seed=s32)
    policy = BL.ALL[traffic["policy"]](s, fl)
    post = None
    if fault is not None:
        model, policy, post = fault(model, policy)
    zeta, tau, h2 = build_provider(fl, traffic["policy"], None, fl.rounds,
                                   s32).schedule()
    schedule = (np.asarray(zeta), np.asarray(tau, np.float32),
                np.asarray(h2, np.float32))
    budgets = sample_budgets(fl, s32)
    shard = DataShard(dev, fl.batch_size, seed=s32)

    ref = model_module(config)
    key = jax.random.key(s32)
    w0 = jax.jit(lambda k: ref.init(k, config))(
        jax.random.fold_in(key, 1))
    ckey0 = jax.random.fold_in(key, 2)
    state = afl_init(model, cfg, fl, key)
    if (jax.tree.structure(state.w) != jax.tree.structure(w0) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(state.w),
                                                jax.tree.leaves(w0)))):
        raise ValueError("the reference's parameters do not match the "
                         "program's")
    state = state._replace(
        w=w0, ckey=ckey0,
        w_n=jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), w0))
    run = jax.jit(make_run_fn(model, cfg, fl, policy, rounds=seg,
                              eval_every=seg, sampler=shard.traced_batch))
    rows = [tuple(jnp.asarray(a[i:i + seg]) for a in schedule)
            for i in range(0, fl.rounds, seg)]
    batch_key = jax.random.fold_in(key, 3)
    eval_batch = jax.device_put({k: jnp.asarray(v) for k, v in ev.items()})
    return {
        "fl": fl, "s": s, "n": n, "seg": seg, "model": model, "ref": ref,
        "run": run, "state": state, "w0": w0, "ckey0": ckey0,
        "rows": rows, "budgets": budgets, "eval": eval_batch,
        "batch_key": batch_key, "dev": dev, "schedule": schedule,
        "shard": shard, "policy": policy, "cfg": cfg,
        "counts": np.array([len(d[next(iter(d))]) for d in dev], np.int32),
        "post": post,
        "flops_per_round": n * fl.batch_size * ref.flops_per_sample(config),
    }


def segment(b: dict, i: int, spans: Spans):
    """Run segment ``i`` of the cell on the carried state; returns its
    history, fetched (the eval and counters of the segment)."""
    import jax

    with spans.span("feed"):
        zeta, tau, h2 = b["rows"][i % len(b["rows"])]
        key = jax.random.fold_in(b["batch_key"], i)
    with spans.span("dispatch"):
        state, hist, _ = b["run"](b["state"], zeta, tau, h2, b["budgets"],
                                  b["eval"], key, {}, {})
    if b["post"] is not None:
        state = b["post"](b["state"], state)
    b["state"] = state
    with spans.span("fetch"):
        return jax.device_get(hist)


def finite(hist: dict) -> bool:
    return all(np.all(np.isfinite(np.asarray(v))) for v in hist.values())


def program_readings(b: dict, hist: dict, i: int, out: dict) -> None:
    """Record the readings of the program after segment ``i``."""
    import jax
    import jax.numpy as jnp

    out.setdefault("count", []).append(
        float(hist["k_mean"][-1]) * max(float(hist["uploads"][-1]), 1.0))
    st = b["state"]
    if i == 0:
        out["grad1"] = norms(st.g_n)
    out["dw"] = norms(jax.tree.map(jnp.subtract, st.w, b["w0"]))
    out["dwn"] = norms(jax.tree.map(lambda a, w: a - w[None], st.w_n,
                                    b["w0"]))
    out["err"] = norms(st.e_n)
    out["gsum"] = norms(st.g_n)
    out["kappa"] = np.asarray(st.kappa).tolist()
    out["energy"] = float(np.sum(np.asarray(st.energy, np.float64)))


def norms(tree) -> dict:
    """Per-leaf norms (over every client where the leaves are stacked)."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32)))) for _, l in flat])
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(flat, vals)}


def reference_readings(b: dict, config: dict, traffic: dict, dtype=None):
    """The plain reference over the checked segments (float32 at highest
    matmul precision unless ``dtype`` says otherwise)."""
    import jax
    import jax.numpy as jnp

    from bench.harness import algorithm1

    dtype = dtype or jnp.float32
    loss = lambda p, batch: b["ref"].loss(p, batch, config)
    steps = traffic["checked_segments"]
    keys = [jax.random.fold_in(b["batch_key"], i) for i in range(steps)]
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return algorithm1.run(
            consts(config, traffic, b["fl"], b["s"]), loss, b["w0"], b["dev"],
            b["counts"], keys, b["ckey0"], b["schedule"], b["budgets"],
            steps=steps, segment=b["seg"], dtype=dtype)


def checked(config: dict, traffic: dict, seed: int, spans: Spans, *,
            fault=None):
    """Set-up's drive through the checked segments: ``(b, readings,
    failed rounds)``."""
    b = build(config, traffic, seed, fault=fault)
    got, failed = {}, 0
    for i in range(traffic["checked_segments"]):
        hist = segment(b, i, spans)
        failed += 0 if finite(hist) else b["seg"]
        program_readings(b, hist, i, got)
    return b, got, failed


def free_program(b: dict) -> None:
    """Drop the program's state and compiled segment before the reference
    runs, so that the reference neither sets the peak nor runs short."""
    for k in ("state", "run", "rows", "eval", "model", "shard"):
        b.pop(k, None)
    gc.collect()


def run(cell: dict, *, seed: int, seconds: float, trace: bool, start: float,
        devices, fault=None) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.harness.cli import device_info

    config, traffic = cell["config"], cell["traffic"]
    spans = Spans(annotate=trace)
    b, got, setup_failed = checked(config, traffic, seed, spans, fault=fault)
    i, failed = traffic["checked_segments"], 0
    out = {"end_to_end": {}, "trace": None,
           "reports": ["rounds_per_s", "setup_s"]}

    def window(until):
        nonlocal i, failed
        done, t0, t_end, n0 = 0, time.perf_counter(), None, lowered()
        with spans.span(WINDOW):
            while not until(done, time.perf_counter() - t0):
                hist = segment(b, i, spans)
                t_end = time.perf_counter()
                failed += 0 if finite(hist) else b["seg"]
                done += b["seg"]
                i += 1
        print(f"programs lowered in the window: {lowered() - n0}",
              file=sys.stderr, flush=True)
        return t0, done, t_end - t0

    if trace:
        nseg = traffic["trace_segments"]
        with profile(TRACE_DIR):
            t0, done, span = window(lambda d, _: d >= nseg * b["seg"])
        summary = summarize(TRACE_DIR)
        summary.update(rounds=done, flops_per_round=b["flops_per_round"],
                       peaks=_peaks(devices),
                       kernel_elements_per_round=b["n"] * b["s"])
        out["trace"] = summary
    else:
        t0, done, span = window(lambda _, t: t >= seconds)
        out["end_to_end"] = {"rounds_per_s": done / span,
                             "setup_s": t0 - start}
    device = device_info(devices)
    if not all(jax.device_get([jnp.all(jnp.isfinite(l)) for l in
                               jax.tree.leaves(b["state"][:7])])):
        failed = done
    free_program(b)
    want = reference_readings(b, config, traffic)
    checks = compare.federation_checks(got, want, cell["limits"])
    out.update(correct=(failed == 0 and setup_failed == 0
                        and all(v <= lim for _, v, lim in checks)),
               attempted=done, failed=failed, device=device, checks=checks)
    return out


def _peaks(devices) -> dict:
    from bench.harness.costs import peaks

    return peaks(devices[0].device_kind)

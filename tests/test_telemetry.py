"""Telemetry subsystem: registry algebra, engine parity, exporters.

The load-bearing contract is bit-identity: histogram bin counts are sums
of 0/1 weights (exact integers in f32, reduction-order independent), so
the loop runner, the scan engine, and the pjit distributed step must emit
*bit-identical* histograms for the same seeded run — pinned here with
``assert_array_equal``, not allclose.  The slow test re-checks the vmapped
seed axis sharded over a mesh of 2 simulated host devices.
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, get_config
from repro.core import baselines as BL
from repro.core.distributed import DistConfig, init_state, make_afl_train_step, run_afl_rounds
from repro.core.runner import build_provider, resolve_telemetry, run_afl, sample_budgets
from repro.experiments import DataShard, run_afl_scanned, run_seed_batch
from repro.launch.train import build_device_data
from repro.models.registry import build_model
from repro.telemetry import (
    AFL_REGISTRY,
    HIST_KEYS,
    Counter,
    DeviceTable,
    Gauge,
    Histogram,
    JsonlSink,
    MetricRegistry,
    PhaseTracer,
    TelemetrySuite,
    TheoryProbes,
    export_bench,
    load_bench,
    merge_fetched,
    parse_csv_row,
    participation_gini,
    read_jsonl,
    render_report,
    report_from_config,
    to_jsonable,
    top_stragglers,
)

ROUNDS, EVERY = 8, 4
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


def _assert_snapshots_equal(a: dict, b: dict, err=""):
    """Hists + integral counters exactly equal; float totals to 1e-6."""
    for k in a["hist"]:
        np.testing.assert_array_equal(a["hist"][k], b["hist"][k],
                                      err_msg=f"{err} hist {k!r}")
    for k in ("rounds", "contacts", "successes"):
        assert a["counters"][k] == b["counters"][k], (err, k)
    for k in ("bits_total", "energy_total"):
        np.testing.assert_allclose(a["counters"][k], b["counters"][k],
                                   rtol=1e-6, err_msg=f"{err} {k}")
    assert a["gauges"] == b["gauges"], err


# count-like (N,) fields: exact-integer f32 updates, bit-identical across
# engines; float accumulators agree to rounding; e_norm2 is a param-dim
# reduction whose summation order differs between compiled programs, so it
# only gets an absolute tolerance (values near denormal scale here)
_TABLE_EXACT = ("rounds", "contacts", "successes", "failures",
                "last_contact", "staleness_sum", "staleness_max")
_TABLE_CLOSE = ("tau_sum", "bits_sum", "energy_sum")


def _assert_tables_equal(a: dict, b: dict, err=""):
    for k in _TABLE_EXACT:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{err} table {k}")
    for k in _TABLE_CLOSE:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6,
                                   err_msg=f"{err} table {k}")
    np.testing.assert_allclose(a["e_norm2"], b["e_norm2"], rtol=0.5,
                               atol=1e-9, err_msg=f"{err} table e_norm2")


def _assert_probes_equal(a: dict, b: dict, err=""):
    for k in ("rounds", "contacts", "successes"):
        assert a[k] == b[k], (err, k)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-9,
                                   err_msg=f"{err} probe {k}")


def _assert_suites_equal(a: dict, b: dict, err=""):
    _assert_snapshots_equal(a["metrics"], b["metrics"], err)
    _assert_tables_equal(a["device"], b["device"], err)
    _assert_probes_equal(a["probes"], b["probes"], err)


def _suite_for(model, fl):
    return TelemetrySuite(
        metrics=AFL_REGISTRY, device=DeviceTable(fl.num_devices),
        probes=TheoryProbes(s=model.num_params(), u=fl.value_bits),
    )


# ---------------------------------------------------------------------------
# registry algebra (host-only, fast)
# ---------------------------------------------------------------------------


def test_hist_keys_single_source():
    """core.runner re-exports the telemetry module's HIST_KEYS object."""
    from repro.core.runner import HIST_KEYS as runner_keys
    from repro.experiments.scan_engine import HIST_KEYS as scan_keys

    assert runner_keys is HIST_KEYS
    assert scan_keys is HIST_KEYS


def test_engines_emit_same_history_keys(federation):
    cfg, model, fl, shard, ev = federation
    loop = run_afl(model, cfg, fl, "mads", shard, ev, rounds=2, eval_every=2)
    scan = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=2,
                           eval_every=2)
    assert set(loop.history) == set(HIST_KEYS)
    assert set(scan.history) == set(HIST_KEYS)


def test_histogram_bins_underflow_interior_overflow():
    reg = MetricRegistry(
        counters=(Counter("n"),), gauges=(Gauge("r"),),
        histograms=(Histogram("h", edges=(1.0, 2.0, 4.0)),),
    )
    s = reg.init_state()
    # 0.5 -> underflow; 1.0, 1.5 -> [1,2); 3.0 -> [2,4); 4.0, 9.0 -> overflow
    vals = jnp.asarray([0.5, 1.0, 1.5, 3.0, 4.0, 9.0])
    s = reg.update(s, counters={"n": 6.0}, gauges={"r": 1.0},
                   hists={"h": (vals, jnp.ones_like(vals))})
    np.testing.assert_array_equal(np.asarray(s["hist"]["h"]),
                                  [1.0, 2.0, 1.0, 2.0])
    assert float(s["counters"]["n"]) == 6.0
    assert float(s["gauges"]["r"]) == 1.0
    # masked weights drop samples without perturbing the others
    s = reg.update(s, hists={"h": (vals, jnp.asarray([0., 1., 0., 1., 0., 1.]))})
    np.testing.assert_array_equal(np.asarray(s["hist"]["h"]),
                                  [1.0, 3.0, 2.0, 3.0])
    with pytest.raises(KeyError):
        reg.update(s, hists={"nope": (vals, vals)})


def test_merge_associative_and_stacked():
    reg = AFL_REGISTRY
    rng = np.random.default_rng(0)
    states = []
    for i in range(3):
        s = reg.init_state()
        m = {
            "uploads": jnp.asarray(rng.integers(0, 2, 4), jnp.float32),
            "success": jnp.asarray(rng.integers(0, 2, 4), jnp.float32),
            "theta": jnp.asarray(rng.uniform(1, 100, 4), jnp.float32),
            "bits": jnp.asarray(rng.uniform(1e3, 1e8, 4), jnp.float32),
            "k": jnp.asarray(rng.uniform(1, 1e6, 4), jnp.float32),
            "b": jnp.asarray(rng.uniform(1, 32, 4), jnp.float32),
            "energy": jnp.asarray(rng.uniform(0, 1, 4), jnp.float32),
        }
        from repro.telemetry import record_round

        states.append(record_round(reg, s, m, jnp.asarray([1., 3., 9., 80.])))
    a, b, c = states
    left = reg.fetch(reg.merge(reg.merge(a, b), c))
    right = reg.fetch(reg.merge(a, reg.merge(b, c)))
    _assert_snapshots_equal(left, right, "associativity")
    # merge_stacked == the pairwise fold
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), a, b, c)
    _assert_snapshots_equal(reg.fetch(reg.merge_stacked(stacked)), left,
                            "stacked")
    # numpy mirror of merge agrees with the device merge
    _assert_snapshots_equal(
        merge_fetched([reg.fetch(a), reg.fetch(b), reg.fetch(c)]), left,
        "merge_fetched")


# ---------------------------------------------------------------------------
# engine parity: loop vs scan vs pjit step, bit-identical histograms
# ---------------------------------------------------------------------------


def test_loop_scan_parity_bit_identical(federation):
    """Same seeded mads run through both engines: identical snapshots."""
    cfg, model, fl, shard, ev = federation
    loop = run_afl(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                   eval_every=EVERY, seed=3, telemetry=AFL_REGISTRY)
    scan = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                           eval_every=EVERY, seed=3, telemetry=AFL_REGISTRY)
    assert loop.telemetry is not None and scan.telemetry is not None
    _assert_snapshots_equal(loop.telemetry, scan.telemetry, "loop-vs-scan")
    assert loop.telemetry["counters"]["rounds"] == ROUNDS
    # something was actually observed
    assert loop.telemetry["counters"]["contacts"] > 0
    assert sum(loop.telemetry["hist"]["staleness"]) == \
        loop.telemetry["counters"]["contacts"]


def test_dist_step_telemetry_matches_loop(federation):
    """The pjit step's in-program record_round equals the loop engine's."""
    cfg, model, fl, shard, ev = federation
    policy = BL.ALL["mads"](model.num_params(), fl)
    dcfg = DistConfig(
        num_clients=fl.num_devices, learning_rate=fl.learning_rate,
        rounds=fl.rounds, state_dtype="float32", upload_dtype="float32",
    )
    step = jax.jit(make_afl_train_step(model, cfg, dcfg, policy.controller,
                                       telemetry=AFL_REGISTRY))
    provider = build_provider(fl, "mads", None, ROUNDS, 0)
    budgets = sample_budgets(fl, 0)
    key = shard.seed_key(0)
    flat = lambda b: jax.tree.map(
        lambda v: v.reshape((-1,) + v.shape[2:]), b)
    _, hist, tstate = run_afl_rounds(
        step, init_state(model, dcfg, jax.random.key(0)), provider,
        lambda r: flat(shard.traced_batch(key, r)), budgets,
        rounds=ROUNDS, telemetry=AFL_REGISTRY,
    )
    assert len(hist) == ROUNDS
    loop = run_afl(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                   eval_every=EVERY, seed=0, telemetry=AFL_REGISTRY)
    _assert_snapshots_equal(AFL_REGISTRY.fetch(tstate), loop.telemetry,
                            "dist-vs-loop")


def test_seed_vmap_telemetry_matches_independent(federation):
    """Vmapped seeds carry per-seed states; each slice equals the
    independent scanned run, and merging recovers the totals."""
    cfg, model, fl, shard, ev = federation
    batch = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                           rounds=ROUNDS, eval_every=EVERY,
                           telemetry=AFL_REGISTRY)
    snaps = [r.telemetry for r in batch]
    assert all(s is not None for s in snaps)
    for seed, snap in zip((0, 1), snaps):
        ind = run_afl_scanned(model, cfg, fl, "mads", shard, ev,
                              rounds=ROUNDS, eval_every=EVERY, seed=seed,
                              telemetry=AFL_REGISTRY)
        _assert_snapshots_equal(snap, ind.telemetry, f"vmap seed {seed}")
    merged = merge_fetched(snaps)
    assert merged["counters"]["rounds"] == 2 * ROUNDS
    np.testing.assert_array_equal(
        merged["hist"]["staleness"],
        np.asarray(snaps[0]["hist"]["staleness"], np.float64)
        + np.asarray(snaps[1]["hist"]["staleness"], np.float64))


def test_fl_config_knob_and_resolution(federation):
    """fl.telemetry=True turns on the built-in registry; off -> None."""
    import dataclasses

    cfg, model, fl, shard, ev = federation
    assert resolve_telemetry(fl, None) is None
    assert resolve_telemetry(fl, AFL_REGISTRY) is AFL_REGISTRY
    fl_on = dataclasses.replace(fl, telemetry=True)
    assert resolve_telemetry(fl_on, None) is AFL_REGISTRY
    res = run_afl_scanned(model, cfg, fl_on, "mads", shard, ev,
                          rounds=ROUNDS, eval_every=EVERY, seed=3)
    assert res.telemetry is not None
    off = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=2,
                          eval_every=2)
    assert off.telemetry is None


# ---------------------------------------------------------------------------
# TelemetrySuite: flight recorder + probes through every engine
# ---------------------------------------------------------------------------


def test_resolve_telemetry_suite_knobs(federation):
    """FLConfig suite knobs build an equivalent (hashable) suite each call
    — one jit-cache key — and probes require a model size."""
    import dataclasses

    cfg, model, fl, shard, ev = federation
    s = model.num_params()
    fl_suite = dataclasses.replace(fl, telemetry_perdevice=True,
                                   telemetry_probes=True)
    t1 = resolve_telemetry(fl_suite, None, s=s)
    t2 = resolve_telemetry(fl_suite, None, s=s)
    assert isinstance(t1, TelemetrySuite)
    assert t1 == t2 and hash(t1) == hash(t2)
    assert t1.device.n == fl.num_devices
    assert t1.probes.s == s
    # s=0 (unknown model size): probes silently drop, table stays
    t3 = resolve_telemetry(fl_suite, None, s=0)
    assert t3.device is not None and t3.probes is None
    # an explicit registry still wins over the knobs
    assert resolve_telemetry(fl_suite, AFL_REGISTRY, s=s) is AFL_REGISTRY
    # device-only knob: no probes section in the snapshot
    fl_dev = dataclasses.replace(fl, telemetry_perdevice=True)
    t4 = resolve_telemetry(fl_dev, None, s=s)
    snap = t4.fetch(t4.init_state())
    assert snap["device"] is not None and snap.get("probes") is None


def test_suite_loop_scan_parity(federation):
    """Same seeded run, suite carried through both engines: per-device
    count fields bit-identical, probe accumulators equal."""
    cfg, model, fl, shard, ev = federation
    suite = _suite_for(model, fl)
    loop = run_afl(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                   eval_every=EVERY, seed=3, telemetry=suite)
    scan = run_afl_scanned(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                           eval_every=EVERY, seed=3, telemetry=suite)
    _assert_suites_equal(loop.telemetry, scan.telemetry, "suite loop-vs-scan")
    dev = loop.telemetry["device"]
    assert dev["rounds"] == ROUNDS
    # table totals reconcile with the registry's federation-wide counters
    c = loop.telemetry["metrics"]["counters"]
    assert float(dev["contacts"].sum()) == c["contacts"]
    assert float(dev["successes"].sum()) == c["successes"]
    np.testing.assert_allclose(float(dev["bits_sum"].sum()),
                               c["bits_total"], rtol=1e-5)
    # and with the probe accumulators
    p = loop.telemetry["probes"]
    assert p["rounds"] == ROUNDS
    assert p["contacts"] == c["contacts"]
    assert p["successes"] == c["successes"]


def test_suite_dist_step_matches_loop(federation):
    """The pjit step's in-program suite recording equals the loop's."""
    cfg, model, fl, shard, ev = federation
    from repro.core.distributed import telemetry_shardings

    suite = _suite_for(model, fl)
    policy = BL.ALL["mads"](model.num_params(), fl)
    dcfg = DistConfig(
        num_clients=fl.num_devices, learning_rate=fl.learning_rate,
        rounds=fl.rounds, state_dtype="float32", upload_dtype="float32",
    )
    step = jax.jit(make_afl_train_step(model, cfg, dcfg, policy.controller,
                                       telemetry=suite))
    provider = build_provider(fl, "mads", None, ROUNDS, 0)
    budgets = sample_budgets(fl, 0)
    key = shard.seed_key(0)
    flat = lambda b: jax.tree.map(
        lambda v: v.reshape((-1,) + v.shape[2:]), b)
    _, hist, tstate = run_afl_rounds(
        step, init_state(model, dcfg, jax.random.key(0)), provider,
        lambda r: flat(shard.traced_batch(key, r)), budgets,
        rounds=ROUNDS, telemetry=suite,
    )
    loop = run_afl(model, cfg, fl, "mads", shard, ev, rounds=ROUNDS,
                   eval_every=EVERY, seed=0, telemetry=suite)
    _assert_suites_equal(suite.fetch(tstate), loop.telemetry,
                         "suite dist-vs-loop")
    # sharding spec: (N,) table rows on the client axis, all else replicated
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sh = telemetry_shardings(suite, mesh)
    assert set(sh) == {"metrics", "device", "probes"}
    assert all(s.spec == jax.sharding.PartitionSpec("data")
               for f, s in sh["device"].items() if f != "rounds")
    assert sh["device"]["rounds"].spec == jax.sharding.PartitionSpec()


def test_suite_seed_vmap_slices(federation):
    """Vmapped seeds: each per-seed suite slice equals the independent
    scanned run; merging recovers federation totals per FIELD_KIND."""
    cfg, model, fl, shard, ev = federation
    suite = _suite_for(model, fl)
    batch = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                           rounds=ROUNDS, eval_every=EVERY, telemetry=suite)
    snaps = [r.telemetry for r in batch]
    assert all(s is not None for s in snaps)
    for seed, snap in zip((0, 1), snaps):
        ind = run_afl_scanned(model, cfg, fl, "mads", shard, ev,
                              rounds=ROUNDS, eval_every=EVERY, seed=seed,
                              telemetry=suite)
        _assert_suites_equal(snap, ind.telemetry, f"suite vmap seed {seed}")
    merged = merge_fetched(snaps)
    dev = merged["device"]
    assert dev["rounds"] == 2 * ROUNDS  # sum across seeds
    np.testing.assert_array_equal(
        dev["contacts"],
        np.asarray(snaps[0]["device"]["contacts"])
        + np.asarray(snaps[1]["device"]["contacts"]))
    np.testing.assert_array_equal(  # max-kind field merges as max
        dev["staleness_max"],
        np.maximum(snaps[0]["device"]["staleness_max"],
                   snaps[1]["device"]["staleness_max"]))
    assert merged["probes"]["rounds"] == 2 * ROUNDS
    # the merged snapshot survives the JSONL sink round-trip
    rec = json.loads(json.dumps(to_jsonable(merged)))
    assert len(rec["device"]["contacts"]) == fl.num_devices
    assert rec["probes"]["contacts"] == merged["probes"]["contacts"]


def test_straggler_extraction_and_gini():
    """Host-side row extraction orders starved devices first."""
    table = DeviceTable(4)
    snap = {
        "contacts": np.asarray([9., 0., 4., 2.]),
        "successes": np.asarray([8., 0., 2., 1.]),
        "failures": np.asarray([1., 0., 2., 1.]),
        "last_contact": np.asarray([10., 0., 6., 9.]),
        "staleness_sum": np.asarray([9., 0., 40., 4.]),
        "staleness_max": np.asarray([2., 0., 30., 3.]),
        "tau_sum": np.asarray([18., 0., 8., 4.]),
        "bits_sum": np.asarray([9e6, 0., 4e6, 2e6]),
        "energy_sum": np.asarray([90., 0., 40., 20.]),
        "e_norm2": np.asarray([1e-3, 0., 2e-3, 5e-4]),
        "rounds": 10.0,
    }
    worst = top_stragglers(snap, k=2)
    assert [r["device"] for r in worst] == [1, 3]
    assert worst[0]["contacts"] == 0.0 and worst[0]["success_rate"] == 0.0
    assert worst[1]["staleness_mean"] == pytest.approx(2.0)
    gini = participation_gini(snap)
    assert 0.0 < gini < 1.0
    uniform = dict(snap, contacts=np.full(4, 5.0))
    assert participation_gini(uniform) == pytest.approx(0.0, abs=1e-9)
    # summary renders without touching devices
    assert "stale_mean" in table.summary(snap)


def test_probes_calibrated_synthetic():
    """Drive the probes with a synthetic run matching the theory's
    generative model (tau ~ Exp(c), Proposition-1 spend): measured terms
    land on the closed forms."""
    from repro.core import theory

    s, u, c, lam, delta, rate = 4096, 16, 6.0, 30.0, 10.0, 50.0
    n_dev, n_rounds = 64, 400
    probes = TheoryProbes(s=s, u=u)
    state = probes.init_state()
    rng = np.random.default_rng(7)
    since = np.zeros(n_dev)  # rounds since last successful upload
    bitcost = u + np.log2(s)
    # Lemma 2 counts staleness in rounds of length delta; a round overlaps
    # a contact with probability 1 - exp(-delta/lam) under the renewal model
    p_contact = 1.0 - np.exp(-delta / lam)
    for _ in range(n_rounds):
        okf = (rng.random(n_dev) < p_contact).astype(np.float32)
        tau = rng.exponential(c, n_dev).astype(np.float32) * okf
        k = np.minimum(tau * rate / bitcost, s)
        succ = okf * (k >= 1.0)
        theta = since  # staleness in rounds at this round
        m = {"uploads": jnp.asarray(okf), "success": jnp.asarray(succ),
             "theta": jnp.asarray(theta, jnp.float32),
             "k": jnp.asarray(k, jnp.float32),
             "bits": jnp.asarray(tau * rate * (k >= 1.0), jnp.float32),
             "energy": jnp.zeros(n_dev, jnp.float32),
             "x_norm2": jnp.ones(n_dev, jnp.float32)}
        state = probes.update(state, m, jnp.asarray(tau))
        since = np.where(succ > 0, 0.0, since + 1.0)
    rep = probes.report(probes.fetch(state), c=c, lam=lam, delta=delta,
                        rate=rate, n=n_dev)
    t = rep["terms"]
    # P(k >= 1) = P(tau >= bitcost/rate) = gamma exactly under Exp(c)
    assert abs(t["success_rate"]["delta"]) < 0.03
    assert t["success_rate"]["expected"] == pytest.approx(
        theory.gamma(rate, c, s, u))
    # E[(s-k)/s] matches the Monte-Carlo closed form
    assert abs(t["error_fraction"]["delta"]) < 0.05
    # Lemma 2 is a bound for a different renewal model — same order of
    # magnitude is the meaningful check
    th = t["staleness_second_moment"]
    assert th["expected"] == pytest.approx(
        theory.staleness_second_moment(c, lam, delta))
    assert 0.3 < (th["measured"] + 1.0) / th["expected"] < 3.0
    # measured mean rate self-calibrates to the true A (bits = rate * tau)
    assert rep["measured"]["mean_rate"] == pytest.approx(rate, rel=1e-4)
    th1 = rep["theorem1"]
    assert th1["total"] > 0 and np.isfinite(th1["total"])
    assert th1["total"] == pytest.approx(
        th1["t1_init_gap"] + th1["t2_sparsify_staleness_coupling"]
        + th1["t3_staleness_sq"] + th1["t4_grad_noise"])
    # the terminal table renders every term
    assert "success_rate" in probes.summary(rep)


def test_probe_report_from_config(federation):
    """End-to-end: a scanned run with probes produces a finite report at
    the FLConfig's contact operating point."""
    import dataclasses

    cfg, model, fl, shard, ev = federation
    fl_p = dataclasses.replace(fl, telemetry_probes=True)
    res = run_afl_scanned(model, cfg, fl_p, "mads", shard, ev, rounds=ROUNDS,
                          eval_every=EVERY, seed=3)
    assert res.telemetry is not None and res.telemetry["probes"] is not None
    suite = resolve_telemetry(fl_p, None, s=model.num_params())
    rep = report_from_config(suite.probes, res.telemetry["probes"], fl_p)
    assert rep["c"] == fl.mean_contact and rep["lam"] == fl.mean_intercontact
    assert set(rep["terms"]) == {"error_fraction",
                                 "staleness_second_moment", "success_rate"}
    for t in rep["terms"].values():
        assert np.isfinite(t["measured"]) and np.isfinite(t["expected"])
    assert 0.0 <= rep["terms"]["success_rate"]["measured"] <= 1.0
    assert np.isfinite(rep["theorem1"]["total"])


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_spans_and_fence():
    tracer = PhaseTracer()
    with tracer.span("compile"):
        pass
    for _ in range(3):
        with tracer.span("execute", r=1):
            tracer.fence(jnp.ones(4) * 2)
            tracer.fence({"host": [1, 2]})  # non-array pytree: no-op
    tot = tracer.totals()
    assert tot["compile"]["count"] == 1
    assert tot["execute"]["count"] == 3
    assert tot["execute"]["total_s"] >= tot["execute"]["max_s"] > 0
    assert "execute" in tracer.summary()
    events = tracer.events()
    assert len(events) == 4 and all(e["kind"] == "span" for e in events)
    json.dumps(events)  # sink-ready
    # without profile_dir, start/stop are no-ops
    tracer.start()
    tracer.stop()


def test_tracer_nested_spans_and_exceptions():
    """Nested spans record parent/depth; a raising span still lands its
    record (with the error type) and the stack unwinds cleanly."""
    tracer = PhaseTracer()
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
        with pytest.raises(ValueError):
            with tracer.span("broken"):
                raise ValueError("boom")
    with tracer.span("after"):  # stack recovered: top-level again
        pass
    ev = {e["name"]: e for e in tracer.events()}
    assert ev["inner"]["parent"] == "outer" and ev["inner"]["depth"] == 1
    assert ev["broken"]["parent"] == "outer"
    assert ev["broken"]["error"] == "ValueError"
    assert "error" not in ev["inner"]
    assert "parent" not in ev["outer"] and "parent" not in ev["after"]
    assert ev["outer"]["duration_s"] >= ev["inner"]["duration_s"]
    json.dumps(list(ev.values()))  # sink-ready with the new fields


# ---------------------------------------------------------------------------
# device phases and the compile counter
# ---------------------------------------------------------------------------


def _compiled_round(federation) -> str:
    """The optimized HLO text of one ``afl_round`` at the tiny ResNet."""
    from repro.core.afl import afl_init, afl_round

    cfg, model, fl, shard, _ = federation
    state = afl_init(model, cfg, fl, jax.random.key(0))
    batch = shard.traced_batch(shard.seed_key(0), 0)
    zeta = jnp.ones((fl.num_devices,))
    policy = BL.ALL["mads"](model.num_params(), fl)
    return afl_round.lower(
        state, batch, zeta, 8.0 * zeta, jnp.full_like(zeta, 1e-9),
        jnp.full_like(zeta, 100.0), model=model, cfg=cfg, fl=fl,
        policy=policy).compile().as_text()


def _running_instructions(hlo_text: str) -> list[str]:
    """Instructions that run as ops of their own: none of a fused or an
    applied computation (a reduction's or a sort's comparator), and no
    parameter, constant or control flow."""
    import re

    inner = set(re.findall(r"\b(?:calls|to_apply)=%?([\w.\-]+)", hlo_text))
    skip = {"parameter", "constant", "while", "conditional", "call"}
    comp, out = None, []
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([\w\-]+)\(",
                     line)
        if m and comp not in inner and m.group(2) not in skip:
            out.append(m.group(1))
    return out


def test_phase_names_and_op_names():
    from repro.telemetry import tracing as T

    assert T.PHASES[:5] == T.ROUND_PHASES
    with pytest.raises(ValueError):
        T.phase("nope")
    assert T.phase_of("jit(run)/while/body/afl.grads/vmap(jvp())/mul") \
        == "grads"
    assert T.phase_of("jit(f)/vmap(afl.state)/sub") == "state"
    assert T.phase_of("jit(f)/while/body/add") == T.UNSCOPED
    text = ('%fused (p: f32[2]) -> f32[2] {\n'
            '  %x = f32[2] add(p, p), metadata={op_name="jit(f)/afl.compress/add"}\n'
            '}\n\nENTRY %main (a: f32[2]) -> f32[2] {\n'
            '  %a = f32[2] parameter(0)\n'
            '  %fusion.3 = f32[2] fusion(%a), kind=kLoop, calls=%fused\n'
            '  ROOT %sort.1 = f32[2] sort(%fusion.3), metadata={op_name='
            '"jit(f)/afl.select/sort" source_file="a{b}.py"}\n}\n')
    assert T.op_phases(text) == {"x": "compress", "a": T.UNSCOPED,
                                 "fusion.3": "compress", "sort.1": "select"}
    assert "metadata" not in T.strip_metadata(text)
    assert "sort(%fusion.3)\n" in T.strip_metadata(text)


def test_op_phases_cover_the_compiled_round(federation):
    """Every phase of the round names instructions of the compiled program,
    and nine in ten of the instructions that run carry a phase."""
    from repro.telemetry import tracing as T

    text = _compiled_round(federation)
    phases = T.op_phases(text)
    assert set(T.ROUND_PHASES) <= set(phases.values())
    running = _running_instructions(text)
    scoped = [n for n in running if phases[n] != T.UNSCOPED]
    assert len(running) > 100
    assert len(scoped) >= 0.9 * len(running)


def test_scopes_leave_the_compiled_round_unchanged(federation, monkeypatch):
    """The scopes change only metadata: with ``phase`` a no-op the
    optimized HLO, metadata stripped, is the same text."""
    import contextlib

    from repro.core import afl
    from repro.telemetry import tracing as T

    scoped = _compiled_round(federation)
    monkeypatch.setattr(afl, "phase", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        plain = _compiled_round(federation)
    finally:
        jax.clear_caches()
    assert "afl.grads" in scoped and "afl.grads" not in plain
    assert T.strip_metadata(scoped) == T.strip_metadata(plain)


def test_compile_counter_counts_a_fresh_jit():
    from repro.telemetry.tracing import compiles

    x = jnp.arange(7.0).block_until_ready()
    before = compiles.totals()
    t0 = time.time()
    jax.jit(lambda x: x * 3.0 + 1.0)(x).block_until_ready()
    after = compiles.totals()
    assert after["lowered"] - before["lowered"] == 1
    assert after["compiled"] - before["compiled"] == 1
    assert after["traced"] - before["traced"] >= 1
    assert after["jax_s"] > before["jax_s"]
    assert after["jax_s"] - before["jax_s"] <= time.time() - t0
    # nothing began after now; the counter counts up to a moment
    assert compiles.totals(until=time.time()) == after
    assert compiles.totals(until=t0)["lowered"] == before["lowered"]
    json.dumps(after)  # sink-ready


def test_compile_counter_unions_nested_spans():
    from repro.telemetry.tracing import CompileCounter, TRACE_EVENT, \
        COMPILE_EVENT, CACHE_LOAD_EVENT, LOWER_EVENT

    c = CompileCounter()  # not installed: fed by hand
    c._span(TRACE_EVENT, 0.0, 4.0)
    c._span(TRACE_EVENT, 1.0, 2.0)  # an inner jit traced inside the outer
    c._span(LOWER_EVENT, 4.0, 5.0)
    c._span(COMPILE_EVENT, 6.0, 8.0)
    c._span("/jax/other", 0.0, 100.0)
    tot = c.totals()
    assert (tot["traced"], tot["lowered"], tot["compiled"]) == (2, 1, 1)
    assert (tot["trace_s"], tot["lower_s"], tot["compile_s"]) == (4, 1, 2)
    assert tot["jax_s"] == 7.0
    assert c.totals(until=5.0)["compiled"] == 0
    c._duration(CACHE_LOAD_EVENT, 0.5)
    assert c.totals()["cache_load_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_jsonl_sink_roundtrip_and_aggregate(tmp_path):
    """write -> read -> aggregate: the sweep telemetry file contract."""
    reg = AFL_REGISTRY
    s = reg.init_state()
    from repro.telemetry import record_round

    m = {"uploads": jnp.asarray([1., 1., 0., 0.]),
         "success": jnp.asarray([1., 0., 0., 0.]),
         "theta": jnp.asarray([2., 5., 1., 1.]),
         "bits": jnp.asarray([1e5, 0., 0., 0.]),
         "k": jnp.asarray([100., 0., 0., 0.]),
         "b": jnp.asarray([8., 0., 0., 0.]),
         "energy": jnp.asarray([0.5, 0.2, 0., 0.])}
    s = record_round(reg, s, m, jnp.asarray([3., 7., 0., 0.]))
    snap = reg.fetch(s)

    path = tmp_path / "telemetry.jsonl"
    with JsonlSink(str(path)) as sink:
        sink.emit({"kind": "metrics", "group": "a", **to_jsonable(snap)})
        sink.emit({"kind": "metrics", "group": "b", **to_jsonable(snap)})
        sink.emit({"kind": "span", "name": "run", "duration_s": 1.0})
        with pytest.raises(TypeError):
            sink.emit({"bad": object()})  # eager validation
    loaded = read_jsonl(str(path))
    assert len(loaded) == 3
    metrics = [r for r in loaded if r["kind"] == "metrics"]
    agg = merge_fetched(metrics)
    assert agg["counters"]["rounds"] == 2.0
    assert agg["counters"]["contacts"] == 4.0
    np.testing.assert_array_equal(
        np.asarray(agg["hist"]["staleness"]),
        2.0 * np.asarray(snap["hist"]["staleness"], np.float64))
    # summary renders from a merged JSONL snapshot too
    assert "success_rate" in reg.summary(agg)


def test_jsonl_sink_sanitizes_nonfinite(tmp_path, caplog):
    """NaN/inf become null (valid JSON) with a warning; serialisability is
    still validated eagerly."""
    import logging

    path = tmp_path / "t.jsonl"
    with caplog.at_level(logging.WARNING, logger="repro.telemetry.export"):
        with JsonlSink(str(path)) as sink:
            sink.emit({"kind": "metrics", "ok": 1.5, "bad": float("nan"),
                       "worse": [float("inf"), 2.0],
                       "nested": {"neg": float("-inf")}})
    assert "sanitized 3 non-finite" in caplog.text
    rec = read_jsonl(str(path))[0]  # strict json.loads round-trips
    assert rec["ok"] == 1.5 and rec["bad"] is None
    assert rec["worse"] == [None, 2.0] and rec["nested"]["neg"] is None


def test_render_report_sections(tmp_path):
    """Events from a suite run render every report section; the CLI
    wrapper writes the same document."""
    table = DeviceTable(2)
    probes = TheoryProbes(s=1024, u=8)
    ts = table.init_state()
    ps = probes.init_state()
    reg = AFL_REGISTRY.init_state()
    from repro.telemetry import record_round

    m = {"uploads": jnp.asarray([1., 0.]), "success": jnp.asarray([1., 0.]),
         "theta": jnp.asarray([2., 5.]), "bits": jnp.asarray([1e5, 0.]),
         "k": jnp.asarray([100., 0.]), "b": jnp.asarray([8., 0.]),
         "energy": jnp.asarray([0.5, 0.]),
         "x_norm2": jnp.asarray([1., 1.]),
         "e_norm2": jnp.asarray([1e-4, 2e-4])}
    tau = jnp.asarray([3., 0.])
    reg = record_round(AFL_REGISTRY, reg, m, tau)
    ts = table.update(ts, m, tau)
    ps = probes.update(ps, m, tau)
    snap = {"metrics": AFL_REGISTRY.fetch(reg), "device": table.fetch(ts),
            "probes": probes.fetch(ps)}
    rep = probes.report(snap["probes"], c=6.0, lam=30.0, delta=10.0)
    events = [
        {"kind": "span", "name": "group", "duration_s": 2.0},
        {"kind": "span", "name": "compile", "parent": "group", "depth": 1,
         "duration_s": 1.5},
        {"kind": "span", "name": "broken", "parent": "group", "depth": 1,
         "duration_s": 0.1, "error": "ValueError"},
        {"kind": "group_metrics", "group": "mads/exp/v10", "seeds": 1,
         **to_jsonable(snap)},
        {"kind": "metrics", **to_jsonable(snap)},
        {"kind": "probe_report", "group": "mads/exp/v10", **rep},
    ]
    json.dumps(events)
    bench = {"suite": "afl", "rows": [parse_csv_row(
        "afl_scan_n8,6235.5,rounds_per_s=160.4")], "history": []}
    text = render_report(events, bench=bench, title="T")
    for section in ("# T", "## Phase breakdown", "## Federation counters",
                    "## Distributions", "## Per-group results",
                    "## Stragglers", "Participation Gini",
                    "## Theory vs measured", "Theorem-1",
                    "## Bench trajectory", "(1 raised)", "mads/exp/v10",
                    "afl_scan_n8"):
        assert section in text, section
    # plain-registry events (no suite sections) still render
    plain = render_report([{"kind": "metrics",
                            **to_jsonable(snap["metrics"])}])
    assert "## Federation counters" in plain
    assert "## Stragglers" not in plain
    # CLI wrapper: same renderer end to end
    tpath = tmp_path / "telemetry.jsonl"
    with JsonlSink(str(tpath)) as sink:
        sink.extend(events)
    script = os.path.join(os.path.dirname(__file__), "..", "tools",
                          "report.py")
    out = subprocess.run(
        [sys.executable, script, str(tpath), "--title", "T"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    rendered = open(tmp_path / "report.md").read()
    assert "## Theory vs measured" in rendered


def test_bench_export_trajectory_and_compare(tmp_path):
    rows = ["afl_scan_n8,6235.5,rounds_per_s=160.4;speedup_vs_loop=2.4x",
            "afl_loop_n8,15111.4,rounds_per_s=66.2"]
    rec = parse_csv_row(rows[0])
    assert rec["name"] == "afl_scan_n8"
    assert rec["metrics"] == {"rounds_per_s": 160.4, "speedup_vs_loop": 2.4}

    out = tmp_path / "bench"
    p = export_bench("afl", rows, out_dir=str(out), meta={"smoke": True})
    assert os.path.basename(p) == "BENCH_afl.json"
    data = load_bench(p)
    assert data["suite"] == "afl" and data["history"] == []
    assert data["rows"][1]["metrics"]["rounds_per_s"] == 66.2
    # re-export pushes the previous rows onto the trajectory
    export_bench("afl", rows, out_dir=str(out))
    assert len(load_bench(p)["history"]) == 1

    # regression checker: ok at parity, exit 1 on a >30% throughput drop
    base = tmp_path / "base"
    export_bench("afl", rows, out_dir=str(base))
    script = os.path.join(os.path.dirname(__file__), "..", "tools",
                          "bench_compare.py")
    ok = subprocess.run(
        [sys.executable, script, str(base / "BENCH_afl.json"), p, "--check"],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    slow = ["afl_scan_n8,6235.5,rounds_per_s=100.0;speedup_vs_loop=1.5x",
            "afl_loop_n8,15111.4,rounds_per_s=66.2"]
    export_bench("afl", slow, out_dir=str(out))
    bad = subprocess.run(
        [sys.executable, script, str(base / "BENCH_afl.json"), p, "--check"],
        capture_output=True, text=True)
    assert bad.returncode == 1
    assert "REGRESSED" in bad.stdout
    # missing baseline: fresh branches pass
    none = subprocess.run(
        [sys.executable, script, str(base / "nope.json"), p, "--check"],
        capture_output=True, text=True)
    assert none.returncode == 0


# ---------------------------------------------------------------------------
# 2 simulated host devices: sharded seed axis, same histograms
# ---------------------------------------------------------------------------


MESH_SCRIPT = r"""
import jax
from repro.launch.mesh import force_host_device_count
force_host_device_count(2)
import numpy as np

from repro.configs import FLConfig, get_config
from repro.experiments import DataShard, run_seed_batch
from repro.launch.mesh import make_seed_mesh
from repro.launch.train import build_device_data
from repro.models.registry import build_model
from repro.telemetry import AFL_REGISTRY, merge_fetched

assert jax.device_count() == 2, jax.devices()

cfg = get_config("resnet9-cifar10").replace(d_model=4)
model = build_model(cfg)
fl = FLConfig(num_devices=4, rounds=6, batch_size=8, learning_rate=0.02,
              mean_contact=6.0, mean_intercontact=30.0,
              energy_budget=(40.0, 80.0))
dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=64, seed=0)
shard = DataShard(dev, fl.batch_size, seed=0)

mesh = make_seed_mesh(2)
assert mesh is not None
sharded = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                         rounds=6, eval_every=3, mesh=mesh,
                         telemetry=AFL_REGISTRY)
single = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                        rounds=6, eval_every=3, mesh=None,
                        telemetry=AFL_REGISTRY)
for i in range(2):
    a, b = sharded[i].telemetry, single[i].telemetry
    for k in a["hist"]:
        assert np.array_equal(a["hist"][k], b["hist"][k]), (i, k)
    for k in ("rounds", "contacts", "successes"):
        assert a["counters"][k] == b["counters"][k], (i, k)
m = merge_fetched([r.telemetry for r in sharded])
assert m["counters"]["rounds"] == 12
print("MESH_TELEMETRY_OK")
"""


@pytest.mark.slow
def test_two_device_mesh_histograms_bit_identical():
    """Seed axis sharded over 2 simulated host devices: per-seed telemetry
    histograms equal the unsharded run's exactly (integer-count contract)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_TELEMETRY_OK" in out.stdout


MESH_SUITE_SCRIPT = r"""
import jax
from repro.launch.mesh import force_host_device_count
force_host_device_count(2)
import numpy as np

from repro.configs import FLConfig, get_config
from repro.experiments import DataShard, run_seed_batch
from repro.launch.mesh import make_seed_mesh
from repro.launch.train import build_device_data
from repro.models.registry import build_model
from repro.telemetry import (AFL_REGISTRY, DeviceTable, TelemetrySuite,
                             TheoryProbes, merge_fetched)

assert jax.device_count() == 2, jax.devices()

cfg = get_config("resnet9-cifar10").replace(d_model=4)
model = build_model(cfg)
fl = FLConfig(num_devices=4, rounds=6, batch_size=8, learning_rate=0.02,
              mean_contact=6.0, mean_intercontact=30.0,
              energy_budget=(40.0, 80.0))
dev, ev = build_device_data(cfg, fl, train_n=160, eval_n=64, seed=0)
shard = DataShard(dev, fl.batch_size, seed=0)
suite = TelemetrySuite(
    metrics=AFL_REGISTRY, device=DeviceTable(fl.num_devices),
    probes=TheoryProbes(s=model.num_params(), u=fl.value_bits))

mesh = make_seed_mesh(2)
assert mesh is not None
sharded = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                         rounds=6, eval_every=3, mesh=mesh, telemetry=suite)
single = run_seed_batch(model, cfg, fl, "mads", shard, ev, seeds=[0, 1],
                        rounds=6, eval_every=3, mesh=None, telemetry=suite)
EXACT = ("rounds", "contacts", "successes", "failures", "last_contact",
         "staleness_sum", "staleness_max")
for i in range(2):
    a, b = sharded[i].telemetry, single[i].telemetry
    for k in a["metrics"]["hist"]:
        assert np.array_equal(a["metrics"]["hist"][k],
                              b["metrics"]["hist"][k]), (i, k)
    for k in EXACT:
        assert np.array_equal(a["device"][k], b["device"][k]), (i, k)
    for k in ("tau_sum", "bits_sum", "energy_sum"):
        assert np.allclose(a["device"][k], b["device"][k], rtol=1e-6), (i, k)
    for k in ("rounds", "contacts", "successes"):
        assert a["probes"][k] == b["probes"][k], (i, k)
    for k in a["probes"]:
        assert np.allclose(a["probes"][k], b["probes"][k], rtol=1e-5,
                           atol=1e-9), (i, k)
m = merge_fetched([r.telemetry for r in sharded])
assert m["device"]["rounds"] == 12
assert m["probes"]["rounds"] == 12
print("MESH_SUITE_OK")
"""


@pytest.mark.slow
def test_two_device_mesh_suite_bit_identical():
    """The full suite (registry + flight recorder + probes) sharded over 2
    simulated host devices matches the unsharded per-seed snapshots."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", MESH_SUITE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_SUITE_OK" in out.stdout

"""An ingest cell: an open-loop stream of uploads into the MES.

Set-up makes a pool of distinct compressed uploads from the seed with the
engines' own codec pass (``core.afl.compress_uploads``, the top-k codec at
``value_bits``) over gradients of the configuration's parameter shapes,
turns each into a wire payload, and builds ``serve.IngestServer`` at its
default mode over the harness's weights.  Arrivals are Poisson at the
traffic's fixed rate; each upload is a pool entry (cycled) computed
against a model version up to ``max_stale`` rounds old.

One host thread plays the clients and the server: it submits every upload
that is due, then steps the server (which fences its ingest), and sleeps
when nothing is due or queued.  An upload's latency runs from its due time
to the fenced end of the step that applied it; a refused upload misses
every limit.  Uploads due in the window are applied after it closes too,
waiting up to ``DRAIN_S``.

After the window the server's weights are compared with a plain float32
aggregation of the same decoded uploads under the same staleness weights.
"""
from __future__ import annotations

import collections
import math
import sys
import time

import numpy as np

from bench.harness.spans import Spans, lowered, seed32
from bench.harness.trace import WINDOW, profile, summarize

DRAIN_S = 60.0
POOL_CHUNK = 16  # clients per codec pass while the pool is made


def make_pool(w0, traffic: dict, seed: int) -> list:
    """Distinct wire payloads from the codec, made on the device in chunks."""
    import jax
    import jax.numpy as jnp

    from repro.compression import TopKCompressor
    from repro.compression.wire import WirePayload
    from repro.core.afl import compress_uploads

    leaves, treedef = jax.tree.flatten(w0)
    s = sum(l.size for l in leaves)
    u, max_k = traffic["value_bits"], traffic["max_k"]
    ib = int(math.ceil(math.log2(s)))
    comp = TopKCompressor(s=s, u=u)
    rng = np.random.default_rng(seed32(seed))
    key = jax.random.key(seed32(seed))

    @jax.jit
    def chunk(kg, kc, budgets):
        n = budgets.shape[0]
        g = jax.tree.unflatten(treedef, [
            jax.random.normal(jax.random.fold_in(kg, i), (n,) + l.shape)
            for i, l in enumerate(leaves)])
        up, _, stats, _ = compress_uploads(
            comp, g, jax.tree.map(jnp.zeros_like, g), kc, budgets, n)
        flat = jnp.concatenate([l.reshape(n, -1) for l in
                                jax.tree.leaves(up)], axis=1)
        idx = jax.vmap(lambda row: jnp.nonzero(
            row, size=max_k, fill_value=s)[0])(flat)
        vals = jnp.take_along_axis(flat, jnp.minimum(idx, s - 1), axis=1)
        return idx, vals, jnp.sum(flat != 0, axis=1), stats["step"]

    pool, cap = [], max_k * (u + ib)
    for lo in range(0, traffic["pool"], POOL_CHUNK):
        n = min(POOL_CHUNK, traffic["pool"] - lo)
        key, kg, kc = jax.random.split(key, 3)
        budgets = jnp.asarray(rng.uniform(0.25, 1.0, n) * cap, jnp.float32)
        idx, vals, count, step = jax.device_get(chunk(kg, kc, budgets))
        for i in range(n):
            k = int(count[i])
            codes = np.rint(vals[i, :k].astype(np.float64) / step[i])
            pool.append(WirePayload(
                coords=idx[i, :k].astype(np.int32),
                codes=codes.astype(np.int32), step=float(step[i]),
                b=float(u), k=k, device=lo + i,
                bits=float(k * (u + ib) + 32 * (k > 0))))
    return pool


def arrivals(traffic: dict, seed: int, seconds: float):
    """Due times (s from the window's start) of the uploads due in the
    window, each one's pool entry and staleness: Poisson at the fixed
    rate, from the seed."""
    rng = np.random.default_rng(seed32(seed) + 1)
    rate = traffic["rate_per_s"]
    n = int(rate * seconds * 1.5) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    while due[-1] < seconds:
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    due = due[due < seconds]
    entry = rng.integers(0, traffic["pool"], due.size)
    stale = rng.integers(0, traffic["max_stale"] + 1, due.size)
    return due, entry, stale


def build(config: dict, traffic: dict, seed: int):
    import jax

    from repro.compression.wire import pack_batch
    from repro.core.afl import StalenessWeight
    from repro.serve import IngestServer

    from bench.harness.federation import model_module

    ref = model_module(config)
    w0 = jax.jit(lambda k: ref.init(k, config))(
        jax.random.fold_in(jax.random.key(seed32(seed)), 1))
    s = sum(l.size for l in jax.tree.leaves(w0))
    if s != config["params"]:
        raise ValueError(f"{config['name']}: {s} parameters, the "
                         f"configuration states {config['params']}")
    pool = make_pool(w0, traffic, seed)
    staleness = StalenessWeight(family=traffic["staleness"])
    server = IngestServer(w0, num_devices=traffic["num_devices"],
                          batch=traffic["batch"], max_k=traffic["max_k"],
                          staleness=staleness)
    # the one ingest program, compiled before the window (pure: discarded)
    packed = pack_batch(pool[:traffic["batch"]], s=s, max_k=server.max_k,
                        batch=server.batch)
    jax.block_until_ready(server._ingest(server.w, packed, server.tstate))
    return {"w0": w0, "s": s, "pool": pool, "server": server,
            "staleness": staleness}


def serve(b: dict, traffic: dict, seed: int, seconds: float, spans: Spans):
    """The open loop over one window; returns per-upload records."""
    server = b["server"]
    due, entry, stale = arrivals(traffic, seed, seconds)
    n = due.size
    done = np.full(n, np.inf)   # fenced end of the applying step, window s
    late = np.zeros(n)          # how late the generator submitted
    refused = np.zeros(n, bool)
    tags = np.zeros(n, np.int64)  # model version each upload was made on
    dtau = np.zeros(n)          # staleness the server saw
    queued = collections.deque()
    i, t0, n0 = 0, time.perf_counter(), lowered()
    with spans.span(WINDOW):
        while True:
            now = time.perf_counter() - t0
            with spans.span("generate"):
                while i < n and due[i] <= now:
                    tags[i] = server.rnd - int(stale[i])
                    item = b["pool"][entry[i]]._replace(rnd=int(tags[i]))
                    late[i] = time.perf_counter() - t0 - due[i]
                    if server.submit(item):
                        queued.append(i)
                    else:
                        refused[i] = True
                    i += 1
            if queued:
                with spans.span("step"):
                    took = server.step()
                end = time.perf_counter() - t0
                for _ in range(took):
                    u = queued.popleft()
                    done[u] = end
                    # packed at the round before the step advanced it
                    dtau[u] = max(server.rnd - 1 - tags[u], 0)
            elif i < n:
                with spans.span("idle"):
                    time.sleep(max(0.0, min(due[i] - now, 0.005)))
            else:
                break
            if now > seconds + DRAIN_S:
                break
    print(f"programs lowered in the window: {lowered() - n0}",
          file=sys.stderr, flush=True)
    return {"due": due, "done": done, "late": late, "refused": refused,
            "entry": entry, "dtau": dtau, "t0": t0}


def reference_w(b: dict, rec: dict, traffic: dict, dtype=None):
    """Plain aggregation of the applied uploads: decode each (codes x step),
    weight it by the staleness rule, add them up, average over the
    population.  float32 unless ``dtype`` says otherwise."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    s, max_k = b["s"], traffic["max_k"]
    applied = np.flatnonzero(np.isfinite(rec["done"]))

    @jax.jit
    def add(acc, coords, codes, steps, weights):
        vals = codes.astype(dtype) * steps.astype(dtype)[:, None]
        vals = vals * weights.astype(dtype)[:, None]
        return acc.at[coords].add(vals, mode="drop")

    acc = jnp.zeros((s,), dtype)
    block = 256
    for lo in range(0, applied.size, block):
        ids = applied[lo:lo + block]
        coords = np.full((block, max_k), s, np.int32)
        codes = np.zeros((block, max_k), np.int32)
        steps = np.zeros(block, np.float32)
        weights = np.zeros(block, np.float32)
        for j, u in enumerate(ids):
            p = b["pool"][rec["entry"][u]]
            coords[j, :p.k], codes[j, :p.k] = p.coords, p.codes
            steps[j] = p.step
            weights[j] = float(b["staleness"].weight(rec["dtau"][u]))
        acc = add(acc, coords, codes, steps, weights)
    out, off = [], 0
    leaves, treedef = jax.tree.flatten(b["w0"])
    for l in leaves:
        part = acc[off:off + l.size].reshape(l.shape)
        out.append(l.astype(dtype) - part / traffic["num_devices"])
        off += l.size
    return jax.tree.unflatten(treedef, out)


def readings(b: dict, w, want) -> dict:
    """Per-leaf norms of the server's distance from the reference, and of
    the reference's own update."""
    import jax
    import jax.numpy as jnp

    from bench.harness.federation import norms

    return {"gap": norms(jax.tree.map(
        lambda a, c: a.astype(jnp.float32) - c.astype(jnp.float32), w,
        want)), "update": norms(jax.tree.map(
            lambda c, w0: c.astype(jnp.float32) - w0, want, b["w0"]))}


def ingest_numbers(r: dict, applied: int, ingested: float) -> dict:
    """``w_gap``: the worst leaf's distance from the reference over the
    larger of its update and the median leaf's; ``applied``: uploads the
    server counts as ingested against those the harness saw applied."""
    import statistics

    floor = statistics.median(r["update"].values()) or max(
        r["update"].values())
    gap = max(r["gap"][k] / max(r["update"][k], floor) for k in r["gap"])
    return {"w_gap": gap, "applied": float(abs(ingested - applied))}


def run(cell: dict, *, seed: int, seconds: float, trace: bool, start: float,
        devices, fault=None) -> dict:
    import jax

    from bench.harness.cli import device_info
    from bench.harness.federation import TRACE_DIR, _peaks

    config, traffic = cell["config"], cell["traffic"]
    spans = Spans(annotate=trace)
    b = build(config, traffic, seed)
    if fault is not None:
        fault(b)
    server = b["server"]
    out = {"reports": ["ingest_p95_ms", "setup_s"], "end_to_end": {},
           "trace": None}
    t_setup = time.perf_counter() - start
    if trace:
        window = traffic["trace_seconds"]
        with profile(TRACE_DIR):
            rec = serve(b, traffic, seed, window, spans)
        summary = summarize(TRACE_DIR)
        t0 = rec["t0"]
        packs = [sp.duration for sp in server.tracer.spans
                 if sp.name == "serve.pack" and sp.start >= t0]
        summary.update(pack_s=packs, peaks=_peaks(devices))
        out["trace"] = summary
    else:
        window = seconds
        rec = serve(b, traffic, seed, window, spans)
    lat_ms = (rec["done"] - rec["due"]) * 1e3
    lat_ms[rec["refused"]] = np.inf
    n = lat_ms.size
    failed = int(np.sum(~np.isfinite(lat_ms)))
    p95 = float(np.percentile(lat_ms, 95)) if n else float("inf")
    if not math.isfinite(p95):
        p95 = (window + DRAIN_S) * 1e3
    print(f"generator lateness: p50 {np.median(rec['late']) * 1e3:.3f} ms, "
          f"p95 {np.percentile(rec['late'], 95) * 1e3:.3f} ms, max "
          f"{np.max(rec['late']) * 1e3:.3f} ms over {n} uploads", flush=True)
    if not trace:
        out["end_to_end"] = {"ingest_p95_ms": p95, "setup_s": t_setup}
    device = device_info(devices)
    snap = server.snapshot()
    w = server.w
    b.pop("server")
    applied = int(np.sum(np.isfinite(rec["done"])))
    want = reference_w(b, rec, traffic)
    nums = ingest_numbers(readings(b, w, want), applied,
                          snap["counters"]["ingested"])
    checks = [(k, nums[k], lim) for k, lim in cell["limits"]["limits"].items()]
    out.update(correct=all(v <= lim for _, v, lim in checks),
               attempted=n, failed=failed, device=device, checks=checks)
    return out


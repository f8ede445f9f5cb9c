"""Soak the streaming ingestion server: sustained uploads/sec.

Generates a population of compressed uploads with the engines' own codec
invocation (``core.afl.compress_uploads`` — the same function both the
single-host and pjit rounds call), serialises them to the wire format,
and drives them through ``serve.IngestServer`` in a bounded-queue
producer/consumer loop, measuring sustained aggregation throughput:

    PYTHONPATH=src python -m repro.launch.soak --uploads 10000 \
        --batch 256 --params 4096 --staleness hinge --out-dir out/

The per-upload loop baseline (the fused op at batch=1 — what a naive
server does) runs alongside; ``speedup_vs_loop`` is the headline number
and ``BENCH_serve.json`` (``--out-dir``) feeds the
``tools/bench_compare.py`` CI gate.  ``--mesh N`` shards the batch axis
over N simulated host devices (``core.distributed.ingest_shardings``).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

__all__ = ["run_soak", "make_payloads", "main"]

_CODECS = ("topk", "topk32", "qsgd", "joint", "fixed-kb")


def _make_codec(name: str, s: int):
    from repro.compression import (FixedKbCompressor, JointCompressor,
                                   QSGDCompressor, TopKCompressor)

    if name == "topk":
        return TopKCompressor(s=s, u=8)
    if name == "topk32":
        return TopKCompressor(s=s, u=32)
    if name == "qsgd":
        return QSGDCompressor(s=s)
    if name == "joint":
        return JointCompressor(s=s)
    if name == "fixed-kb":
        return FixedKbCompressor(s=s, b=8)
    raise ValueError(f"unknown codec {name!r}; known: {_CODECS}")


def make_payloads(uploads: int, s: int, max_k: int, *, codec: str = "topk",
                  max_stale: int = 32, seed: int = 0, chunk: int = 512):
    """Compress ``uploads`` synthetic gradients and serialise to the wire.

    Chunks of devices go through ``compress_uploads`` (vmap over the
    chunk, EF state threaded — exactly the engines' codec pass); each
    device's dense payload is then encoded host-side with the codec's
    reported ``(step, b)`` so quantised codecs ship integer grid codes.
    Upload round tags are back-dated up to ``max_stale`` rounds so the
    staleness-weight family has a spread of ``delta_tau`` to act on.
    """
    import jax
    import jax.numpy as jnp

    from repro.compression.wire import encode_upload, index_bits
    from repro.core.afl import compress_uploads

    comp = _make_codec(codec, s)
    shapes = {"layer0": (s // 2,), "layer1": (s - s // 2,)}
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    # budgets that keep k within the wire's max_k (dense qsgd ships k = s)
    u_bits = 32 if codec == "topk32" else 8
    cap = float(max_k) * (u_bits + index_bits(s))
    payloads = []
    for lo in range(0, uploads, chunk):
        n = min(chunk, uploads - lo)
        key, kg, kc = jax.random.split(key, 3)
        g_n = {name: jax.random.normal(jax.random.fold_in(kg, i),
                                       (n,) + shp, jnp.float32)
               for i, (name, shp) in enumerate(shapes.items())}
        e_n = jax.tree.map(jnp.zeros_like, g_n)
        budgets = jnp.asarray(
            rng.uniform(0.25, 1.0, size=n) * cap, jnp.float32)
        upload, _, cstats, _ = compress_uploads(comp, g_n, e_n, kc,
                                                budgets, n)
        up_np = {k: np.asarray(v) for k, v in upload.items()}
        step_np = np.asarray(cstats["step"], np.float64)
        b_np = np.asarray(cstats["b"], np.float64)
        stale = rng.integers(0, max_stale, size=n)
        for i in range(n):
            payloads.append(encode_upload(
                {k: v[i] for k, v in up_np.items()},
                b=b_np[i] if b_np[i] > 0 else 32.0, step=float(step_np[i]),
                device=lo + i, rnd=-int(stale[i]), max_k=max_k))
    return payloads


def _drain_all(server, payloads) -> None:
    """Producer/consumer loop: offer until backpressure, then step."""
    i, n = 0, len(payloads)
    while i < n or len(server.buffer):
        while i < n:
            if server.submit(payloads[i]):
                i += 1
            elif server.buffer.policy == "reject":
                i += 1  # refused for good — counted, client re-uploads later
            else:
                break  # deferred: retry the same payload after a step
        server.step()


def run_soak(*, uploads: int = 10_000, batch: int = 256, s: int = 4096,
             max_k: int = 256, codec: str = "topk",
             staleness_family: str = "constant", alpha: float = 1.0,
             queue_cap: int = 0, queue_policy: str = "defer",
             mode: str = "parity", baseline: bool = True,
             baseline_n: int = 2048, mesh=None, seed: int = 0,
             tracer=None) -> dict:
    """One soak point; returns throughput numbers + the telemetry snapshot."""
    import jax
    import jax.numpy as jnp

    from repro.core.afl import StalenessWeight
    from repro.compression.wire import pack_batch
    from repro.serve import IngestServer
    from repro.telemetry.tracing import PhaseTracer

    tracer = tracer or PhaseTracer()
    if codec == "qsgd":
        max_k = s  # dense codec: every coordinate rides the wire
    with tracer.span("soak.generate", uploads=uploads):
        payloads = make_payloads(uploads, s, max_k, codec=codec, seed=seed)
    sw = StalenessWeight(family=staleness_family, alpha=alpha)
    w = {"layer0": jnp.zeros((s // 2,), jnp.float32),
         "layer1": jnp.zeros((s - s // 2,), jnp.float32)}

    def build(b, cap):
        srv = IngestServer(
            w, num_devices=uploads, batch=b, max_k=max_k, staleness=sw,
            queue_capacity=cap, queue_policy=queue_policy, mesh=mesh,
            mode=mode, tracer=tracer)
        # warm the jit outside the timed region (ingest is pure: discard)
        packed = pack_batch([], s=srv.s, max_k=max_k, batch=b)
        if srv._shardings is not None:
            packed = {k: jax.device_put(v, srv._shardings["batch"])
                      for k, v in packed.items()}
        jax.block_until_ready(srv._ingest(srv.w, packed, srv.tstate))
        return srv

    with tracer.span("soak.fused", uploads=uploads):
        server = build(batch, queue_cap or 4 * batch)
        t0 = time.perf_counter()
        _drain_all(server, payloads)
        jax.block_until_ready(server.w)
        fused_wall = time.perf_counter() - t0
    snap = server.snapshot()
    done = snap["counters"]["ingested"]
    out = {
        "uploads": uploads, "batch": batch, "s": s, "max_k": max_k,
        "codec": codec, "staleness": staleness_family, "mode": mode,
        "fused_wall_s": fused_wall, "fused_per_s": done / fused_wall,
        "snapshot": snap, "server": server,
    }
    if baseline:
        nb = min(uploads, baseline_n)
        with tracer.span("soak.loop_baseline", uploads=nb):
            loop_srv = build(1, max(queue_cap, 4 * batch) or 4 * batch)
            t0 = time.perf_counter()
            _drain_all(loop_srv, payloads[:nb])
            jax.block_until_ready(loop_srv.w)
            loop_wall = time.perf_counter() - t0
        out["loop_per_s"] = nb / loop_wall
        out["speedup_vs_loop"] = out["fused_per_s"] / out["loop_per_s"]
    return out


def main(argv=None) -> None:
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--uploads", type=int, default=10_000)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--params", type=int, default=4096,
                    help="flat model size s")
    ap.add_argument("--max-k", type=int, default=256,
                    help="wire payload coordinate capacity")
    ap.add_argument("--codec", default="topk", choices=_CODECS)
    ap.add_argument("--staleness", default="constant",
                    choices=("constant", "hinge", "poly"))
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="arrival buffer capacity (0 = 4x batch)")
    ap.add_argument("--queue-policy", default="defer",
                    choices=("reject", "defer"))
    ap.add_argument("--mode", default="parity",
                    choices=("parity", "scatter"))
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the batch over N simulated host devices")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the per-upload loop baseline")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small point (CI): 1500 uploads, s=2048")
    ap.add_argument("--out-dir", default="",
                    help="export BENCH_serve.json here")
    args = ap.parse_args(argv)

    mesh = None
    if args.mesh > 1:
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(args.mesh)
        import jax
        from jax.sharding import Mesh
        devs = np.asarray(jax.devices()[: args.mesh]).reshape(args.mesh, 1)
        mesh = Mesh(devs, ("data", "model"))

    if args.smoke:
        args.uploads, args.params = min(args.uploads, 1500), 2048
        args.batch, args.max_k = min(args.batch, 128), min(args.max_k, 128)

    from repro.telemetry import export_bench
    from repro.telemetry.tracing import PhaseTracer

    tracer = PhaseTracer()
    res = run_soak(
        uploads=args.uploads, batch=args.batch, s=args.params,
        max_k=args.max_k, codec=args.codec,
        staleness_family=args.staleness, alpha=args.alpha,
        queue_cap=args.queue_cap, queue_policy=args.queue_policy,
        mode=args.mode, baseline=not args.no_baseline, mesh=mesh,
        seed=args.seed, tracer=tracer)

    server = res.pop("server")
    print(server.registry.summary(res["snapshot"]))
    print(tracer.summary())
    name = (f"soak_{args.codec}_{args.staleness}"
            f"_n{args.uploads}_b{args.batch}_s{args.params}")
    derived = f"uploads_per_s={res['fused_per_s']:.0f}"
    if "speedup_vs_loop" in res:
        derived += (f";loop_per_s={res['loop_per_s']:.0f}"
                    f";speedup_vs_loop={res['speedup_vs_loop']:.1f}x")
    row = f"{name},{res['fused_wall_s'] / max(args.uploads, 1) * 1e6:.1f},{derived}"
    print(row)
    if args.out_dir:
        export_bench("serve", [row], args.out_dir)


if __name__ == "__main__":
    main()

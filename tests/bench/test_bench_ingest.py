"""The ingest cell at a size a CPU test run can hold: the plain aggregation
agrees with the fused ingest on one batch; ``correct`` is true for the
sound server, false with an upload altered where it is applied, and the
control (the plain aggregation at bfloat16) fails the cell's limit."""
from __future__ import annotations

import time

import numpy as np
import pytest

from conftest import tiny_cell

SEED = 2**34 + 1


@pytest.fixture(scope="module")
def cell():
    cell = tiny_cell("resnet9.ingest.steady")
    cell["traffic"].update(max_k=256, pool=16, rate_per_s=200,
                           trace_seconds=1)
    return cell


def drive(cell, fault=None):
    import jax

    from bench.harness import ingest

    return ingest.run(cell, seed=SEED, seconds=1.0, trace=False,
                      start=time.perf_counter(), devices=jax.devices()[:1],
                      fault=fault)


def test_reference_matches_fused_ingest_on_one_batch(cell):
    import jax

    from bench.harness import ingest

    traffic = dict(cell["traffic"], staleness="poly")
    b = ingest.build(cell["config"], traffic, SEED)
    server = b.pop("server")
    batch = min(traffic["batch"], len(b["pool"]))
    for p in b["pool"][:batch]:
        assert server.submit(p._replace(rnd=-3))
    assert server.step() == batch
    rec = {"done": np.zeros(batch), "entry": np.arange(batch),
           "dtau": np.full(batch, 3.0)}
    want = ingest.reference_w(b, rec, traffic)
    nums = ingest.ingest_numbers(ingest.readings(b, server.w, want), batch,
                                 batch)
    assert nums["w_gap"] <= 1e-5, nums


def test_sound_server_is_correct(cell):
    res = drive(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 100
    assert 0 < res["end_to_end"]["ingest_p95_ms"] < 1e4


def test_altered_upload_is_not_correct(cell):
    from bench.harness.faults import altered_upload

    res = drive(cell, altered_upload)
    assert not res["correct"], res["checks"]


def test_control_fails_the_limit(cell):
    import jax.numpy as jnp

    from bench.harness import ingest
    from bench.harness.spans import Spans

    b = ingest.build(cell["config"], cell["traffic"], SEED)
    rec = ingest.serve(b, cell["traffic"], SEED, 0.5, Spans())
    b.pop("server")
    want = ingest.reference_w(b, rec, cell["traffic"])
    low = ingest.reference_w(b, rec, cell["traffic"], dtype=jnp.bfloat16)
    applied = int(np.sum(np.isfinite(rec["done"])))
    nums = ingest.ingest_numbers(ingest.readings(b, low, want), applied,
                                 applied)
    limit = cell["limits"]["limits"]["w_gap"]
    assert nums["w_gap"] > limit, nums


def test_same_seed_same_uploads(cell):
    """Arrivals, pool entries, staleness and the payloads themselves come
    from the seed alone."""
    import jax

    from bench.configs import resnet9
    from bench.harness.ingest import arrivals, make_pool

    traffic, config = cell["traffic"], cell["config"]
    w0 = resnet9.init(jax.random.key(0), config)

    def draw(seed):
        return arrivals(traffic, seed, 2.0), make_pool(w0, traffic, seed)

    (one, pool), (again, pool2) = draw(2**40 + 7), draw(2**40 + 7)
    for a, b in zip(one, again):
        np.testing.assert_array_equal(a, b)
    for p, q in zip(pool, pool2):
        np.testing.assert_array_equal(p.coords, q.coords)
        np.testing.assert_array_equal(p.codes, q.codes)
        assert p.step == q.step
    other, _ = draw(2**40 + 8)
    assert not np.array_equal(one[0][:10], other[0][:10])
    # Poisson at the fixed rate: about rate x seconds uploads
    assert abs(one[0].size - 2.0 * traffic["rate_per_s"]) < 100

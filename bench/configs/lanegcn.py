"""Plain reference of the repo's LaneGCN-lite block (after Liang et al.,
"Learning Lane Graph Representations for Motion Forecasting", ECCV 2020).

Not the full LaneGCN graph: an actor net of two 1-D convolutions with a
max over time, a map net of two chain-graph convolutions over the lane
nodes, one actor-to-map attention, and a two-layer regression head for
the future (x, y) positions.  Loss: average displacement error.

``init`` makes the run's weights from the seed; ``flops_per_sample``
counts the model FLOPs of one training sample.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness.costs import conv_flops, dense_flops, train_flops


def relu(x):
    """max(x, 0) with the gradient 0 at x = 0 (jnp.maximum splits it)."""
    return jnp.where(x > 0, x, jnp.zeros_like(x))


def _shapes(mc):
    d, ff, out = mc["d_model"], mc["d_ff"], 2 * mc["future"]
    return {
        "actor_conv1": (3, 2, d), "actor_conv2": (3, d, d),
        "map_in": (2, d), "gcn1": (2 * d, d), "gcn2": (2 * d, d),
        "fuse_q": (d, d), "fuse_k": (d, d), "fuse_v": (d, d),
        "head1": (2 * d, ff), "head2": (ff, out),
    }


def init(key, mc):
    shapes = _shapes(mc)
    params = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                shapes.items()):
        fan_in = 1
        for n in shape[:-1]:
            fan_in *= n
        params[name] = {
            "w": jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5,
            "b": jnp.zeros((shape[-1],), jnp.float32),
        }
    return params


def _conv1d_relu(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride,), "SAME", dimension_numbers=("NWC", "WIO", "NWC"))
    return relu(y + p["b"])


def _dense(p, x):
    return x @ p["w"] + p["b"]


def predict(params, past, lanes, mc):
    dt = params["map_in"]["w"].dtype
    a = _conv1d_relu(params["actor_conv1"], past.astype(dt), 1)
    a = _conv1d_relu(params["actor_conv2"], a, 2)
    actor = jnp.max(a, axis=1)
    m = relu(_dense(params["map_in"], lanes.astype(dt)))
    for name in ("gcn1", "gcn2"):
        neighbours = 0.5 * (jnp.roll(m, 1, axis=1) + jnp.roll(m, -1, axis=1))
        m = relu(_dense(params[name], jnp.concatenate([m, neighbours], -1)))
    q = _dense(params["fuse_q"], actor)
    k = _dense(params["fuse_k"], m)
    v = _dense(params["fuse_v"], m)
    scores = jnp.einsum("bd,bmd->bm", q, k) / jnp.sqrt(
        jnp.asarray(mc["d_model"], dt))
    att = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bm,bmd->bd", att, v)
    h = relu(_dense(params["head1"], jnp.concatenate([actor, ctx], -1)))
    return _dense(params["head2"], h).reshape(-1, mc["future"], 2)


def loss(params, batch, mc):
    """Average displacement error of the predicted future positions."""
    pred = predict(params, batch["past"], batch["lanes"], mc)
    diff = pred - batch["future"].astype(pred.dtype)
    return jnp.mean(jnp.sqrt(jnp.sum(jnp.square(diff), axis=-1)))


def flops_per_sample(mc) -> float:
    """Forward + backward model FLOPs of one trajectory."""
    d, ff, m = mc["d_model"], mc["d_ff"], mc["lane_nodes"]
    past = mc["past"]
    fwd = conv_flops(past * d, 3, 2)
    fwd += conv_flops(-(-past // 2) * d, 3, d)
    fwd += dense_flops(m, 2, d) + 2 * dense_flops(m, 2 * d, d)
    fwd += dense_flops(1, d, d) + 2 * dense_flops(m, d, d)
    fwd += 2 * dense_flops(m, d, 1)  # scores and the weighted sum
    fwd += dense_flops(1, 2 * d, ff) + dense_flops(1, ff, 2 * mc["future"])
    return train_flops(fwd)

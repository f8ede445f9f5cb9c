"""Plain reference of ResNet-9 (D. Page, "How to train your ResNet",
Myrtle.ai 2018; the paper's §VI image model) as the repo's model computes it.

Straight ``jax.numpy``/``lax`` at the parameters' dtype: no kernels,
no batching over clients.  Departures from Page's network, which the
program makes too: batch norm always uses the batch's own statistics (no
running averages), the head is a global max pool and a dense layer, with
no logit scale.

``init`` makes the run's weights from the seed (He-normal convolutions);
``flops_per_sample`` counts the model FLOPs of one training sample.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness.costs import conv_flops, dense_flops, train_flops

# (name, input width multiple, output width multiple, pool after)
LAYERS = (("c1", 0, 1, False), ("c2", 1, 2, True), ("r1a", 2, 2, False),
          ("r1b", 2, 2, False), ("c3", 2, 4, True), ("c4", 4, 8, True),
          ("r2a", 8, 8, False), ("r2b", 8, 8, False))


def relu(x):
    """max(x, 0) with the gradient 0 at x = 0 (jnp.maximum splits it)."""
    return jnp.where(x > 0, x, jnp.zeros_like(x))


def _widths(mc, cin_mult, cout_mult):
    w = mc["d_model"]
    return (mc["channels"] if cin_mult == 0 else cin_mult * w), cout_mult * w


def init(key, mc):
    params = {}
    keys = jax.random.split(key, len(LAYERS) + 1)
    for k, (name, cin_m, cout_m, _) in zip(keys, LAYERS):
        cin, cout = _widths(mc, cin_m, cout_m)
        std = (2.0 / (9 * cin)) ** 0.5
        params[name] = {
            "w": jax.random.normal(k, (3, 3, cin, cout), jnp.float32) * std,
            "scale": jnp.ones((cout,), jnp.float32),
            "bias": jnp.zeros((cout,), jnp.float32),
        }
    width = 8 * mc["d_model"]
    params["fc"] = {
        "w": jax.random.normal(keys[-1], (width, mc["classes"]),
                               jnp.float32) * 0.02,
        "b": jnp.zeros((mc["classes"],), jnp.float32),
    }
    return params


def _conv_bn_relu(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    return relu(y)


def _max_pool(x):
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def logits(params, images):
    x = images.astype(params["c1"]["w"].dtype)
    x = _conv_bn_relu(params["c1"], x)
    x = _max_pool(_conv_bn_relu(params["c2"], x))
    x = x + _conv_bn_relu(params["r1b"], _conv_bn_relu(params["r1a"], x))
    x = _max_pool(_conv_bn_relu(params["c3"], x))
    x = _max_pool(_conv_bn_relu(params["c4"], x))
    x = x + _conv_bn_relu(params["r2b"], _conv_bn_relu(params["r2a"], x))
    x = jnp.max(x, axis=(1, 2))
    return x @ params["fc"]["w"] + params["fc"]["b"]


def loss(params, batch, mc):
    """Mean softmax cross-entropy of the batch."""
    z = logits(params, batch["images"])
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)
    return -jnp.mean(picked)


def flops_per_sample(mc) -> float:
    """Forward + backward model FLOPs of one image."""
    side, fwd = mc["image_size"], 0.0
    for name, cin_m, cout_m, pool in LAYERS:
        cin, cout = _widths(mc, cin_m, cout_m)
        fwd += conv_flops(side * side * cout, 9, cin)
        if pool:
            side //= 2
    fwd += dense_flops(1, 8 * mc["d_model"], mc["classes"])
    return train_flops(fwd)

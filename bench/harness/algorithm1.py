"""Plain reference of the paper's Algorithm 1: a per-client loop.

Each round every client takes one SGD step on its own minibatch and adds
it to its cumulative gradient; a client in contact picks its power and
sparsification degree in closed form (MADS, Propositions 1 and 2),
uploads the top-k of its gradient plus error memory, keeps the rest as
error, and receives the new global model; the MES averages the uploads
over the population.  Two upload codecs are written out:

* ``mads``: one global magnitude threshold for ~k of s coordinates at
  32-bit values (the ``exact`` sort or the ``sampled`` estimate from a
  strided sample);
* ``mads-joint``: the closed-form (k, b) split of the contact's bit budget,
  a strict threshold, b-bit stochastic rounding with a counter-based
  dither, and the all-or-nothing budget gate.

Nothing here is imported from the program.  The harness gives it the same
inputs it gives the program: the initial weights it made, the clients'
data, the contact schedule and energy budgets, and the keys.  The client's
minibatch rows and the codec's dither seeds are drawn from those keys by
the rules the program documents.  Arithmetic is in the weights' dtype; the
harness runs it at float32 under ``highest`` matmul precision, and its
control at bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SCALE_BITS = 32  # one float32 quantisation scale per quantised message


def strided_sample(leaf, m: int):
    """|leaf| on a rectangular strided grid of about ``m`` elements: the
    dimensions other than the last are strided first, largest first."""
    shape, size = leaf.shape, leaf.size
    if size <= m or not shape:
        return jnp.abs(leaf).reshape(-1)
    strides, red = [1] * len(shape), size / m
    order = sorted(range(len(shape)),
                   key=lambda i: (i == len(shape) - 1, -shape[i]))
    for i in order:
        if red <= 1.0:
            break
        st = int(min(shape[i], max(1, round(red))))
        strides[i] = st
        red /= st
    idx = tuple(slice(0, n, st) for n, st in zip(shape, strides))
    return jnp.abs(leaf[idx]).reshape(-1)


def magnitudes(leaves, method: str, sample: int):
    """The magnitudes a threshold is read from: all of them (``exact``),
    or a strided sample sized by each leaf's share of ``sample``."""
    if method == "exact":
        return jnp.concatenate([jnp.abs(l).reshape(-1) for l in leaves])
    s = sum(l.size for l in leaves)
    return jnp.concatenate([
        strided_sample(l, max(int(sample * l.size / s), 16)) for l in leaves])


def topk_threshold(leaves, k, method: str, sample: int):
    """|x| >= t selects ~k of s (Algorithm 1's S(x) at traced k)."""
    s = sum(l.size for l in leaves)
    mags = jnp.sort(magnitudes(leaves, method, sample))[::-1]
    if method == "exact":
        idx = jnp.floor(k).astype(jnp.int32) - 1
    else:
        idx = jnp.floor(k / s * mags.size).astype(jnp.int32) - 1
    t = mags[jnp.clip(idx, 0, mags.size - 1)]
    return jnp.where(k < 1.0, jnp.inf, t)


def strict_threshold(leaves, k, method: str, sample: int):
    """|x| >= t selects at most floor(k) (ties only select fewer): the
    (k+1)-th largest magnitude, one ulp up."""
    s = sum(l.size for l in leaves)
    mags = jnp.sort(magnitudes(leaves, method, sample))[::-1]
    if method == "exact":
        idx = jnp.floor(k).astype(jnp.int32)
    else:
        idx = jnp.floor(jnp.clip(k / s, 0.0, 1.0) * mags.size).astype(
            jnp.int32)
    t = mags[jnp.clip(idx, 0, mags.size - 1)].astype(jnp.float32)
    t = jnp.where(k < 1.0, jnp.inf, jnp.where(k >= s, -jnp.inf, t))
    return jnp.nextafter(t, jnp.float32(jnp.inf))


def dither(seed, idx):
    """U[0, 1) from the lowbias32 hash of (seed, element index), top 24 bits."""
    h = idx.astype(jnp.uint32) ^ seed.astype(jnp.uint32)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * 2.0 ** -24


def mads_select(c, zeta, theta, x_norm2, q, tau, h2):
    """Proposition 2's power, then Proposition 1's k, for one client."""
    s, lam, bw, n0 = c["s"], c["wire_bits"], c["bandwidth"], c["n0"]
    noise = bw * n0 / jnp.maximum(h2, 1e-30)
    exponent = jnp.minimum(s * lam / (jnp.maximum(tau, 1e-9) * bw), 60.0)
    cap = jnp.minimum(c["p_max"], noise * (2.0 ** exponent - 1.0))
    p = (3.0 * c["v"] * zeta * theta * bw * x_norm2
         / (jnp.maximum(q, 1e-12) * s * lam) - noise)
    p = jnp.clip(p, 0.0, cap)
    rate = bw * jnp.log2(1.0 + p * h2 / (bw * n0))
    k = jnp.clip(tau * rate / lam, 0.0, float(s)) * zeta
    p = p * zeta
    return k, p, p * tau, rate


def upload_topk(c, leaves, k):
    t = topk_threshold(leaves, k, c["method"], c["sample"])
    ups = [jnp.where(jnp.abs(l) >= t, l, jnp.zeros_like(l)) for l in leaves]
    errs = [l - u for l, u in zip(leaves, ups)]
    count = sum(jnp.sum(jnp.abs(l) >= t) for l in leaves)
    return ups, errs, count.astype(jnp.float32)


def upload_joint(c, leaves, budget, key):
    """The closed-form (k, b) split, strict threshold, b-bit stochastic
    rounding, and the budget gate of the joint codec."""
    s, ib = c["s"], c["index_bits"]
    grid = jnp.asarray(c["b_grid"], jnp.float32)
    avail = jnp.maximum(budget - SCALE_BITS, 0.0)
    kappa = jnp.clip(avail / (s * (grid + ib)), 0.0, 1.0)
    b = grid[jnp.argmax(kappa * (1.0 - 4.0 ** (1.0 - grid) / 3.0))]
    k = jnp.floor(jnp.clip(avail / (b + ib), 0.0, float(s)))
    if c["method"] == "sampled":
        # back off three standard errors of the sampled count
        m = float(min(c["sample"], s))
        rel = jnp.minimum(3.0 * jnp.sqrt(s / (jnp.maximum(k, 1.0) * m)), 0.5)
        k = jnp.floor(jnp.maximum(k * (1.0 - rel), 0.0))
    t = strict_threshold(leaves, k, c["method"], c["sample"])
    levels = jnp.maximum(2.0 ** (b - 1.0) - 1.0, 1.0)
    amax = jnp.max(jnp.stack([jnp.max(jnp.abs(l)) for l in leaves]))
    step = jnp.maximum(amax.astype(jnp.float32), 1e-12) / levels
    seed = jax.random.randint(key, (), 0, np.iinfo(np.int32).max,
                              dtype=jnp.int32)
    ups, errs, count, base = [], [], jnp.float32(0.0), 0
    for l in leaves:
        keep = jnp.abs(l) >= t
        u = dither(seed, base + jnp.arange(l.size).reshape(l.shape))
        qv = jnp.clip(jnp.floor(l / step + u), -levels, levels) * step
        up = jnp.where(keep, qv, 0.0).astype(l.dtype)
        ups.append(up)
        errs.append(l - up)
        count = count + jnp.sum(keep).astype(jnp.float32)
        base += l.size
    bits = count * (b + ib) + SCALE_BITS * (count > 0)
    fits = bits <= budget
    ups = [jnp.where(fits, u, jnp.zeros_like(u)) for u in ups]
    errs = [jnp.where(fits, e, l) for e, l in zip(errs, leaves)]
    return ups, errs, count * fits


def client_round(c, loss, w_n, g_n, e_n, data, rows, zeta, theta, q, tau,
                 h2, budget_j, key, agg):
    """One client's round.  Returns its new local model (before the global
    model replaces it on contact), cumulative gradient, error memory,
    queue and energy, its realised count, and ``agg`` plus its upload."""
    batch = jax.tree.map(lambda a: a[rows], data)
    grad = jax.grad(loss)(w_n, batch)
    lr = jnp.asarray(c["eta"], jax.tree.leaves(g_n)[0].dtype)
    g_new = jax.tree.map(lambda g, d: g + lr * d, g_n, grad)
    g_leaves = jax.tree.leaves(g_new)
    e_leaves = jax.tree.leaves(e_n)
    x = [e + g for e, g in zip(e_leaves, g_leaves)]
    x_norm2 = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in x)
    k, p, energy, rate = mads_select(c, zeta, theta, x_norm2, q, tau, h2)
    if c["policy"] == "mads":
        ups, errs, count = upload_topk(c, x, k)
    else:
        budget = tau * rate * zeta
        ups, errs, count = upload_joint(
            c, [g + e for g, e in zip(g_leaves, e_leaves)], budget, key)
    treedef = jax.tree.structure(g_new)
    contact = zeta > 0
    agg = [a + jnp.where(contact, u, 0).astype(a.dtype)
           for a, u in zip(agg, ups)]
    w_local = jax.tree.map(lambda w, d: w - lr * d, w_n, grad)
    e_new = jax.tree.unflatten(treedef, [
        jnp.where(contact, er, e) for er, e in zip(errs, e_leaves)])
    g_keep = jax.tree.map(lambda g: jnp.where(contact, jnp.zeros_like(g), g),
                          g_new)
    q_new = jnp.maximum(q + energy - budget_j / c["rounds"], 0.0)
    return w_local, g_keep, e_new, q_new, energy, count * contact, agg


def leaf_norms(tree) -> dict:
    """Per-leaf norms of a tree (over every client where the leaves are
    stacked), keyed by the leaf's path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32)))) for _, leaf in flat])
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(flat, vals)}


def one_round(c, loss, w, w_n, g_n, e_n, q, data, rows, zeta, theta, tau, h2,
              budgets, keys):
    """One round of Algorithm 1: the clients one after another (a scan over
    the client axis of the stacked per-client state), then the MES's
    average, and the new global model to every client in contact."""
    n = zeta.shape[0]

    def client(agg, xs):
        (w_m, g_m, e_m, q_m, d_m, rows_m, zeta_m, theta_m, tau_m, h2_m,
         b_m, key_m) = xs
        w_loc, g_m, e_m, q_m, energy, count, agg = client_round(
            c, loss, w_m, g_m, e_m, d_m, rows_m, zeta_m, theta_m, q_m, tau_m,
            h2_m, b_m, key_m, agg)
        return agg, (w_loc, g_m, e_m, q_m, energy, count)

    agg = [jnp.zeros_like(l) for l in jax.tree.leaves(w)]
    agg, (w_loc, g_n, e_n, q, energy, count) = jax.lax.scan(
        client, agg, (w_n, g_n, e_n, q, data, rows, zeta, theta, tau, h2,
                      budgets, keys))
    w = jax.tree.unflatten(jax.tree.structure(w), [
        l - (a / n).astype(l.dtype) for l, a in zip(jax.tree.leaves(w), agg)])
    contact = zeta > 0
    w_n = jax.tree.map(
        lambda loc, g: jnp.where(
            contact.reshape((n,) + (1,) * g.ndim), g[None], loc), w_loc, w)
    return w, w_n, g_n, e_n, q, energy, count


def run(c, loss, w0, data, counts, keys, ckey, schedule, budgets, *,
        steps: int, segment: int, dtype):
    """Follow the program through ``steps`` segments of ``segment`` rounds.

    ``data``: per-client dicts of arrays; ``counts``: rows per client;
    ``keys[i]``: segment i's minibatch key (round j of it draws
    ``randint(fold_in(keys[i], j), (N, B), 0, counts)``); ``ckey``: the
    codec's key carry; ``schedule``: (zeta, tau, h2), each (R, N), cycled.

    Returns the readings the harness compares: the realised count of each
    segment, per-leaf norms of the cumulative gradients after the first,
    and after the last of the global and local models' change from
    ``w0``, of the error memories and cumulative gradients, the rounds of
    last contact, and the energy spent: each client's cumulative energy
    E_n is state of the round like its queue, kept in ``dtype``, and the
    clients' totals are summed in float64."""
    n, rounds = len(data), schedule[0].shape[0]
    w0 = jax.tree.map(lambda a: jnp.asarray(a, dtype), w0)
    longest = int(max(counts))

    def stack(k):
        """Every client's rows of one input, padded to the longest client
        (the padding is never drawn)."""
        out = np.stack([np.concatenate([d[k], np.zeros(
            (longest - len(d[k]),) + d[k].shape[1:], d[k].dtype)])
            for d in data])
        return jnp.asarray(out, out.dtype if out.dtype.kind in "iu" else dtype)

    stacked = {k: stack(k) for k in data[0]}
    w = w0
    w_n = jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), w0)
    g_n = e_n = jax.tree.map(jnp.zeros_like, w_n)
    q = jnp.zeros((n,), dtype)
    kappa = np.zeros(n, np.int64)
    budgets = jnp.asarray(budgets, dtype)
    round_fn = jax.jit(partial(one_round, c, loss))
    rows_fn = jax.jit(lambda key, j: jax.random.randint(
        jax.random.fold_in(key, j), (n, c["batch"]), 0,
        jnp.asarray(counts)[:, None]))
    spent = jnp.zeros((n,), dtype)
    out = {"count": []}
    r = 0
    for i in range(steps):
        seg_counts = []
        for j in range(segment):
            r += 1
            row = (r - 1) % rounds
            zeta, tau, h2 = (np.asarray(a[row]) for a in schedule)
            ckey, sub = jax.random.split(ckey)
            w, w_n, g_n, e_n, q, energy, count = round_fn(
                w, w_n, g_n, e_n, q, stacked, rows_fn(keys[i], j),
                jnp.asarray(zeta, dtype), jnp.asarray(r - kappa, dtype),
                jnp.asarray(tau, dtype), jnp.asarray(h2, dtype), budgets,
                jax.random.split(sub, n))
            kappa[zeta > 0] = r
            spent = spent + energy
            seg_counts.append(count)
        out["count"].append(float(np.sum(jax.device_get(seg_counts),
                                         dtype=np.float64)))
        if i == 0:
            out["grad1"] = leaf_norms(g_n)
    diff = lambda t: jax.tree.map(lambda a, b: a - b, t, w0)
    out["dw"] = leaf_norms(diff(w))
    out["dwn"] = leaf_norms(diff(w_n))
    out["err"] = leaf_norms(e_n)
    out["gsum"] = leaf_norms(g_n)
    out["kappa"] = kappa.tolist()
    out["energy"] = float(np.sum(np.asarray(jax.device_get(spent),
                                            np.float64)))
    return out

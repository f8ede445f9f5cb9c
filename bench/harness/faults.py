"""Faults planted under a federation cell's timed path.

Each takes ``(model, policy)`` and returns ``(model, policy, post)`` for
``federation.build``; the harness's own tests and ``calibrate.py`` use
them to show that ``correct`` comes out false, and to read each fault's
numbers on the chip.
"""
from __future__ import annotations

import dataclasses


def half_batch(model, policy):
    """Every client's loss is the mean over the first half of its batch."""
    import jax

    loss = model.loss_fn

    def half(params, cfg, batch):
        return loss(params, cfg, jax.tree.map(
            lambda a: a[: a.shape[0] // 2], batch))

    return dataclasses.replace(model, loss_fn=half), policy, None


def unchanged_state(model, policy):
    """Each segment hands on the state it was given."""
    return model, policy, lambda old, new: old


def double_energy(model, policy):
    """The upload decision reports twice the energy each upload spends, so
    the clients' energy and their virtual queues take twice the spend."""
    select = type(policy).select

    def doubled(self, *args):
        k, p, energy = select(self, *args)
        return k, p, 2.0 * energy

    cls = type("DoubleEnergy", (type(policy),), {"select": doubled})
    fields = {f.name: getattr(policy, f.name)
              for f in dataclasses.fields(policy)}
    return model, cls(**fields), None


FAULTS = {"half_batch": half_batch, "unchanged_state": unchanged_state,
          "double_energy": double_energy}


def altered_upload(b):
    """The ingest op applies every batch with its first upload's values
    doubled."""
    server = b["server"]
    ingest = server._ingest

    def altered(w, packed, tstate):
        codes = packed["codes"].copy()
        codes[0] *= 2
        return ingest(w, dict(packed, codes=codes), tstate)

    server._ingest = altered


INGEST_FAULTS = {"altered_upload": altered_upload}

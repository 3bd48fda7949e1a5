"""Faults planted under the timed path, to show that the comparison sees
them (the benchmark's own runs plant none).

``FAULTS`` take a step function of the trainer (``make_train_step``'s
or ``make_extended_train_step``'s) and return a broken one with its
signature.  ``SWEEP_FAULTS`` take the engine's ``Results`` of one sweep
inside the extended step and return them altered, as a kernel that
misplaced its answers would.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def frozen(step):
    """The step returns the state it was given (its metrics still run)."""
    def broken(params, opt_state, batch, *rest):
        _, _, metrics = step(params, opt_state, batch, *rest)
        return params, opt_state, metrics
    return broken


def half_batch(step):
    """Half of the batch left out; the step's means run over the rest."""
    def broken(params, opt_state, batch, *rest):
        half = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
        return step(params, opt_state, half, *rest)
    return broken


def altered(step):
    """One answer altered where it is produced: the first parameter
    leaf's update comes out doubled (a gradient counted twice)."""
    def broken(params, opt_state, batch, *rest):
        new, opt_state, metrics = step(params, opt_state, batch, *rest)
        leaves, tree = jax.tree.flatten(new)
        old = jax.tree.leaves(params)
        leaves[0] = (2 * leaves[0].astype("float32")
                     - old[0].astype("float32")).astype(leaves[0].dtype)
        return jax.tree.unflatten(tree, leaves), opt_state, metrics
    return broken


def permuted(res):
    """Every sample's per-sample answers are written one tile down (N/16
    samples, at least one): ``batch_grad``, ``batch_l2`` and ``ggn_trace``
    along the sample axis, ``batch_dot`` along its rows.  Every sum over
    the batch, and so every mean, is unchanged."""
    def roll(a):
        return jnp.roll(a, max(1, a.shape[0] // 16), axis=0)

    ext = dict(res.ext)
    for q in ("batch_grad", "batch_l2", "ggn_trace", "batch_dot"):
        if q in ext:
            ext[q] = jax.tree.map(roll, ext[q])
    return dataclasses.replace(res, ext=ext)


FAULTS = {"frozen": frozen, "half_batch": half_batch, "altered": altered}
SWEEP_FAULTS = {"permuted": permuted}

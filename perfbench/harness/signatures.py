"""Small signatures of the extension quantities, kept per sample, per
pair and per leaf, so that the comparison sees a permuted sample, a
misplaced pair or a misplaced entry that a mean hides.

Per leaf of each quantity's parameter-shaped tree:

* ``batch_l2``, ``ggn_trace`` ([N]) and ``batch_dot`` ([N, N]) are kept
  whole;
* ``batch_grad`` ([N, *param]) becomes [N, K]: each sample's gradient
  projected onto K fixed directions ``u_kᵀ g_n v_k``;
* every other quantity (a parameter-shaped tensor, or a Kronecker
  factor) becomes [K] the same way.

The directions are standard normal, drawn from a fixed key by the length
of each axis, so the program and the reference project alike in every
run.  A projection onto them has the Frobenius norm of what it projects
as its spread, so ``norms`` gives the scale a gap is measured against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

K = 4
WHOLE = frozenset({"batch_l2", "ggn_trace", "batch_dot"})
PER_SAMPLE = frozenset({"batch_grad"})
_KEY = 0x5EED


def _dirs(length, axis):
    key = jax.random.fold_in(jax.random.PRNGKey(_KEY), 2 * length + axis)
    return jax.random.normal(key, (length, K), jnp.float32)


def _project(x, lead):
    """Project the axes after the first ``lead`` onto the K directions;
    more than two such axes are folded into two.  Products and sums run
    elementwise in float32 (one pass over ``x``), not as matrix products,
    whose TPU passes at ``highest`` precision would copy ``x`` thrice."""
    x = x.astype(jnp.float32)
    rest = x.shape[lead:]
    if len(rest) > 2:
        x = x.reshape(x.shape[:lead] + (-1, rest[-1]))
        rest = x.shape[lead:]
    if len(rest) == 1:
        w = _dirs(rest[0], 1)                                   # [b, K]
    else:
        w = _dirs(rest[0], 0)[:, None, :] * _dirs(rest[1], 1)[None]
    axes = tuple(range(lead, lead + len(rest)))
    return jnp.sum(x[..., None] * w, axis=axes)


def sign(name, tree):
    """The signature of one quantity's tree (jit-compatible)."""
    if name in WHOLE:
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    lead = 1 if name in PER_SAMPLE else 0
    return jax.tree.map(lambda a: _project(a, lead), tree)


def norms(name, tree):
    """Per-leaf scales of a reference quantity that its signature does not
    hold: each sample's gradient norm for ``batch_grad`` ([N]), each
    tensor's Frobenius norm for the projected tensors (a scalar), nothing
    for the quantities kept whole."""
    if name in WHOLE:
        return jax.tree.map(lambda a: jnp.zeros((), jnp.float32), tree)
    if name in PER_SAMPLE:
        return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(
            jnp.square(a.astype(jnp.float32)).reshape(a.shape[0], -1), 1)),
            tree)
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


@jax.jit
def sign_all(ext):
    """``{name: signature}`` of a ``{name: tree}`` of whole quantities."""
    return {q: sign(q, t) for q, t in ext.items()}


@jax.jit
def norms_all(ext):
    return {q: norms(q, t) for q, t in ext.items()}

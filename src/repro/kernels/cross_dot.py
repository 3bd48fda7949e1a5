"""Fused cross-block pairwise-dot Pallas kernel (Gram / empirical NTK tiles).

``fused_first_order``'s ``dot`` output is the *diagonal* Gram block of one
row set against itself.  The streaming-Gram lane (``SweepPlan.accumulate``
with BatchDot / NTK) and the NTK extension family need the general
row-block × row-block tile

    out[n, m] = ⟨G1[n], G2[m]⟩,    G1[n] = A1_nᵀ B1_n,  G2[m] = A2_mᵀ B2_m

for two *different* row sets — microbatch pair (p, q) off-diagonal blocks,
or one shard's rows against the gathered columns.  Like the fused kernel,
each per-sample gradient tile is formed exactly once per feature-tile pair
on the MXU and immediately contracted; the [N, a, b] per-sample gradients
never hit HBM.

A leading group axis ``E`` batches independent problems through one launch:
E=1 for BatchDot cross blocks, E=C for the class-diagonal empirical NTK
(``ntk_classwise``), where A is broadcast over classes and B carries the
per-class output Jacobian factors.

Shapes:  A1 [E, N1, R, a], B1 [E, N1, R, b], A2 [E, N2, R, a],
         B2 [E, N2, R, b]  →  out [E, N1, N2] float32.

Tiling: grid (E, N1/bn1, N2/bn2, a/ba, b/bb) — the sample-block pair
outermost (parallel) so each output tile accumulates over its feature
tiles in one run (``arbitrary``, init at (0, 0)).  The output is laid out
[E, NB1, NB2, bn1, bn2] so every tile spans the array's own minor dims;
the caller reassembles [E, N1, N2].  The tile contraction over both feature
axes runs as one matmul on the flattened [bn, ba·bb] tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compiler import mosaic_params


def _kernel(a1_ref, b1_ref, a2_ref, b2_ref, out_ref):
    i, j = pl.program_id(3), pl.program_id(4)
    a1 = a1_ref[0].astype(jnp.float32)  # [bn1, R, ba]
    b1 = b1_ref[0].astype(jnp.float32)  # [bn1, R, bb]
    a2 = a2_ref[0].astype(jnp.float32)  # [bn2, R, ba]
    b2 = b2_ref[0].astype(jnp.float32)  # [bn2, R, bb]
    # Per-sample gradient tiles for this feature-tile pair: batch n,
    # contract the unit axis r.
    G1 = jax.lax.dot_general(
        a1, b1, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [bn1, ba, bb]
    G2 = jax.lax.dot_general(
        a2, b2, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [bn2, ba, bb]

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # out[n, m] += ⟨G1[n], G2[m]⟩ — contract both feature axes at once.
    out_ref[0, 0, 0] += jax.lax.dot_general(
        G1.reshape(G1.shape[0], -1), G2.reshape(G2.shape[0], -1),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def cross_dot_pallas(A1, B1, A2, B2, *, block_a=128, block_b=128,
                     block_n1=8, block_n2=8, interpret=True):
    """A1/B1: [E, N1, R, a/b], A2/B2: [E, N2, R, a/b] →
    [E, N1/bn1, N2/bn2, bn1, bn2] float32.

    Caller is responsible for padding the feature axes to block multiples,
    N1/N2 to their sample-block multiples and R to a sublane multiple —
    see the ``cross_dot`` registry entry in :mod:`repro.kernels.ops`, which
    owns that policy and reassembles [E, N1, N2].
    """
    e, n1, r, a = A1.shape
    n2 = A2.shape[1]
    bn1, bn2 = block_n1, block_n2
    grid = (e, n1 // bn1, n2 // bn2, pl.cdiv(a, block_a),
            pl.cdiv(B1.shape[-1], block_b))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn1, r, block_a),
                         lambda k, p, q, i, j: (k, p, 0, i)),
            pl.BlockSpec((1, bn1, r, block_b),
                         lambda k, p, q, i, j: (k, p, 0, j)),
            pl.BlockSpec((1, bn2, r, block_a),
                         lambda k, p, q, i, j: (k, q, 0, i)),
            pl.BlockSpec((1, bn2, r, block_b),
                         lambda k, p, q, i, j: (k, q, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bn1, bn2),
                               lambda k, p, q, i, j: (k, p, q, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (e, n1 // bn1, n2 // bn2, bn1, bn2), jnp.float32),
        compiler_params=mosaic_params("parallel", "parallel", "parallel",
                                      "arbitrary", "arbitrary",
                                      interpret=interpret),
        interpret=interpret,
    )(A1, B1, A2, B2)

"""Fused first-order statistics Pallas kernel (one pass, K reductions).

BackPACK's economics (paper §2.2): every first-order quantity — per-sample
gradient L2 norms, the summed squared gradient (second moment / variance)
— is a cheap reduction of the SAME ``(input,
grad_out)`` pair the batch gradient already consumes.  The seed engine still
paid one kernel launch (and one HBM read of A and B) *per statistic*; this
kernel forms each per-sample gradient tile

    G[n] = A_nᵀ B_n        (on the MXU, one [N, ba, bb] batch per tile pair)

exactly once per ``(a, b)`` feature-tile pair and emits every *requested*
reduction from the in-register tile:

    moment[a, b]  = Σ_n  G[n]∘G[n]          (second moment / variance)
    l2[n]         = Σ_ab G[n]∘G[n]          (per-sample gradient norms)

The extension mask (``want_l2 / want_moment``) is static: an unrequested
output has no ref, no VMEM footprint and no FLOPs — ``K`` stat sweeps
collapse into 1 with marginal cost per extra statistic.

A leading *group* axis ``E`` batches independent problems through one launch
(E=1 for Dense/attention projections/conv-unfold; E=n_experts for MoE
``BatchedDense``, where capacity slots are the sample units).

Shapes:  A: [E, N, R, a], B: [E, N, R, b]   (R = summed sequence/patch axis)
Outputs: l2 [E, N] · moment [E, a, b], both float32.

Tiling: grid (E, a/ba, b/bb, N/bn) — E parallel, the sample blocks
innermost so the moment tile accumulates over them in one run; l2
accumulates over every feature tile, so its whole [NB, bn, 1] column stays
resident per group.  Sample blocks keep one grid step inside the scoped
VMEM at real widths (3C3D conv1 at N=128 holds 64 MB per input otherwise).
Pairwise dots (BatchDot) need every pair of sample blocks: they are the
cross-dot kernel's (:mod:`repro.kernels.cross_dot`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compiler import mosaic_params

# Output slots in kernel-ref order (static mask selects a subset).
OUTPUTS = ("l2", "moment")


def _make_kernel(want_l2, want_moment):
    def kernel(a_ref, b_ref, *o_refs):
        i, j, nb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
        refs = iter(o_refs)
        l2_ref = next(refs) if want_l2 else None
        mom_ref = next(refs) if want_moment else None

        a = a_ref[0].astype(jnp.float32)  # [bn, R, ba]
        b = b_ref[0].astype(jnp.float32)  # [bn, R, bb]
        # G[n] = A_nᵀ B_n for this feature-tile pair: batch over n, contract r.
        G = jax.lax.dot_general(
            a, b, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [bn, ba, bb]
        G2 = G * G
        if want_moment:
            @pl.when(nb == 0)
            def _init_moment():
                mom_ref[...] = jnp.zeros_like(mom_ref)

            mom_ref[0] += jnp.sum(G2, axis=0)
        if want_l2:
            @pl.when((i == 0) & (j == 0) & (nb == 0))
            def _init_l2():
                l2_ref[...] = jnp.zeros_like(l2_ref)

            l2_ref[0, nb] += jnp.sum(jnp.sum(G2, axis=1), axis=1,
                                     keepdims=True)  # [bn, 1]

    return kernel


def fused_first_order_pallas(A, B, *, want_l2=True, want_moment=False,
                             block_a=128, block_b=128, block_n=8,
                             interpret=True):
    """A: [E, N, R, a], B: [E, N, R, b] → dict of requested float32 stats:
    l2 [E, N/bn, bn, 1] and moment [E, a, b].

    Caller is responsible for padding (a, b) to block multiples, N to a
    ``block_n`` multiple and R to a sublane multiple — see the
    ``fused_first_order`` registry entry in :mod:`repro.kernels.ops`,
    which owns that policy.
    """
    if not (want_l2 or want_moment):
        raise ValueError("fused_first_order: empty extension mask")
    e, n, r, a = A.shape
    b = B.shape[-1]
    nbs = n // block_n
    grid = (e, pl.cdiv(a, block_a), pl.cdiv(b, block_b), nbs)

    out_shapes, out_specs, names = [], [], []
    if want_l2:
        out_shapes.append(
            jax.ShapeDtypeStruct((e, nbs, block_n, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((1, nbs, block_n, 1),
                                      lambda k, i, j, p: (k, 0, 0, 0)))
        names.append("l2")
    if want_moment:
        out_shapes.append(jax.ShapeDtypeStruct((e, a, b), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_a, block_b),
                                      lambda k, i, j, p: (k, i, j)))
        names.append("moment")

    sem_ij = "arbitrary" if want_l2 else "parallel"
    outs = pl.pallas_call(
        _make_kernel(want_l2, want_moment),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, r, block_a),
                         lambda k, i, j, p: (k, p, 0, i)),
            pl.BlockSpec((1, block_n, r, block_b),
                         lambda k, i, j, p: (k, p, 0, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=mosaic_params("parallel", sem_ij, sem_ij,
                                      "arbitrary", interpret=interpret),
        interpret=interpret,
    )(A, B)
    if len(names) == 1:
        outs = (outs,) if not isinstance(outs, (tuple, list)) else outs
    return dict(zip(names, outs))

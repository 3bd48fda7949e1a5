"""Fused second-order sweep Pallas kernel (one pass, K curvature stats).

BackPACK's §2.3 economics, one level up from the first-order kernel: every
curvature quantity of a Dense-shaped layer — the GGN diagonal (Eq. 19/22),
the output-side Kronecker B-factor (Eq. 23, shared by KFLR and KFAC), a
per-sample GGN trace — is a cheap reduction of the SAME ``(A, S)`` pair,
where ``A`` is the layer-input tape and ``S`` the backpropagated
loss-Hessian factor.  The per-extension path re-reads ``S`` from HBM once
per statistic (and the jnp diag path even broadcasts ``A`` to ``[C·N, R,
a]`` copies); here each ``S`` tile is loaded into VMEM exactly once and
feeds every *requested* accumulator:

    t[c,n]      = A_nᵀ S_{c,n}              (MXU, [C′·N, ba, bb] per tile)
    diag[a, b]  = Σ_{c,n} t∘t               (GGN / DiagGGN-MC diagonal)
    kron[b, b]  = Σ_{c,n,r} S Sᵀ            (KFLR / KFAC B-factor, unscaled)
    trace[n]    = Σ_{c,a,b} t∘t             (per-sample GGN trace — beyond
                                             paper: curvature telemetry)

The extension mask (``want_diag / want_kron / want_trace``) is static: an
unrequested output has no ref, no VMEM footprint and no FLOPs.  The MC
sweep reuses the kernel unchanged — the Monte-Carlo sample axis stands in
for the class axis ``C``.

The class axis is folded into the grid in chunks of ``class_chunk``: at
LM-vocabulary scale the per-class contribution tensor ``[C, N, a, b]``
(and the broadcast copy of ``A``) never materializes; VMEM holds one
``[C′, N, R, bb]`` tile of ``S`` at a time.  For the Kronecker factor the
kernel takes a second, full-width view of the same ``S`` buffer so
``SᵀS`` columns span the whole output dimension — no extra HBM copy, the
two views alias one array.

Shapes:  A: [N, R, a];  S: [C, N, R, b]   (R = summed sequence/patch axis)
Outputs: diag [a, b] · kron [b, b] · trace [N/bn, bn, 1], all float32.

Tiling: grid (b/bb, a/ba, C/C′, N/bn), class chunks and sample blocks
innermost so every accumulator sees its revisits consecutively: diag tile
(i, j) accumulates over (c, n); kron tile (j, ·) accumulates over
(i=0, c, n) runs; trace accumulates over everything, so its whole column
stays resident.  Sample blocks keep one grid step inside the scoped VMEM
at real widths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compiler import mosaic_params

# Output slots in kernel-ref order (static mask selects a subset).
OUTPUTS = ("diag", "kron", "trace")


def _make_kernel(want_diag, want_kron, want_trace):
    need_t = want_diag or want_trace  # A only feeds the contraction tile

    def kernel(*refs):
        j, i = pl.program_id(0), pl.program_id(1)
        c, nb = pl.program_id(2), pl.program_id(3)
        it = iter(refs)
        a_ref = it.__next__() if need_t else None
        s_ref = it.__next__()
        sf_ref = it.__next__() if want_kron else None
        diag_ref = it.__next__() if want_diag else None
        kron_ref = it.__next__() if want_kron else None
        tr_ref = it.__next__() if want_trace else None

        s = s_ref[...].astype(jnp.float32)  # [C', bn, R, bb]
        cc, n, r, bb = s.shape
        if need_t:
            a = a_ref[...].astype(jnp.float32)  # [bn, R, ba]
            # Broadcast A over the class chunk in VMEM (never in HBM) and
            # batch the contraction over the fused (c, n) axis on the MXU.
            arep = jnp.broadcast_to(a[None], (cc,) + a.shape)
            t = jax.lax.dot_general(
                arep.reshape(cc * n, r, a.shape[-1]),
                s.reshape(cc * n, r, bb),
                (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [C'·bn, ba, bb]
            t2 = t * t
        if want_diag:
            @pl.when((c == 0) & (nb == 0))
            def _init_diag():
                diag_ref[...] = jnp.zeros_like(diag_ref)

            diag_ref[...] += jnp.sum(t2, axis=0)
        if want_trace:
            @pl.when((i == 0) & (j == 0) & (c == 0) & (nb == 0))
            def _init_trace():
                tr_ref[...] = jnp.zeros_like(tr_ref)

            per = jnp.sum(t2.reshape(cc, n, *t2.shape[1:]), axis=0)
            tr_ref[nb] += jnp.sum(jnp.sum(per, axis=1), axis=1,
                                  keepdims=True)  # [bn, 1]
        if want_kron:
            @pl.when((i == 0) & (c == 0) & (nb == 0))
            def _init_kron():
                kron_ref[...] = jnp.zeros_like(kron_ref)

            # SᵀS touches only S — accumulate once per (j, c, n), not per
            # a-tile.
            @pl.when(i == 0)
            def _acc_kron():
                sf = sf_ref[...].astype(jnp.float32)  # [C', bn, R, b]
                kron_ref[...] += jax.lax.dot_general(
                    s.reshape(-1, bb), sf.reshape(-1, sf.shape[-1]),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

    return kernel


def fused_second_order_pallas(A, S, *, want_diag=True, want_kron=False,
                              want_trace=False, block_a=128, block_b=128,
                              class_chunk=1, block_n=8, interpret=True):
    """A: [N, R, a], S: [C, N, R, b] → dict of requested float32 stats.

    Caller is responsible for padding (a, b) to block multiples, N to a
    ``block_n`` multiple, R to a sublane multiple and C to a
    ``class_chunk`` multiple — see the ``fused_second_order`` registry
    entry in :mod:`repro.kernels.ops`, which owns that policy.
    """
    if not (want_diag or want_kron or want_trace):
        raise ValueError("fused_second_order: empty extension mask")
    c, n, r, b = S.shape
    a = A.shape[-1]
    cc, bn = class_chunk, block_n
    nbs = n // bn
    # Kron-only launches never read A: drop the input and collapse the
    # a-tile grid axis so no step fetches tiles it would discard.
    need_t = want_diag or want_trace
    grid = (pl.cdiv(b, block_b), pl.cdiv(a, block_a) if need_t else 1,
            pl.cdiv(c, cc), nbs)

    in_specs, inputs = [], []
    if need_t:
        in_specs.append(
            pl.BlockSpec((bn, r, block_a), lambda j, i, k, p: (p, 0, i)))
        inputs.append(A)
    inputs.append(S)
    in_specs.append(
        pl.BlockSpec((cc, bn, r, block_b), lambda j, i, k, p: (k, p, 0, j)))
    if want_kron:
        # Second view of the SAME array, full output width (see module doc).
        # Only the i == 0 lane reads it (the kron accumulator fires once per
        # (j, c, n), not per a-tile), so for i > 0 the index map parks on
        # the block the i == 0 sweep ended on: an unchanged block index
        # lets the pipeline elide the re-fetch instead of streaming the
        # full-width slab every step.
        last_c, last_n = pl.cdiv(c, cc) - 1, nbs - 1
        in_specs.append(
            pl.BlockSpec((cc, bn, r, b),
                         lambda j, i, k, p: (jnp.where(i == 0, k, last_c),
                                             jnp.where(i == 0, p, last_n),
                                             0, 0)))
        inputs.append(S)

    out_shapes, out_specs, names = [], [], []
    if want_diag:
        out_shapes.append(jax.ShapeDtypeStruct((a, b), jnp.float32))
        out_specs.append(
            pl.BlockSpec((block_a, block_b), lambda j, i, k, p: (i, j)))
        names.append("diag")
    if want_kron:
        out_shapes.append(jax.ShapeDtypeStruct((b, b), jnp.float32))
        out_specs.append(
            pl.BlockSpec((block_b, b), lambda j, i, k, p: (j, 0)))
        names.append("kron")
    if want_trace:
        out_shapes.append(jax.ShapeDtypeStruct((nbs, bn, 1), jnp.float32))
        out_specs.append(
            pl.BlockSpec((nbs, bn, 1), lambda j, i, k, p: (0, 0, 0)))
        names.append("trace")

    # Grid axes are parallel unless some accumulator spans them: the class
    # and sample axes always accumulate; the a-axis carries the kron
    # (written once at i == 0, revisited after) and trace accumulators;
    # the b-axis only the trace.  Diag-only thus keeps a (parallel,
    # parallel, arbitrary, arbitrary) schedule.
    sem_j = "arbitrary" if want_trace else "parallel"
    sem_i = "arbitrary" if (want_kron or want_trace) else "parallel"
    outs = pl.pallas_call(
        _make_kernel(want_diag, want_kron, want_trace),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=mosaic_params(sem_j, sem_i, "arbitrary", "arbitrary",
                                      interpret=interpret),
        interpret=interpret,
    )(*inputs)
    if len(names) == 1:
        outs = (outs,) if not isinstance(outs, (tuple, list)) else outs
    return dict(zip(names, outs))

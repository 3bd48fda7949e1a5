"""Fused GLM predictive-variance Pallas kernel (Laplace serving hot path).

The linearized (GLM) predictive of a Laplace posterior needs, per sample
``n`` and output class ``c``, the quadratic form ``diag(J Σ Jᵀ)`` where the
per-layer Jacobian tile w.r.t. a Dense-shaped weight block is

    J[c, n] = Σ_r a_{n,r} s_{c,n,r}ᵀ          ([a × b], never materialized)

with ``A`` the layer-input tape and ``S`` the backpropagated output-identity
factor (the same ``(A, S)`` pair the curvature kernels consume — the GGN
sweep with ``S₀ = I`` over outputs instead of the loss-Hessian factor).

Two posterior structures land on ONE kernel:

* **diag** Σ: ``var[c,n] = Σ_{ij} J[c,n,i,j]² σ²[i,j]`` — the kernel takes
  the covariance diagonal ``Sigma [a, b]`` and weights the squared
  contraction tile elementwise (``want_sigma=True``).
* **Kronecker** Σ = (A'⁻¹ ⊗ B'⁻¹): the caller half-transforms the inputs,
  ``Ã = A L_A`` and ``S̃ = S L_B`` with ``L L ᵀ`` the factor inverses, and the
  quadratic form collapses to ``‖J̃[c,n]‖²_F`` — the same kernel with
  ``want_sigma=False``.  The transform is two thin matmuls outside the
  kernel; the O(C·N·a·b) contraction stays fused.

The naive baseline materializes the per-sample Jacobian tensor
``[C, N, a, b]`` in HBM (then squares it, then reduces it — 3 full passes
of traffic); here each ``(a, b)`` tile of the contraction lives only in
VMEM/registers on its way into the ``[C, N]`` accumulator.

Shapes:  A: [N, R, a];  S: [C, N, R, b];  Sigma: [a, b] (optional)
Output:  var [C, N/bn, bn, 1] float32 (a column per class and sample
block, so every output tile spans the array's own minor dims).

Tiling: grid (C/C′, N/bn, a/ba, b/bb) — class chunks and sample blocks
outermost so each output block ``var[c-chunk, n-block]`` stays resident
across its whole (i, j) accumulation run; the (a, b) tile axes are
``arbitrary`` under Mosaic, the class and sample axes ``parallel``
(distinct output blocks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compiler import mosaic_params


def _make_kernel(want_sigma):
    def kernel(*refs):
        it = iter(refs)
        a_ref = next(it)
        s_ref = next(it)
        sig_ref = next(it) if want_sigma else None
        var_ref = next(it)
        i, j = pl.program_id(2), pl.program_id(3)

        s = s_ref[...].astype(jnp.float32)      # [C', bn, R, bb]
        a = a_ref[...].astype(jnp.float32)      # [bn, R, ba]
        cc, n, r, bb = s.shape
        # Broadcast A over the class chunk in VMEM (never in HBM) and batch
        # the r-contraction over the fused (c, n) axis on the MXU.
        arep = jnp.broadcast_to(a[None], (cc,) + a.shape)
        t = jax.lax.dot_general(
            arep.reshape(cc * n, r, a.shape[-1]),
            s.reshape(cc * n, r, bb),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                        # [C'·bn, ba, bb]
        t2 = t * t
        if want_sigma:
            t2 = t2 * sig_ref[...].astype(jnp.float32)[None]
        contrib = jnp.sum(jnp.sum(t2.reshape(cc, n, *t2.shape[1:]), axis=2),
                          axis=2, keepdims=True)  # [C', bn, 1]

        @pl.when((i == 0) & (j == 0))
        def _init():
            var_ref[...] = jnp.zeros_like(var_ref)

        var_ref[:, 0] += contrib

    return kernel


def predictive_var_pallas(A, S, Sigma=None, *, block_a=128, block_b=128,
                          class_chunk=1, block_n=8, interpret=True):
    """A: [N, R, a], S: [C, N, R, b] (+ Sigma [a, b]) →
    var [C, N/bn, bn, 1] float32.

    Caller is responsible for padding (a, b) to block multiples, N to a
    ``block_n`` multiple, R to a sublane multiple and C to a
    ``class_chunk`` multiple — see the ``predictive_var`` registry entry
    in :mod:`repro.kernels.ops`, which owns that policy.  Zero padding is
    exact everywhere: padded A/S rows and columns contribute zero to the
    contraction tile, so their squared entries vanish regardless of
    Sigma's padding.
    """
    c, n, r, b = S.shape
    a = A.shape[-1]
    cc, bn = class_chunk, block_n
    want_sigma = Sigma is not None
    grid = (pl.cdiv(c, cc), n // bn, pl.cdiv(a, block_a),
            pl.cdiv(b, block_b))

    in_specs = [
        pl.BlockSpec((bn, r, block_a), lambda k, p, i, j: (p, 0, i)),
        pl.BlockSpec((cc, bn, r, block_b), lambda k, p, i, j: (k, p, 0, j)),
    ]
    inputs = [A, S]
    if want_sigma:
        in_specs.append(
            pl.BlockSpec((block_a, block_b), lambda k, p, i, j: (i, j)))
        inputs.append(Sigma)

    return pl.pallas_call(
        _make_kernel(want_sigma),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((cc, 1, bn, 1),
                               lambda k, p, i, j: (k, p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, n // bn, bn, 1), jnp.float32),
        compiler_params=mosaic_params("parallel", "parallel", "arbitrary",
                                      "arbitrary", interpret=interpret),
        interpret=interpret,
    )(*inputs)

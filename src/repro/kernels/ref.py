"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax.numpy as jnp


def sq_matmul(A, B):
    """C[a,b] = Σ_n A²[n,a] B²[n,b] — the paper's (A∘A)ᵀ(B∘B) (App. A.1)."""
    Af, Bf = A.astype(jnp.float32), B.astype(jnp.float32)
    return (Af * Af).T @ (Bf * Bf)


def per_sample_moment(A, B):
    """M[a,b] = Σ_n (Σ_r A[n,r,a] B[n,r,b])² — sequence 2nd moment."""
    Af, Bf = A.astype(jnp.float32), B.astype(jnp.float32)
    g = jnp.einsum("nra,nrb->nab", Af, Bf)
    return jnp.sum(g * g, axis=0)


def batch_l2(A, B):
    """l2[n] = Σ_rs (A_n A_nᵀ)[r,s] (B_n B_nᵀ)[r,s] — Gram trick."""
    Af, Bf = A.astype(jnp.float32), B.astype(jnp.float32)
    ga = jnp.einsum("nra,nsa->nrs", Af, Af)
    gb = jnp.einsum("nrb,nsb->nrs", Bf, Bf)
    return jnp.sum(ga * gb, axis=(1, 2))


def ggn_diag(A, S):
    """diag[a,b] = Σ_{c,n} (Σ_r A[n,r,a] S[c,n,r,b])² (Eq. 19/22)."""
    Af, Sf = A.astype(jnp.float32), S.astype(jnp.float32)
    t = jnp.einsum("nra,cnrb->cnab", Af, Sf)
    return jnp.sum(t * t, axis=(0, 1))


def batch_dot(A, B):
    """D[n,m] = ⟨g_n, g_m⟩ for g = A_nᵀB_n — pairwise Gram trick."""
    Af, Bf = A.astype(jnp.float32), B.astype(jnp.float32)
    ga = jnp.einsum("nra,msa->nmrs", Af, Af)
    gb = jnp.einsum("nrb,msb->nmrs", Bf, Bf)
    return jnp.sum(ga * gb, axis=(2, 3))


def cross_dot(A1, B1, A2, B2):
    """out[e,n,m] = ⟨G1[e,n], G2[e,m]⟩ for G = A_nᵀB_n — cross-block Gram.

    The row-block × row-block generalization of :func:`batch_dot`: two
    different row sets (a microbatch pair's off-diagonal Gram block, or an
    NTK row block against gathered columns), a leading group axis E
    (classes for the class-diagonal empirical NTK).
    """
    g1 = jnp.einsum("enra,enrb->enab", A1.astype(jnp.float32),
                    B1.astype(jnp.float32))
    g2 = jnp.einsum("emra,emrb->emab", A2.astype(jnp.float32),
                    B2.astype(jnp.float32))
    return jnp.einsum("enab,emab->enm", g1, g2)


def fused_second_order(A, S, want_diag=True, want_kron=False,
                       want_trace=False):
    """Oracle for the fused curvature kernel: t[c,n] = A_nᵀ S_cn, reduce.

    A: [N, R, a], S: [C, N, R, b] → dict of requested float32 stats
    (diag [a, b] · kron [b, b] (unscaled SᵀS) · trace [N]).
    """
    Af, Sf = A.astype(jnp.float32), S.astype(jnp.float32)
    out = {}
    if want_diag or want_trace:
        t = jnp.einsum("nra,cnrb->cnab", Af, Sf)
        t2 = t * t
        if want_diag:
            out["diag"] = jnp.sum(t2, axis=(0, 1))
        if want_trace:
            out["trace"] = jnp.sum(t2, axis=(0, 2, 3))
    if want_kron:
        out["kron"] = jnp.einsum("cnri,cnrj->ij", Sf, Sf)
    return out


def predictive_var(A, S, Sigma=None):
    """var[c,n] = Σ_{ab} (Σ_r A[n,r,a] S[c,n,r,b])² [· Sigma[a,b]].

    The naive per-sample-Jacobian baseline for the GLM predictive
    variance: materialize J[c,n] = A_nᵀS_cn, square, (weight,) reduce.
    """
    Af, Sf = A.astype(jnp.float32), S.astype(jnp.float32)
    t = jnp.einsum("nra,cnrb->cnab", Af, Sf)
    t2 = t * t
    if Sigma is not None:
        t2 = t2 * Sigma.astype(jnp.float32)
    return jnp.sum(t2, axis=(2, 3))


def fused_first_order(A, B, want_l2=True, want_moment=False):
    """Oracle for the fused kernel: materialize G[n] = A_nᵀB_n, reduce.

    A: [E, N, R, a], B: [E, N, R, b] → dict of requested stats
    (l2 [E, N] · moment [E, a, b]), all float32.
    """
    Af, Bf = A.astype(jnp.float32), B.astype(jnp.float32)
    g = jnp.einsum("enra,enrb->enab", Af, Bf)
    out = {}
    if want_l2:
        out["l2"] = jnp.sum(g * g, axis=(2, 3))
    if want_moment:
        out["moment"] = jnp.sum(g * g, axis=1)
    return out

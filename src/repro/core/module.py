"""Module protocol + generalized-backprop combinators (paper §2.1, Fig. 2).

BackPACK's central abstraction: *a module only needs to know how to multiply
by its Jacobians*.  Every module exposes

  ``apply(params, x)``                       forward
  ``forward_tape(params, x)``                forward + tape (default: input)
  ``backward(params, tape, g, exts, cfg)``   one cotangent sweep step:
      returns ``(g_in, param_grads, stats)`` where ``stats[ext]`` mirrors the
      params pytree (first-order extensions, Eq. 5/9–11 + KFAC A-factors)
  ``jac_t_mat(params, tape, M)``             transposed-Jacobian applied to a
      stack of cotangents ``M``: leading factor axis ``[C̃, *out]→[C̃, *in]``
      (the matrix-Jacobian product the paper §2.1 calls out as missing from
      AD frameworks)
  ``curv_backward(params, tape, S, exts, cfg)``  GGN-factor sweep step
      (Eq. 18): returns ``(S_in, curv_stats)``
  ``kfra_backward(params, tape, Gbar, exts, cfg)``  averaged-curvature sweep
      (Eq. 24); chain models only
  ``hess_backward(params, tape, g, factors, exts, cfg)``  Hessian-diagonal
      sweep with signed residual factors (Eq. 25/26); chain models only

Parameter-free modules fall back to ``jax.vjp`` (re-linearization = remat);
parameterized modules (Dense / Embedding / norms) carry hand-derived
formulas that never materialize per-sample gradients (App. A.1).

Axis convention: activations are ``[N, *reduce_axes, feature]``; axis 0 is
the sample axis.  Per-sample gradients sum over the middle axes *inside* the
square — the sequence/conv generalization of the paper's rank-1 trick.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs

from .extensions import (
    ExtensionConfig,
    FusedMask,
    FusedSecondMask,
    first_order_mask,
    second_order_mask,
)


def _f32(x):
    return x.astype(jnp.float32)


def _nra(x):
    """Reshape [N, *R, d] -> [N, R, d] (R = prod of middle axes)."""
    n, d = x.shape[0], x.shape[-1]
    return x.reshape(n, -1, d)


class UnsupportedSweep(Exception):
    """Raised when a sweep (KFRA / DiagHessian) hits a non-chain module."""


@dataclasses.dataclass(frozen=True)
class Axes:
    """Logical sharding axis names for one parameter leaf."""

    names: tuple

    def prepend(self, name):
        return Axes((name,) + tuple(self.names))


def is_axes(x):
    return isinstance(x, Axes)


# ---------------------------------------------------------------------------
# shared moment helpers (the paper's App. A.1 formulas, sequence-generalized)
# ---------------------------------------------------------------------------


def per_sample_sq_sum(A, B, chunk=8, use_kernels=False):
    """Σ_n (A_nᵀ B_n)∘² without keeping all N [a×b] matrices.

    A: [N, R, a], B: [N, R, b]  →  [a, b] float32.
    R == 1 reduces to the paper's ``(A∘A)ᵀ(B∘B)`` (App. A.1).
    """
    A, B = _f32(A), _f32(B)
    n, r, a = A.shape
    b = B.shape[-1]
    if r == 1:
        if use_kernels:
            from repro.kernels import ops as kops

            return kops.sq_matmul(A[:, 0, :], B[:, 0, :])
        return jnp.einsum("na,nb->ab", A[:, 0, :] ** 2, B[:, 0, :] ** 2)
    if use_kernels:
        from repro.kernels import ops as kops

        return kops.per_sample_moment(A, B)

    chunk = max(1, min(chunk, n))
    pad = (-n) % chunk
    if pad:
        A = jnp.concatenate([A, jnp.zeros((pad, r, a), A.dtype)], 0)
        B = jnp.concatenate([B, jnp.zeros((pad, r, b), B.dtype)], 0)
    Ac = A.reshape(-1, chunk, r, a)
    Bc = B.reshape(-1, chunk, r, b)

    def body(carry, ab):
        Ai, Bi = ab
        g = jnp.einsum("nra,nrb->nab", Ai, Bi)
        return carry + jnp.sum(g * g, axis=0), None

    with jax.named_scope(f"chunkscan_T{Ac.shape[0]}"):
        out, _ = jax.lax.scan(body, jnp.zeros((a, b), jnp.float32), (Ac, Bc))
    return out


def _pairwise_rows(ps, shard_axes=None, cross_split=None):
    """Gram rows G Gᵀ for per-sample rows ``ps`` [N, ...] → [rows, M] f32.

    Single device: rows == M == N (the full pairwise matrix).  Under a
    batch-sharded sweep (``shard_axes`` set, inside ``shard_map``) each
    shard computes its *row block* against the all-gathered rows
    (M == global N); the sharded out-spec concatenates the blocks back
    into the exact full matrix — pairwise stats are the one statistic a
    shard cannot finish from local samples alone.  With ``cross_split``
    (the streaming-Gram pair passes; mutually exclusive with
    ``shard_axes``) the batch is a concatenated microbatch pair and only
    the cross block ``rows[:cs] @ rows[cs:].T`` is emitted.
    """
    rows, cols = _pair_operands(_f32(ps).reshape(ps.shape[0], -1),
                                shard_axes, cross_split)
    return rows @ cols.T


def _pair_operands(x, shard_axes=None, cross_split=None):
    """(rows, cols) sample sets [.., ...] a pairwise stat pairs up: all ×
    all on one device, the local rows × the all-gathered batch under a
    sharded sweep (gathering factors costs activation-sized traffic, not
    [N, a, b] per-sample gradients), the ``[:cs] × [cs:]`` cross block
    under ``cross_split``.  The sample axis is the leading one."""
    if cross_split is not None:
        return x[:cross_split], x[cross_split:]
    if shard_axes:
        return x, jax.lax.all_gather(x, shard_axes, axis=0, tiled=True)
    return x, x


def per_sample_dots(A1, B1, A2, B2):
    """D[n,m] = ⟨g_n, g'_m⟩ for g = A_nᵀB_n — pairwise Gram trick.

    A1/B1: [N, R, a/b] row samples, A2/B2: [M, R, a/b] column samples
    (:func:`_pair_operands`) → [N, M] float32.  diag of the full matrix
    == batch_l2.
    """
    ga = jnp.einsum("nra,msa->nmrs", A1, A2)
    gb = jnp.einsum("nrb,msb->nmrs", B1, B2)
    return jnp.sum(ga * gb, axis=(2, 3))


def _pair_split(cfg):
    """(shard_axes, cross_split) a pairwise stat hook should honour:
    cross blocks are a single-device streaming construct — under a
    sharded sweep the gathered-column row block already carries every
    pair and the driver slices it (see ``engine._run_accumulated``)."""
    axes = getattr(cfg, "shard_axes", None)
    cs = None if axes else getattr(cfg, "cross_split", None)
    return axes, cs


def per_sample_l2(A, B, use_kernels=False):
    """‖g_n‖² for g_n = A_nᵀ B_n — Gram trick (Goodfellow 2015 / App. A.1).

    A: [N, R, a], B: [N, R, b]  →  [N] float32.
    """
    A, B = _f32(A), _f32(B)
    r = A.shape[1]
    if r == 1:
        return jnp.sum(A[:, 0, :] ** 2, -1) * jnp.sum(B[:, 0, :] ** 2, -1)
    if use_kernels:
        from repro.kernels import ops as kops

        return kops.batch_l2(A, B)
    ga = jnp.einsum("nra,nsa->nrs", A, A)
    gb = jnp.einsum("nrb,nsb->nrs", B, B)
    return jnp.sum(ga * gb, axis=(1, 2))


def dense_first_order_stats(A, B, exts, cfg: ExtensionConfig, bias: bool):
    """First-order extension stats for y = x @ W (+ b).

    A: [N, R, a] inputs, B: [N, R, b] output cotangents (already / m).
    Returns ``{ext_name: {'w': ..., 'b': ...}}``.

    With ``cfg.use_kernels`` (and ``cfg.use_fused``, the default) batch_l2
    and the summed squared gradient come out of ONE fused Pallas launch
    over (A, B); the static :class:`~repro.core.extensions.FusedMask`
    selects the outputs.  With ``use_fused=False`` each statistic runs its
    own legacy kernel (the benchmark baseline).  Bias stats are cheap
    row-sums and stay in jnp.

    BatchDot's weight leaf takes the first route that applies, counted
    once per call under ``rule.batch_dot.<route>``:

    * ``rank1`` (R == 1): the closed form (A₁A₂ᵀ) ∘ (B₁B₂ᵀ);
    * ``from_batch_grad`` (BatchGrad requested, not batch-sharded): the
      Gram rows G·Gᵀ of the per-sample gradients BatchGrad already formed
      (its ``[:cs] × [cs:]`` block under ``cross_split``), on either
      ``use_kernels`` route -- no per-sample gradient is formed twice;
    * ``cross_dot`` (``use_kernels``): the Pallas pair-grid kernel on the
      factors;
    * ``einsum``: :func:`per_sample_dots` on the factors.

    A batch-sharded sweep keeps the factor routes even with BatchGrad in
    hand: it gathers the [N, R, a+b] factors, not [N, a·b] gradients, and
    which is smaller depends on the layer's shape.
    """
    names = {e.name for e in exts}
    mask = first_order_mask(names)
    out = {}
    Af, Bf = _f32(A), _f32(B)
    axes, cross = _pair_split(cfg)
    # For R==1 every statistic has a cheaper rank-1 specialization than a
    # fused launch that materializes G[n]=a_n b_nᵀ: l2 is Σa²·Σb²
    # (O(N(a+b))), dot is (AAᵀ)∘(BBᵀ) (O(N²(a+b))), and the moment is the
    # single (A∘A)ᵀ(B∘B) matmul — per_sample_sq_sum routes it to the
    # dedicated sq_matmul kernel below.  Skip the fused kernel entirely.
    rank1 = A.shape[1] == 1
    kmask = FusedMask() if rank1 else mask
    fused = None
    if cfg.use_kernels and cfg.use_fused and kmask.any():
        from repro.kernels import ops as kops

        fused = kops.fused_first_order(Af, Bf, **kmask.wants())
    if "batch_grad" in names:
        d = {"w": jnp.einsum("nra,nrb->nab", Af, Bf)}
        if bias:
            d["b"] = jnp.sum(Bf, axis=1)
        out["batch_grad"] = d
    if mask.moment:
        w = (fused["moment"] if fused is not None and kmask.moment
             else per_sample_sq_sum(A, B, use_kernels=cfg.use_kernels))
        d = {"w": w}
        if bias:
            bsum = jnp.sum(Bf, axis=1)
            d["b"] = jnp.sum(bsum * bsum, axis=0)
        out["_sum_grad2"] = d
    if mask.l2:
        # per_sample_l2 short-circuits to the rank-1 closed form when R==1.
        l2w = (fused["l2"] if fused is not None and kmask.l2
               else per_sample_l2(A, B, use_kernels=cfg.use_kernels))
        if bias:
            bsum = jnp.sum(Bf, axis=1)
            out["batch_l2"] = {"w": l2w, "b": jnp.sum(bsum * bsum, -1)}
        else:
            out["batch_l2"] = {"w": l2w}
    if "batch_dot" in names:
        A1, A2 = _pair_operands(Af, axes, cross)
        B1, B2 = _pair_operands(Bf, axes, cross)
        if rank1:
            # (A1 A2ᵀ) ∘ (B1 B2ᵀ), O(N·M·(a+b)).
            route = "rank1"
            dw = (A1[:, 0] @ A2[:, 0].T) * (B1[:, 0] @ B2[:, 0].T)
        elif "batch_grad" in out and not axes:
            # G = A_nᵀB_n is in hand for BatchGrad: one G·Gᵀ, O(N·M·a·b).
            route = "from_batch_grad"
            dw = _pairwise_rows(out["batch_grad"]["w"], None, cross)
        elif cfg.use_kernels:
            from repro.kernels import ops as kops

            # No G in hand (BatchDot alone, or batch-sharded): the pair grid.
            route = "cross_dot"
            dw = kops.cross_dot(A1, B1, A2, B2)
        else:
            # No G in hand, kernels off: the einsum forms [N, M, R, R]
            # (64 GiB at 3C3D conv1, N=128).
            route = "einsum"
            dw = per_sample_dots(A1, B1, A2, B2)
        obs.count(f"rule.batch_dot.{route}")
        if bias:
            bsum = jnp.sum(Bf, axis=1)
            out["batch_dot"] = {"w": dw,
                                "b": _pairwise_rows(bsum, axes, cross)}
        else:
            out["batch_dot"] = {"w": dw}
    if "kfac" in names or "kflr" in names:
        n, r, _ = A.shape
        a_fac = jnp.einsum("nra,nrc->ac", Af, Af) / float(n * r)
        out["_kron_a"] = {"w": a_fac}
    return out


def _dense_ntk_stats(A, S, names, cfg: ExtensionConfig, bias: bool):
    """Empirical-NTK row blocks for y = x @ W (+ b) from raw-Jacobian
    factors.

    A: [N, R, a] inputs, S: [C, N, R, b] identity-cotangent factors (the
    raw output Jacobian backpropagated to this layer — no loss weighting).
    The per-class per-sample weight Jacobian is G[c,n] = A_nᵀ S[c,n]; the
    class-diagonal kernel block

        T[c, n, m] = ⟨G[c,n], G[c,m]⟩ = Σ_{r,s} (A_n·A_m)(S_cn·S_cm)

    is emitted as [N, M, C] (``ntk_classwise``; sample axes leading so the
    Gram reducer's row-block algebra applies) or class-summed [N, M]
    (``ntk``).  Column semantics mirror :func:`per_sample_dots`: M == N
    single-device, global N under a sharded sweep (row block vs the
    all-gathered factors), the ``[cs, N - cs]`` cross block under
    ``cross_split`` (the streaming pair passes).  The fused path batches
    the class axis through one ``cross_dot`` launch (E = C); rank-1
    layers take the closed form (A₁A₂ᵀ) ∘ per-class (S₁S₂ᵀ).
    """
    out = {}
    Af, Sf = _f32(A), _f32(S)
    axes, cross = _pair_split(cfg)
    rank1 = A.shape[1] == 1
    A1 = A2 = Af
    S1 = S2 = Sf
    if axes:
        A2 = jax.lax.all_gather(Af, axes, axis=0, tiled=True)
        S2 = jax.lax.all_gather(Sf, axes, axis=1, tiled=True)
    elif cross is not None:
        A1, A2 = Af[:cross], Af[cross:]
        S1, S2 = Sf[:, :cross], Sf[:, cross:]
    if rank1:
        KA = A1[:, 0] @ A2[:, 0].T                            # [N, M]
        KS = jnp.einsum("cnb,cmb->cnm", S1[:, :, 0], S2[:, :, 0])
        T = KA[None] * KS                                     # [C, N, M]
    elif cfg.use_kernels and cfg.use_fused:
        from repro.kernels import ops as kops

        c = S1.shape[0]
        T = kops.cross_dot(jnp.broadcast_to(A1[None], (c,) + A1.shape), S1,
                           jnp.broadcast_to(A2[None], (c,) + A2.shape), S2)
    else:
        ga = jnp.einsum("nra,msa->nmrs", A1, A2)
        gs = jnp.einsum("cnrb,cmsb->cnmrs", S1, S2)
        T = jnp.einsum("nmrs,cnmrs->cnm", ga, gs)
    if bias:
        Sb1 = jnp.sum(S1, axis=2)                             # [C, N, b]
        Sb2 = jnp.sum(S2, axis=2)
    if "ntk" in names:
        d = {"w": jnp.sum(T, axis=0)}
        if bias:
            d["b"] = jnp.einsum("cnb,cmb->nm", Sb1, Sb2)
        out["ntk"] = d
    if "ntk_classwise" in names:
        d = {"w": jnp.moveaxis(T, 0, -1)}
        if bias:
            d["b"] = jnp.einsum("cnb,cmb->nmc", Sb1, Sb2)
        out["ntk_classwise"] = d
    return out


def _dense_ggn_gram_stats(A, S, cfg: ExtensionConfig, bias: bool):
    """Loss-scaled logit-space Gram blocks for y = x @ W (+ b).

    A: [N, R, a] inputs, S: [C̃, N, R, b] *loss-scaled* sqrt-Hessian
    factors (the exact sweep's cotangents, carrying 1/√m).  The
    half-sandwich row J'[(n,c)] = A_nᵀ S[c,n] gives the full cross-column
    kernel block

        T[n, m, c, c'] = ⟨J'[(n,c)], J'[(m,c')]⟩
                       = Σ_{r,s} (A_n,r·A_m,s)(S[c,n,r]·S[c',m,s])

    emitted as [N, M, C̃, C̃] — sample axes leading so the Gram reducer's
    row-block algebra (shard assembly, streaming pair passes) applies
    unchanged.  Column semantics mirror :func:`_dense_ntk_stats`.  The
    fused path flattens the (c, n) row pairs through one ``cross_dot``
    launch (E = 1, N₁ = C̃·N); rank-1 layers take the closed form
    (A₁A₂ᵀ) ⊗-broadcast over the per-column-pair (S₁S₂ᵀ).
    """
    Af, Sf = _f32(A), _f32(S)
    axes, cross = _pair_split(cfg)
    rank1 = A.shape[1] == 1
    A1 = A2 = Af
    S1 = S2 = Sf
    if axes:
        A2 = jax.lax.all_gather(Af, axes, axis=0, tiled=True)
        S2 = jax.lax.all_gather(Sf, axes, axis=1, tiled=True)
    elif cross is not None:
        A1, A2 = Af[:cross], Af[cross:]
        S1, S2 = Sf[:, :cross], Sf[:, cross:]
    c1, n1 = S1.shape[0], S1.shape[1]
    c2, n2 = S2.shape[0], S2.shape[1]
    if rank1:
        KA = A1[:, 0] @ A2[:, 0].T                            # [N, M]
        KS = jnp.einsum("cnb,dmb->nmcd", S1[:, :, 0], S2[:, :, 0])
        T = KA[:, :, None, None] * KS
    elif cfg.use_kernels and cfg.use_fused:
        from repro.kernels import ops as kops

        r = A1.shape[1]
        A1r = jnp.broadcast_to(A1[None], (c1,) + A1.shape)
        A2r = jnp.broadcast_to(A2[None], (c2,) + A2.shape)
        flat = kops.cross_dot(
            A1r.reshape(1, c1 * n1, r, -1), S1.reshape(1, c1 * n1, r, -1),
            A2r.reshape(1, c2 * n2, r, -1), S2.reshape(1, c2 * n2, r, -1))
        # [(c,n), (d,m)] → [n, m, c, d]
        T = flat.reshape(c1, n1, c2, n2).transpose(1, 3, 0, 2)
    else:
        ga = jnp.einsum("nra,msa->nmrs", A1, A2)
        T = jnp.einsum("nmrs,cnrb,dmsb->nmcd", ga, S1, S2)
    d = {"w": T}
    if bias:
        Sb1 = jnp.sum(S1, axis=2)                             # [C, N, b]
        Sb2 = jnp.sum(S2, axis=2)
        d["b"] = jnp.einsum("cnb,dmb->nmcd", Sb1, Sb2)
    return {"ggn_gram": d}


def dense_curv_stats(A, S, exts, cfg: ExtensionConfig, bias: bool, ext_prefix):
    """Second-order stats for a Dense layer from backpropagated factor ``S``.

    A: [N, R, a], S: [C̃, N, R, b] (leading factor axis, carries 1/√m).
    diag contribution: Σ_{c,n} (Σ_r A[n,r,i] S[c,n,r,j])∘²  (Eq. 19/22).
    Kron B factor: R · Σ_{c,n,r} S Sᵀ (Grosse–Martens spatial scaling; exact
    for R=1 where it reduces to App. A.2's B_KFLR/B_KFAC).
    Per-sample GGN trace: Σ_{c,a,b} of the squared contribution per n.

    With ``cfg.use_kernels`` (and ``cfg.use_fused``, the default) every
    requested weight-block curvature statistic comes out of ONE fused
    Pallas launch over (A, S) — the static
    :class:`~repro.core.extensions.FusedSecondMask` selects the outputs,
    and the ``S`` tile is read once for all of them.  Rank-1 (R==1) layers
    skip the launch for cheaper closed forms, as in
    :func:`dense_first_order_stats`.  With
    ``use_fused=False`` each statistic runs its own legacy path (the
    broadcast ``per_sample_sq_sum`` for the diagonal, a jnp einsum for the
    B-factor/trace) — the benchmark baseline.  Bias stats are cheap
    row-sums and stay in jnp.  The MC sweep lands here too: its sample
    axis C̃ simply stands in for the class axis.
    """
    names = {e.name for e in exts}
    if ext_prefix == "ntk":
        # The raw-Jacobian ('jac') sweep lands here with identity
        # cotangents: pairwise kernel blocks instead of curvature sums.
        return _dense_ntk_stats(A, S, names, cfg, bias)
    out = {}
    c, n, r, b = S.shape
    Af, Sf = _f32(A), _f32(S)
    diag_name = "diag_ggn_mc" if ext_prefix == "mc" else "diag_ggn"
    kron_name = "kfac" if ext_prefix == "mc" else "kflr"
    mask = second_order_mask(names)
    # Rank-1 (R==1) layers skip the fused launch, mirroring the first-order
    # path: every statistic separates over the unit sequence axis (diag via
    # the rank-1 branch of per_sample_sq_sum, kron is already the plain
    # SᵀS einsum, trace factors into a product of row norms), which beats a
    # kernel launch that pads R from 1 to a full sublane.
    rank1 = A.shape[1] == 1
    kmask = FusedSecondMask() if rank1 else mask
    fused = None
    if cfg.use_kernels and cfg.use_fused and kmask.any():
        from repro.kernels import ops as kops

        fused = kops.fused_second_order(Af, Sf, **kmask.wants())
    if diag_name in names:
        if fused is not None:
            w = fused["diag"]
        else:
            Arep = jnp.broadcast_to(A[None], (c,) + A.shape).reshape(c * n, r, -1)
            Srep = Sf.reshape(c * n, r, b)
            w = per_sample_sq_sum(Arep, Srep, use_kernels=cfg.use_kernels)
        d = {"w": w}
        if bias:
            ssum = jnp.sum(Sf, axis=2)
            d["b"] = jnp.sum(ssum * ssum, axis=(0, 1))
        out[diag_name] = d
    if kron_name in names:
        ssq = (fused["kron"] if fused is not None
               else jnp.einsum("cnri,cnrj->ij", Sf, Sf))
        b_fac = ssq * float(r)
        out[kron_name] = {"w": {"B": b_fac}}
        if bias:
            out[kron_name]["b"] = {"B": b_fac}
    if "ggn_trace" in names:
        if fused is not None:
            tr = fused["trace"]
        elif rank1:
            # t² = A²[n,a]·S²[c,n,b] separates: trace_n = ‖A_n‖²·Σ_cb S².
            tr = (jnp.sum(Af[:, 0] ** 2, -1)
                  * jnp.sum(Sf[:, :, 0] ** 2, axis=(0, 2)))
        else:
            t = jnp.einsum("nra,cnrb->cnab", Af, Sf)
            tr = jnp.sum(t * t, axis=(0, 2, 3))
        d = {"w": tr}
        if bias:
            ssum = jnp.sum(Sf, axis=2)  # [C, N, b]
            d["b"] = jnp.sum(ssum * ssum, axis=(0, 2))
        out["ggn_trace"] = d
    if "ggn_gram" in names:
        out.update(_dense_ggn_gram_stats(A, S, cfg, bias))
    return out


# ---------------------------------------------------------------------------
# base Module
# ---------------------------------------------------------------------------


class Module:
    """Base module: parameter-free, vjp-backed fallbacks."""

    def init(self, key):
        return ()

    def param_axes(self):
        """Logical sharding axis names, mirroring the params pytree."""
        return ()

    def apply(self, params, x):
        raise NotImplementedError

    def forward_tape(self, params, x):
        return self.apply(params, x), x

    # -- first-order sweep ---------------------------------------------------
    def backward(self, params, tape, g, exts, cfg):
        x = tape
        _, vjp = jax.vjp(self.apply, params, x)
        gp, gx = vjp(g)
        stats = self.generic_stats(params, tape, g, exts, cfg)
        return gx, gp, stats

    def generic_stats(self, params, tape, g, exts, cfg):
        """Per-sample stats for small mixer params via vmapped VJP.

        Only used for parameter-bearing modules without hand-written
        formulas; cost is one extra per-sample VJP of this module alone.
        """
        if not jax.tree_util.tree_leaves(params):
            return {}
        names = {e.name for e in exts}
        wanted = names & {"batch_grad", "batch_l2", "second_moment",
                          "variance", "batch_dot"}
        if not wanted:
            return {}
        x = tape

        def per_sample(gx, xx):
            _, vjp = jax.vjp(lambda p: self.apply(p, jax.tree.map(lambda a: a[None], xx)), params)
            return vjp(jax.tree.map(lambda a: a[None], gx))[0]

        pg = jax.vmap(per_sample)(g, x)  # params-tree with leading N
        out = {}
        if "batch_grad" in names:
            out["batch_grad"] = pg
        if "second_moment" in names or "variance" in names:
            out["_sum_grad2"] = jax.tree.map(lambda a: jnp.sum(_f32(a) ** 2, 0), pg)
        if "batch_l2" in names:
            out["batch_l2"] = jax.tree.map(
                lambda a: jnp.sum(_f32(a).reshape(a.shape[0], -1) ** 2, -1), pg
            )
        if "batch_dot" in names:
            axes, cross = _pair_split(cfg)
            out["batch_dot"] = jax.tree.map(
                lambda a: _pairwise_rows(a, axes, cross), pg
            )
        return out

    # -- matrix-Jacobian products (paper §2.1's missing primitive) -----------
    def jac_t_mat(self, params, tape, M):
        x = tape
        _, vjp = jax.vjp(lambda xx: self.apply(params, xx), x)
        return jax.vmap(lambda m: vjp(m)[0])(M)

    # -- GGN-factor sweep ------------------------------------------------------
    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        return self.jac_t_mat(params, tape, S), {}

    # -- chain-only sweeps ----------------------------------------------------
    def kfra_backward(self, params, tape, Gbar, exts, cfg):
        raise UnsupportedSweep(f"KFRA unsupported for {type(self).__name__}")

    def kfra_partials(self, params, tape, cfg):
        """Batch-mean chain partials of the Ḡ recursion (streaming KFRA).

        Everything batch-dependent in Eq. 24 is a batch expectation — the
        Dense A factor, the activation's E_n[f'f'ᵀ] mask outer.  The
        accumulated lane streams these raw means microbatch by microbatch
        (sample-count-weighted, see ``reducers.MeanReducer``) and replays
        the batch-independent chain on the accumulated *global* means via
        :meth:`kfra_apply` — exact, because the recursion is linear in
        each partial.
        """
        raise UnsupportedSweep(f"KFRA unsupported for {type(self).__name__}")

    def kfra_apply(self, params, Gbar, partials, exts, cfg):
        """Replay one Ḡ recursion step from accumulated chain partials.

        Returns ``(Gbar_in, stats)`` exactly like :meth:`kfra_backward`,
        but every batch expectation is read from ``partials`` (a
        :meth:`kfra_partials` tree, already globally averaged) instead of
        the tape — ``kfra_backward(tape) ==
        kfra_apply(kfra_partials(tape))`` by construction.
        """
        raise UnsupportedSweep(f"KFRA unsupported for {type(self).__name__}")

    def hess_backward(self, params, tape, g, factors, exts, cfg):
        raise UnsupportedSweep(
            f"DiagHessian unsupported for {type(self).__name__}"
        )

    # -- serving --------------------------------------------------------------
    def decode_step(self, params, x, cache):
        """Single-token decode. Stateless modules apply as-is."""
        return self.apply(params, x), cache

    def init_cache(self, params, batch, max_len, dtype):
        return ()

    def cache_axes(self):
        """Logical axis names for the decode cache, mirroring init_cache."""
        return ()


class Lambda(Module):
    """Wrap a parameter-free function (reshapes, rotations, masking...)."""

    def __init__(self, fn: Callable, step_fn: Optional[Callable] = None):
        self.fn = fn
        self.step_fn = step_fn

    def apply(self, params, x):
        return self.fn(x)

    def decode_step(self, params, x, cache):
        if self.step_fn is not None:
            return self.step_fn(x), cache
        return self.fn(x), cache


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


class Dense(Module):
    """y = x @ W (+ b), x: [N, ..., d_in]."""

    def __init__(self, d_in, d_out, use_bias=True, dtype=jnp.float32,
                 init_scale=None, axes=("embed", "mlp")):
        self.d_in, self.d_out, self.use_bias = d_in, d_out, use_bias
        self.dtype = dtype
        self.init_scale = init_scale
        self.axes = axes

    def init(self, key):
        scale = self.init_scale
        if scale is None:
            scale = self.d_in ** -0.5
        w = (jax.random.normal(key, (self.d_in, self.d_out), jnp.float32)
             * scale).astype(self.dtype)
        p = {"w": w}
        if self.use_bias:
            p["b"] = jnp.zeros((self.d_out,), self.dtype)
        return p

    def param_axes(self):
        p = {"w": Axes(tuple(self.axes))}
        if self.use_bias:
            p["b"] = Axes((self.axes[1],))
        return p

    def apply(self, params, x):
        y = x @ params["w"]
        if self.use_bias:
            y = y + params["b"]
        return y

    def backward(self, params, tape, g, exts, cfg):
        x = tape
        A, B = _nra(x), _nra(g)
        gw = jnp.einsum("nra,nrb->ab", _f32(A), _f32(B)).astype(params["w"].dtype)
        grads = {"w": gw}
        if self.use_bias:
            grads["b"] = jnp.sum(_f32(B), axis=(0, 1)).astype(params["w"].dtype)
        g_in = (g @ params["w"].T).reshape(x.shape)
        stats = dense_first_order_stats(A, B, exts, cfg, self.use_bias) if exts else {}
        return g_in, grads, stats

    def jac_t_mat(self, params, tape, M):
        return M @ params["w"].T

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        x = tape
        A = _nra(x)
        c = S.shape[0]
        Sr = S.reshape((c,) + A.shape[:2] + (self.d_out,))
        stats = dense_curv_stats(A, Sr, exts, cfg, self.use_bias, ext_prefix)
        return self.jac_t_mat(params, tape, S), stats

    def kfra_backward(self, params, tape, Gbar, exts, cfg):
        return self.kfra_apply(params, Gbar,
                               self.kfra_partials(params, tape, cfg),
                               exts, cfg)

    def kfra_partials(self, params, tape, cfg):
        A = _nra(tape)
        n, r, _ = A.shape
        return {"a": jnp.einsum("nra,nrc->ac", _f32(A), _f32(A))
                / float(n * r)}

    def kfra_apply(self, params, Gbar, partials, exts, cfg):
        stats = {}
        if "kfra" in {e.name for e in exts}:
            d = {"w": {"A": partials["a"], "B": Gbar}}
            if self.use_bias:
                d["b"] = {"B": Gbar}
            stats["kfra"] = d
        w = _f32(params["w"])
        return w @ Gbar @ w.T, stats

    def hess_backward(self, params, tape, g, factors, exts, cfg):
        x = tape
        A, B = _nra(x), _nra(g)
        diag_w = jnp.zeros((self.d_in, self.d_out), jnp.float32)
        diag_b = jnp.zeros((self.d_out,), jnp.float32)
        new_factors = []
        for S, sign in factors:
            c = S.shape[0]
            Sr = S.reshape((c,) + A.shape[:2] + (self.d_out,))
            Arep = jnp.broadcast_to(A[None], (c,) + A.shape).reshape(c * A.shape[0], A.shape[1], -1)
            Srep = _f32(Sr).reshape(c * A.shape[0], A.shape[1], self.d_out)
            diag_w = diag_w + sign * per_sample_sq_sum(Arep, Srep)
            ssum = jnp.sum(_f32(Sr), axis=2)
            diag_b = diag_b + sign * jnp.sum(ssum * ssum, axis=(0, 1))
            new_factors.append((self.jac_t_mat(params, tape, S), sign))
        g_in, grads, _ = self.backward(params, tape, g, (), cfg)
        stats = {"diag_hessian": {"w": diag_w}}
        if self.use_bias:
            stats["diag_hessian"]["b"] = diag_b
        return g_in, new_factors, stats

    def decode_step(self, params, x, cache):
        return self.apply(params, x), cache


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def _token_sq(tok, g):
    """Per-position share of the squared per-sample embedding gradient.

    tok: [N, ...] token ids, g: [..., N, ..., d] rows at those positions.
    The per-sample gradient row of token id v is h = Σ of the sample's rows
    at positions holding v; giving each of its ``count`` positions h²/count
    makes any sum over positions equal the sum over (sample, id) of h² —
    without the [N, V, d] per-sample scatter, which at LM vocabularies
    outgrows device memory.  Returns [..., N, T, d] float32 (T = flattened
    positions per sample).
    """
    n = tok.shape[0]
    t2 = tok.reshape(n, -1)
    g2 = _f32(g).reshape(g.shape[:g.ndim - tok.ndim - 1] + t2.shape
                         + g.shape[-1:])
    eq = (t2[:, :, None] == t2[:, None, :]).astype(jnp.float32)  # [N, T, T]
    h = jnp.einsum("ntu,...nud->...ntd", eq, g2)
    return h * h / jnp.sum(eq, axis=-1)[..., None]


class Embedding(Module):
    """Token embedding lookup; input int tokens [N, T] -> [N, T, d]."""

    def __init__(self, vocab, d, dtype=jnp.float32, scale=None,
                 axes=("vocab", "embed")):
        self.vocab, self.d = vocab, d
        self.dtype = dtype
        self.scale = scale if scale is not None else d ** -0.5
        self.axes = axes

    def init(self, key):
        w = (jax.random.normal(key, (self.vocab, self.d), jnp.float32)
             * self.scale).astype(self.dtype)
        return {"w": w}

    def param_axes(self):
        return {"w": Axes(tuple(self.axes))}

    def apply(self, params, x):
        return jnp.take(params["w"], x, axis=0)

    def backward(self, params, tape, g, exts, cfg):
        tok = tape
        gw = jnp.zeros((self.vocab, self.d), jnp.float32).at[tok.reshape(-1)].add(
            _f32(g).reshape(-1, self.d)
        )
        grads = {"w": gw.astype(params["w"].dtype)}
        stats = {}
        names = {e.name for e in exts}
        if "second_moment" in names or "variance" in names:
            stats["_sum_grad2"] = {"w": self._scatter_sq(tok, g)}
        if "batch_l2" in names:
            stats["batch_l2"] = {"w": jnp.sum(_token_sq(tok, g), axis=(1, 2))}
        if names & {"batch_grad", "batch_dot"}:
            def scatter_n(tok_n, g_n):
                return jnp.zeros((self.vocab, self.d), jnp.float32).at[
                    tok_n.reshape(-1)
                ].add(_f32(g_n).reshape(-1, self.d))

            pg = jax.vmap(scatter_n)(tok, g)  # [N, V, d] — small-vocab path
            if "batch_grad" in names:
                stats["batch_grad"] = {"w": pg}
            if "batch_dot" in names:
                stats["batch_dot"] = {"w": _pairwise_rows(pg, *_pair_split(cfg))}
        if "kfac" in names or "kflr" in names:
            counts = jnp.zeros((self.vocab,), jnp.float32).at[tok.reshape(-1)].add(1.0)
            stats["_kron_a"] = {"w": counts / float(tok.size)}  # diagonal A
        return None, grads, stats

    def jac_t_mat(self, params, tape, M):
        return None

    def _scatter_sq(self, tok, g):
        """Σ over samples (and leading factor axes) of the squared
        per-sample embedding gradient, [V, d], from per-position rows."""
        q = _token_sq(tok, g)
        q = jnp.sum(q.reshape((-1,) + q.shape[-3:]), axis=0)
        return jnp.zeros((self.vocab, self.d), jnp.float32).at[
            tok.reshape(-1)].add(q.reshape(-1, self.d))

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        tok = tape
        names = {e.name for e in exts}
        stats = {}
        diag_name = "diag_ggn_mc" if ext_prefix == "mc" else "diag_ggn"
        kron_name = "kfac" if ext_prefix == "mc" else "kflr"
        if diag_name in names:
            stats[diag_name] = {"w": self._scatter_sq(tok, S)}
        if kron_name in names:
            Sf = _f32(S)
            b_fac = jnp.einsum("cnti,cntj->ij", Sf, Sf) * float(S.shape[2])
            counts = jnp.zeros((self.vocab,), jnp.float32).at[tok.reshape(-1)].add(1.0)
            stats[kron_name] = {"w": {"A_diag": counts / float(tok.size), "B": b_fac}}
        return None, stats


# ---------------------------------------------------------------------------
# Norms and activations
# ---------------------------------------------------------------------------


class RMSNorm(Module):
    def __init__(self, d, eps=1e-6, dtype=jnp.float32):
        self.d, self.eps, self.dtype = d, eps, dtype

    def init(self, key):
        return {"g": jnp.ones((self.d,), self.dtype)}

    def param_axes(self):
        return {"g": Axes(("embed",))}

    def _norm(self, x):
        mu = jnp.mean(_f32(x) ** 2, axis=-1, keepdims=True)
        r = jax.lax.rsqrt(mu + self.eps)
        return (_f32(x) * r).astype(x.dtype), r

    def apply(self, params, x):
        xh, _ = self._norm(x)
        return xh * params["g"]

    def forward_tape(self, params, x):
        xh, r = self._norm(x)
        return xh * params["g"], (xh, r)

    def backward(self, params, tape, g, exts, cfg):
        xh, r = tape
        u = _f32(g) * _f32(params["g"])
        xhf = _f32(xh)
        g_in = (r * (u - xhf * jnp.mean(xhf * u, axis=-1, keepdims=True))).astype(g.dtype)
        per_sample = jnp.sum(
            _f32(xh).reshape(xh.shape[0], -1, self.d)
            * _f32(g).reshape(g.shape[0], -1, self.d),
            axis=1,
        )  # [N, d]
        grads = {"g": jnp.sum(per_sample, 0).astype(params["g"].dtype)}
        stats = {}
        names = {e.name for e in exts}
        if "batch_grad" in names:
            stats["batch_grad"] = {"g": per_sample}
        if "second_moment" in names or "variance" in names:
            stats["_sum_grad2"] = {"g": jnp.sum(per_sample ** 2, 0)}
        if "batch_l2" in names:
            stats["batch_l2"] = {"g": jnp.sum(per_sample ** 2, -1)}
        if "batch_dot" in names:
            stats["batch_dot"] = {"g": _pairwise_rows(
                per_sample, *_pair_split(cfg))}
        return g_in, grads, stats

    def jac_t_mat(self, params, tape, M):
        xh, r = tape
        u = _f32(M) * _f32(params["g"])
        xhf = _f32(xh)[None]
        return (r[None] * (u - xhf * jnp.mean(xhf * u, axis=-1, keepdims=True))).astype(M.dtype)

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        xh, r = tape
        names = {e.name for e in exts}
        stats = {}
        diag_name = "diag_ggn_mc" if ext_prefix == "mc" else "diag_ggn"
        if diag_name in names:
            t = jnp.einsum(
                "nrd,cnrd->cnd",
                _f32(xh).reshape(xh.shape[0], -1, self.d),
                _f32(S).reshape(S.shape[:2] + (-1, self.d)),
            )
            stats[diag_name] = {"g": jnp.sum(t * t, axis=(0, 1))}
        return self.jac_t_mat(params, tape, S), stats


class GroupRMSNorm(RMSNorm):
    """RMS-normalize within G groups of the last axis (per-head GroupNorm
    à la RWKV); scale is per-channel.  Shard-local when heads are TP-sharded
    — replaces a full-width norm that would all-gather every layer."""

    def __init__(self, d, groups, eps=1e-6, dtype=jnp.float32):
        super().__init__(d, eps=eps, dtype=dtype)
        self.groups = groups

    def _norm(self, x):
        g = self.groups
        xg = _f32(x).reshape(x.shape[:-1] + (g, self.d // g))
        mu = jnp.mean(xg ** 2, axis=-1, keepdims=True)
        r = jax.lax.rsqrt(mu + self.eps)
        xh = (xg * r).reshape(x.shape)
        return xh.astype(x.dtype), r

    def backward(self, params, tape, g, exts, cfg):
        xh, r = tape
        gr = self.groups
        u = (_f32(g) * _f32(params["g"])).reshape(g.shape[:-1] + (gr, -1))
        xhf = _f32(xh).reshape(u.shape)
        g_in = (r * (u - xhf * jnp.mean(xhf * u, axis=-1, keepdims=True)))
        g_in = g_in.reshape(g.shape).astype(g.dtype)
        per_sample = jnp.sum(
            _f32(xh).reshape(xh.shape[0], -1, self.d)
            * _f32(g).reshape(g.shape[0], -1, self.d),
            axis=1,
        )
        grads = {"g": jnp.sum(per_sample, 0).astype(params["g"].dtype)}
        stats = {}
        names = {e.name for e in exts}
        if "batch_grad" in names:
            stats["batch_grad"] = {"g": per_sample}
        if "second_moment" in names or "variance" in names:
            stats["_sum_grad2"] = {"g": jnp.sum(per_sample ** 2, 0)}
        if "batch_l2" in names:
            stats["batch_l2"] = {"g": jnp.sum(per_sample ** 2, -1)}
        if "batch_dot" in names:
            stats["batch_dot"] = {"g": _pairwise_rows(
                per_sample, *_pair_split(cfg))}
        return g_in, grads, stats

    def jac_t_mat(self, params, tape, M):
        xh, r = tape
        gr = self.groups
        shp = M.shape[:-1] + (gr, self.d // gr)
        u = (_f32(M) * _f32(params["g"])).reshape(shp)
        xhf = _f32(xh).reshape((1,) + xh.shape[:-1] + (gr, self.d // gr))
        out = r[None] * (u - xhf * jnp.mean(xhf * u, axis=-1, keepdims=True))
        return out.reshape(M.shape).astype(M.dtype)


class LayerNorm(Module):
    def __init__(self, d, eps=1e-5, dtype=jnp.float32):
        self.d, self.eps, self.dtype = d, eps, dtype

    def init(self, key):
        return {"g": jnp.ones((self.d,), self.dtype),
                "b": jnp.zeros((self.d,), self.dtype)}

    def param_axes(self):
        return {"g": Axes(("embed",)), "b": Axes(("embed",))}

    def _norm(self, x):
        xf = _f32(x)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)

    def apply(self, params, x):
        return self._norm(x) * params["g"] + params["b"]

    def forward_tape(self, params, x):
        return self.apply(params, x), x

    def backward(self, params, tape, g, exts, cfg):
        x = tape
        _, vjp = jax.vjp(self.apply, params, x)
        gp, gx = vjp(g)
        xh = self._norm(x)
        per_g = jnp.sum(
            _f32(xh).reshape(x.shape[0], -1, self.d)
            * _f32(g).reshape(g.shape[0], -1, self.d),
            axis=1,
        )
        per_b = jnp.sum(_f32(g).reshape(g.shape[0], -1, self.d), axis=1)
        stats = {}
        names = {e.name for e in exts}
        if "batch_grad" in names:
            stats["batch_grad"] = {"g": per_g, "b": per_b}
        if "second_moment" in names or "variance" in names:
            stats["_sum_grad2"] = {"g": jnp.sum(per_g ** 2, 0), "b": jnp.sum(per_b ** 2, 0)}
        if "batch_l2" in names:
            stats["batch_l2"] = {"g": jnp.sum(per_g ** 2, -1), "b": jnp.sum(per_b ** 2, -1)}
        if "batch_dot" in names:
            axes, cross = _pair_split(cfg)
            stats["batch_dot"] = {"g": _pairwise_rows(per_g, axes, cross),
                                  "b": _pairwise_rows(per_b, axes, cross)}
        return gx, gp, stats

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        x = tape
        names = {e.name for e in exts}
        stats = {}
        diag_name = "diag_ggn_mc" if ext_prefix == "mc" else "diag_ggn"
        if diag_name in names:
            xh = self._norm(x)
            t = jnp.einsum(
                "nrd,cnrd->cnd",
                _f32(xh).reshape(x.shape[0], -1, self.d),
                _f32(S).reshape(S.shape[:2] + (-1, self.d)),
            )
            sb = jnp.sum(_f32(S).reshape(S.shape[:2] + (-1, self.d)), axis=2)
            stats[diag_name] = {
                "g": jnp.sum(t * t, axis=(0, 1)),
                "b": jnp.sum(sb * sb, axis=(0, 1)),
            }
        return self.jac_t_mat(params, x, S), stats

    def jac_t_mat(self, params, tape, M):
        x = tape if not isinstance(tape, tuple) else tape[0]
        _, vjp = jax.vjp(lambda xx: self.apply(params, xx), x)
        return jax.vmap(lambda m: vjp(m)[0])(M)


_ACTS = {
    "relu": (jax.nn.relu, lambda x: (x > 0).astype(jnp.float32),
             lambda x: jnp.zeros_like(x, jnp.float32)),
    "gelu": (jax.nn.gelu,
             lambda x: jax.vmap(jax.grad(lambda v: jax.nn.gelu(v)))(x.reshape(-1)).reshape(x.shape),
             lambda x: jax.vmap(jax.grad(jax.grad(lambda v: jax.nn.gelu(v))))(x.reshape(-1)).reshape(x.shape)),
    "silu": (jax.nn.silu,
             lambda x: jax.vmap(jax.grad(lambda v: jax.nn.silu(v)))(x.reshape(-1)).reshape(x.shape),
             lambda x: jax.vmap(jax.grad(jax.grad(lambda v: jax.nn.silu(v))))(x.reshape(-1)).reshape(x.shape)),
    "sigmoid": (jax.nn.sigmoid,
                lambda x: jax.nn.sigmoid(x) * (1 - jax.nn.sigmoid(x)),
                lambda x: jax.nn.sigmoid(x) * (1 - jax.nn.sigmoid(x)) * (1 - 2 * jax.nn.sigmoid(x))),
    "tanh": (jnp.tanh,
             lambda x: 1 - jnp.tanh(x) ** 2,
             lambda x: -2 * jnp.tanh(x) * (1 - jnp.tanh(x) ** 2)),
    "identity": (lambda x: x,
                 lambda x: jnp.ones_like(x, jnp.float32),
                 lambda x: jnp.zeros_like(x, jnp.float32)),
}


class Activation(Module):
    """Elementwise activation with first & second derivative (Eq. 25/26)."""

    def __init__(self, name):
        self.name = name
        self.fn, self.d1, self.d2 = _ACTS[name]

    def apply(self, params, x):
        return self.fn(x)

    def backward(self, params, tape, g, exts, cfg):
        return (self.d1(_f32(tape)) * _f32(g)).astype(g.dtype), (), {}

    def jac_t_mat(self, params, tape, M):
        return (self.d1(_f32(tape))[None] * _f32(M)).astype(M.dtype)

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        return self.jac_t_mat(params, tape, S), {}

    def kfra_backward(self, params, tape, Gbar, exts, cfg):
        return self.kfra_apply(params, Gbar,
                               self.kfra_partials(params, tape, cfg),
                               exts, cfg)

    def kfra_partials(self, params, tape, cfg):
        d1 = self.d1(_f32(tape)).reshape(tape.shape[0], -1, tape.shape[-1])
        n, r, h = d1.shape
        # E_n[f'_n f'_nᵀ] (diagonal per-sample Jacobians).  The Ḡ
        # recursion needs the expectation over the *global* batch at every
        # step — a local mean would compound shard bias layer by layer, so
        # under a sharded sweep the expectation is pmean'd here, in-line,
        # not post-hoc.
        outer = jnp.einsum("nri,nrj->ij", d1, d1) / float(n * r)
        axes = getattr(cfg, "shard_axes", None)
        if axes:
            outer = jax.lax.pmean(outer, axes)
        return {"m": outer}

    def kfra_apply(self, params, Gbar, partials, exts, cfg):
        # Ḡ_in = Ḡ ∘ E_n[f'_n f'_nᵀ]
        return Gbar * partials["m"], {}

    def hess_backward(self, params, tape, g, factors, exts, cfg):
        x = _f32(tape)
        d1 = self.d1(x)
        new_factors = [((d1[None] * _f32(S)).astype(S.dtype), sign)
                       for S, sign in factors]
        # residual: R = diag(f''(x) ∘ δ) per sample-unit (Eq. 26)
        resid = self.d2(x) * _f32(g)
        h = x.shape[-1]
        pos = jnp.sqrt(jnp.maximum(resid, 0.0))
        neg = jnp.sqrt(jnp.maximum(-resid, 0.0))
        eye = jnp.eye(h, dtype=jnp.float32)
        shape = (h,) + x.shape
        P = jnp.moveaxis(pos[..., None] * eye, -1, 0).reshape(shape)
        Nf = jnp.moveaxis(neg[..., None] * eye, -1, 0).reshape(shape)
        new_factors.append((P, 1.0))
        new_factors.append((Nf, -1.0))
        g_in = (d1 * _f32(g)).astype(g.dtype)
        return g_in, new_factors, {}


# ---------------------------------------------------------------------------
# combinators
# ---------------------------------------------------------------------------


def _rule(mod: Module):
    """Named scope ``rule.<Module class>`` for a child's rule: its ops carry
    the layer type in their ``op_name``, nested inside the sweep scopes."""
    return jax.named_scope(f"rule.{type(mod).__name__}")


class Sequential(Module):
    def __init__(self, mods: Sequence[Module]):
        self.mods = list(mods)

    def init(self, key):
        keys = jax.random.split(key, len(self.mods))
        return tuple(m.init(k) for m, k in zip(self.mods, keys))

    def param_axes(self):
        return tuple(m.param_axes() for m in self.mods)

    def apply(self, params, x):
        for m, p in zip(self.mods, params):
            x = m.apply(p, x)
        return x

    def forward_tape(self, params, x):
        tapes = []
        for m, p in zip(self.mods, params):
            with _rule(m):
                x, t = m.forward_tape(p, x)
            tapes.append(t)
        return x, tuple(tapes)

    def backward(self, params, tape, g, exts, cfg):
        grads, stats = [None] * len(self.mods), [None] * len(self.mods)
        for i in reversed(range(len(self.mods))):
            with _rule(self.mods[i]):
                g, grads[i], stats[i] = self.mods[i].backward(
                    params[i], tape[i], g, exts, cfg
                )
            if g is None and i > 0:
                raise ValueError("cotangent vanished mid-chain")
        return g, tuple(grads), tuple(stats)

    def jac_t_mat(self, params, tape, M):
        for i in reversed(range(len(self.mods))):
            with _rule(self.mods[i]):
                M = self.mods[i].jac_t_mat(params[i], tape[i], M)
        return M

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        curv = [None] * len(self.mods)
        for i in reversed(range(len(self.mods))):
            with _rule(self.mods[i]):
                S, curv[i] = self.mods[i].curv_backward(
                    params[i], tape[i], S, exts, cfg, ext_prefix
                )
        return S, tuple(curv)

    def kfra_backward(self, params, tape, Gbar, exts, cfg):
        stats = [None] * len(self.mods)
        for i in reversed(range(len(self.mods))):
            with _rule(self.mods[i]):
                Gbar, stats[i] = self.mods[i].kfra_backward(
                    params[i], tape[i], Gbar, exts, cfg
                )
        return Gbar, tuple(stats)

    def kfra_partials(self, params, tape, cfg):
        return tuple(m.kfra_partials(p, t, cfg)
                     for m, p, t in zip(self.mods, params, tape))

    def kfra_apply(self, params, Gbar, partials, exts, cfg):
        stats = [None] * len(self.mods)
        for i in reversed(range(len(self.mods))):
            Gbar, stats[i] = self.mods[i].kfra_apply(
                params[i], Gbar, partials[i], exts, cfg
            )
        return Gbar, tuple(stats)

    def hess_backward(self, params, tape, g, factors, exts, cfg):
        stats = [None] * len(self.mods)
        for i in reversed(range(len(self.mods))):
            g, factors, stats[i] = self.mods[i].hess_backward(
                params[i], tape[i], g, factors, exts, cfg
            )
        return g, factors, tuple(stats)

    def decode_step(self, params, x, cache):
        new_cache = list(cache)
        for i, (m, p) in enumerate(zip(self.mods, params)):
            x, new_cache[i] = m.decode_step(p, x, cache[i])
        return x, tuple(new_cache)

    def init_cache(self, params, batch, max_len, dtype):
        return tuple(
            m.init_cache(p, batch, max_len, dtype)
            for m, p in zip(self.mods, params)
        )

    def cache_axes(self):
        return tuple(m.cache_axes() for m in self.mods)


class Parallel(Module):
    """Apply each child to the same input; output = tuple of child outputs."""

    def __init__(self, mods: Sequence[Module]):
        self.mods = list(mods)

    def init(self, key):
        keys = jax.random.split(key, len(self.mods))
        return tuple(m.init(k) for m, k in zip(self.mods, keys))

    def param_axes(self):
        return tuple(m.param_axes() for m in self.mods)

    def apply(self, params, x):
        return tuple(m.apply(p, x) for m, p in zip(self.mods, params))

    def forward_tape(self, params, x):
        outs, tapes = [], []
        for m, p in zip(self.mods, params):
            with _rule(m):
                o, t = m.forward_tape(p, x)
            outs.append(o)
            tapes.append(t)
        return tuple(outs), tuple(tapes)

    def backward(self, params, tape, g, exts, cfg):
        g_in = None
        grads, stats = [], []
        for m, p, t, gi in zip(self.mods, params, tape, g):
            with _rule(m):
                gx, gr, st = m.backward(p, t, gi, exts, cfg)
            grads.append(gr)
            stats.append(st)
            g_in = gx if g_in is None else jax.tree.map(jnp.add, g_in, gx)
        return g_in, tuple(grads), tuple(stats)

    def jac_t_mat(self, params, tape, M):
        out = None
        for m, p, t, Mi in zip(self.mods, params, tape, M):
            with _rule(m):
                r = m.jac_t_mat(p, t, Mi)
            out = r if out is None else jax.tree.map(jnp.add, out, r)
        return out

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        out = None
        curv = []
        for m, p, t, Si in zip(self.mods, params, tape, S):
            with _rule(m):
                r, cv = m.curv_backward(p, t, Si, exts, cfg, ext_prefix)
            curv.append(cv)
            out = r if out is None else jax.tree.map(jnp.add, out, r)
        return out, tuple(curv)

    def decode_step(self, params, x, cache):
        outs, new_cache = [], list(cache)
        for i, (m, p) in enumerate(zip(self.mods, params)):
            o, new_cache[i] = m.decode_step(p, x, cache[i])
            outs.append(o)
        return tuple(outs), tuple(new_cache)

    def init_cache(self, params, batch, max_len, dtype):
        return tuple(
            m.init_cache(p, batch, max_len, dtype)
            for m, p in zip(self.mods, params)
        )

    def cache_axes(self):
        return tuple(m.cache_axes() for m in self.mods)


class Residual(Module):
    """y = x + inner(x)."""

    def __init__(self, inner: Module):
        self.inner = inner

    def init(self, key):
        return self.inner.init(key)

    def param_axes(self):
        return self.inner.param_axes()

    def apply(self, params, x):
        return x + self.inner.apply(params, x)

    def forward_tape(self, params, x):
        with _rule(self.inner):
            y, t = self.inner.forward_tape(params, x)
        return x + y, t

    def backward(self, params, tape, g, exts, cfg):
        with _rule(self.inner):
            gx, grads, stats = self.inner.backward(params, tape, g, exts, cfg)
        return g + gx, grads, stats

    def jac_t_mat(self, params, tape, M):
        with _rule(self.inner):
            M_in = self.inner.jac_t_mat(params, tape, M)
        return M + M_in

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        with _rule(self.inner):
            S_in, curv = self.inner.curv_backward(params, tape, S, exts, cfg,
                                                  ext_prefix)
        return S + S_in, curv

    def decode_step(self, params, x, cache):
        y, cache = self.inner.decode_step(params, x, cache)
        return x + y, cache

    def init_cache(self, params, batch, max_len, dtype):
        return self.inner.init_cache(params, batch, max_len, dtype)

    def cache_axes(self):
        return self.inner.cache_axes()


_PER_SAMPLE_KEYS = ("batch_grad", "batch_l2", "batch_dot")


def _swap_sample_axis(stats):
    """Scan stacks stats as [L, N, ...]; per-sample stats mirror the stacked
    params ([L, ...]) with a *leading* sample axis, i.e. [N, L, ...]."""

    def rec(node, under_ps):
        if isinstance(node, dict):
            return {k: rec(v, under_ps or k in _PER_SAMPLE_KEYS)
                    for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            return tuple(rec(c, under_ps) for c in node)
        if node is None or not hasattr(node, "ndim"):
            return node
        return jnp.moveaxis(node, 0, 1) if under_ps else node

    return rec(stats, False)


class ScanStack(Module):
    """L homogeneous blocks, scanned — generalized backprop through lax.scan.

    Beyond the paper: BackPACK v1 cannot handle weight sharing or scan-style
    stacking; here tapes/stats are stacked along a leading layer axis and the
    cotangent (resp. GGN factor) is the scan carry.
    """

    def __init__(self, block: Module, n_layers: int, remat: bool = False,
                 seq_constraint=None):
        self.block, self.L = block, n_layers
        self.remat = remat
        self.seq_constraint = seq_constraint

    def _constrain(self, z):
        if self.seq_constraint is None:
            return z
        wsc = jax.lax.with_sharding_constraint
        if isinstance(z, tuple):
            return (wsc(z[0], self.seq_constraint),) + z[1:]
        return wsc(z, self.seq_constraint)

    def init(self, key):
        keys = jax.random.split(key, self.L)
        return jax.vmap(self.block.init)(keys)

    def param_axes(self):
        return jax.tree.map(lambda a: a.prepend("layers"),
                            self.block.param_axes(), is_leaf=is_axes)

    def apply(self, params, x):
        f = self.block.apply
        if self.remat:
            f = jax.checkpoint(f)

        def body(z, p):
            return self._constrain(f(p, z)), None

        with jax.named_scope(f"scanstack_T{self.L}"):
            z, _ = jax.lax.scan(body, x, params)
        return z

    def forward_tape(self, params, x):
        def body(z, p):
            with _rule(self.block):
                z2, t = self.block.forward_tape(p, z)
            return self._constrain(z2), t

        with jax.named_scope(f"scanstack_T{self.L}"):
            z, tapes = jax.lax.scan(body, x, params)
        return z, tapes

    def backward(self, params, tape, g, exts, cfg):
        def body(gc, pt):
            p, t = pt
            with _rule(self.block):
                g_in, grads, stats = self.block.backward(p, t, gc, exts, cfg)
            return g_in, (grads, stats)

        with jax.named_scope(f"scanstack_T{self.L}"):
            g_in, (grads, stats) = jax.lax.scan(body, g, (params, tape),
                                                reverse=True)
        return g_in, grads, _swap_sample_axis(stats)

    def jac_t_mat(self, params, tape, M):
        def body(Mc, pt):
            p, t = pt
            with _rule(self.block):
                return self.block.jac_t_mat(p, t, Mc), None

        with jax.named_scope(f"scanstack_T{self.L}"):
            M_in, _ = jax.lax.scan(body, M, (params, tape), reverse=True)
        return M_in

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        def body(Sc, pt):
            p, t = pt
            with _rule(self.block):
                S_in, curv = self.block.curv_backward(p, t, Sc, exts, cfg,
                                                      ext_prefix)
            return S_in, curv

        with jax.named_scope(f"scanstack_T{self.L}"):
            S_in, curv = jax.lax.scan(body, S, (params, tape), reverse=True)
        return S_in, curv

    def decode_step(self, params, x, cache):
        def body(z, pc):
            p, c = pc
            z2, c2 = self.block.decode_step(p, z, c)
            return z2, c2

        with jax.named_scope(f"scanstack_T{self.L}"):
            x, cache = jax.lax.scan(body, x, (params, cache))
        return x, cache

    def init_cache(self, params, batch, max_len, dtype):
        return jax.vmap(
            lambda p: self.block.init_cache(p, batch, max_len, dtype)
        )(params)

    def cache_axes(self):
        return jax.tree.map(lambda a: a.prepend("layers"),
                            self.block.cache_axes(), is_leaf=is_axes)

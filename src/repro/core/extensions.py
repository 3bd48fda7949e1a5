"""Extension declarations — the quantities BackPACK extracts (paper Table 1/5).

An :class:`Extension` is a pure declaration; the engine inspects the set of
requested extensions to decide which backward sweeps to run:

  * ``first``  — the standard cotangent sweep (always runs: it also produces
                 the batch gradient).  BatchGrad / BatchL2 / SecondMoment /
                 Variance / KFAC-A-factor hook in here.
  * ``ggn``    — a symmetric-factor sweep propagating ``S`` (paper Eq. 18),
                 either with the exact loss-Hessian factorization (DiagGGN,
                 KFLR) or a Monte-Carlo one (DiagGGNMC, KFAC).
  * ``kfra``   — the batch-averaged ``Ḡ`` recursion (paper Eq. 24); chain
                 (Sequential-of-Dense/activation) models only.
  * ``hess``   — exact Hessian diagonal via residual ± factors (Eq. 25/26);
                 chain models only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

from .reducers import (
    CONCAT,
    GRAM,
    GRAM_PAIR,
    KRON,
    MOMENT_MERGE,
    PMEAN,
    PSUM,
    Reducer,
    resolve_reducer,
)


@dataclasses.dataclass(frozen=True)
class Extension:
    """One extractable quantity (a row of the paper's Table 1/5).

    An extension is a *pure declaration* the engine plans sweeps from.
    The declaration is also what the scale-out lanes act on: ``reduce``
    is the :class:`~repro.core.reducers.Reducer` protocol object saying
    how partial results combine across the batch axis, whether that axis
    is split over devices (:meth:`~repro.core.engine.SweepPlan.shard`)
    or over time (:meth:`~repro.core.engine.SweepPlan.accumulate`) —
    every lane drives the same object.

    Parameters
    ----------
    name : str
        Key of the statistic in ``Results.ext``.
    sweep : {'first', 'ggn_exact', 'ggn_mc', 'jac', 'kfra', 'hess'}
        Which backward sweep produces it.
    reduce : Reducer
        How partial results over a split batch combine — one of the
        registered protocol instances (``PSUM``, ``CONCAT``, ``GRAM``,
        ``KRON``, ``PMEAN``, ``MOMENT_MERGE`` from
        :mod:`repro.core.reducers`) or a custom :class:`Reducer`.  The
        pre-protocol string names (``reduce='gram'`` etc.) still resolve,
        with a ``DeprecationWarning`` naming the replacement instance.
    """

    name: str
    sweep: str
    reduce: Union[Reducer, str] = PSUM

    def __post_init__(self):
        # Deprecated string aliases resolve to protocol instances at
        # declaration time (resolve_reducer warns), so the engine only
        # ever sees Reducer objects.
        if not isinstance(self.reduce, Reducer):
            object.__setattr__(self, "reduce", resolve_reducer(self.reduce))


# --- first-order extensions (paper §2.2, App. A.1) -------------------------
BatchGrad = Extension("batch_grad", "first", reduce=CONCAT)
"""Per-sample gradients ``[N, *param]`` of the mean loss (paper Eq. 5)."""

BatchL2 = Extension("batch_l2", "first", reduce=CONCAT)
"""Per-sample squared gradient norms ``[N]`` via the Gram trick (Eq. 9)."""

BatchDot = Extension("batch_dot", "first", reduce=GRAM)
"""Pairwise per-sample gradient dots ``[N, N]`` — beyond-paper
(BackPACK-2.x-style) gradient-similarity / conflict telemetry."""

SecondMoment = Extension("second_moment", "first", reduce=PSUM)
"""Batch-scaled second moment ``N·Σ_n g_n²`` per parameter (Eq. 10)."""

Variance = Extension("variance", "first", reduce=MOMENT_MERGE)
"""Per-parameter gradient variance ``N·Σg² − (Σg)²`` (Eq. 11)."""

# --- second-order extensions (paper §2.3, App. A.2) -------------------------
DiagGGN = Extension("diag_ggn", "ggn_exact", reduce=PSUM)
"""Exact generalized-Gauss-Newton diagonal per parameter (Eq. 19)."""

DiagGGNMC = Extension("diag_ggn_mc", "ggn_mc", reduce=PSUM)
"""Monte-Carlo GGN diagonal (the Eq. 20 factorization of Eq. 19)."""

KFLR = Extension("kflr", "ggn_exact", reduce=KRON)
"""Kronecker-factored low-rank GGN blocks ``A ⊗ B`` with the exact
loss-Hessian factor in ``B`` (Eq. 23)."""

KFAC = Extension("kfac", "ggn_mc", reduce=KRON)
"""KFAC blocks — the Eq. 23 Kronecker pair with the MC factor in ``B``."""

KFRA = Extension("kfra", "kfra", reduce=PMEAN)
"""Kronecker factors from the batch-averaged Ḡ recursion (Eq. 24);
chain (Sequential-of-Dense/activation) models only."""

DiagHessian = Extension("diag_hessian", "hess", reduce=PSUM)
"""Exact Hessian diagonal via signed residual factors (Eq. 25/26);
chain models only."""

GGNTrace = Extension("ggn_trace", "ggn_exact", reduce=CONCAT)
"""Per-sample GGN trace ``[N]`` — beyond-paper curvature-concentration
telemetry (which samples dominate the loss curvature); a marginal-cost
output of the fused second-order kernel.  Dense-shaped layers only."""

# --- empirical NTK family (beyond-paper; Gram blocks of the Jacobian) -------
NTK = Extension("ntk", "jac", reduce=GRAM)
"""Empirical NTK row blocks ``[N, N]`` per layer parameter:
``Θ[n, m] = Σ_c ⟨J_c(x_n), J_c(x_m)⟩`` from *raw* output Jacobians
(identity cotangents — no loss weighting), summed over the class axis.
Vector-output (``z [N, C]``) models; Dense-shaped layers contribute
(like GGNTrace).  Sum the leaves for the total kernel
(:func:`repro.core.engine.ntk_total`)."""

NTKClasswise = Extension("ntk_classwise", "jac", reduce=GRAM)
"""Class-diagonal empirical NTK ``[N, N, C]`` per layer parameter:
``Θ[n, m, c] = ⟨J_c(x_n), J_c(x_m)⟩`` (asdfghjkl's class-wise kernel,
sample axes leading so the Gram reducer's row-block layout applies)."""

GGNGram = Extension("ggn_gram", "ggn_exact", reduce=GRAM_PAIR)
"""Loss-scaled logit-space GGN Gram blocks ``[N, N, C̃, C̃]`` per layer
parameter: ``K[n, m, c, c'] = ⟨Jᵀ√H-col c of x_n, Jᵀ√H-col c' of x_m⟩``
with the exact sqrt loss-Hessian factor (C̃ = U·C columns).  Summing the
leaves (:func:`repro.core.engine.gram_total`) gives the full kernel
matrix ``J' J'ᵀ`` of the half-sandwich ``J' = √Hᵀ J`` — the ``[N·C̃]``
Gram operator that kernel-space natural gradients (``repro.curv.ngd``)
solve against when ``N·C̃ ≪ P``.  Sample axes lead, so the Gram
reducer's row-block shard/stream layouts apply unchanged."""

ALL_EXTENSIONS = (
    BatchGrad,
    BatchL2,
    BatchDot,
    SecondMoment,
    Variance,
    DiagGGN,
    DiagGGNMC,
    KFLR,
    KFAC,
    KFRA,
    DiagHessian,
    GGNTrace,
    NTK,
    NTKClasswise,
    GGNGram,
)
_BY_NAME = {e.name: e for e in ALL_EXTENSIONS}


def by_name(name: str) -> Extension:
    return _BY_NAME[name]


def sweeps_needed(extensions) -> set:
    return {e.sweep for e in extensions}


def reduce_spec(extensions) -> dict:
    """``{extension name: Reducer}`` for a set of extensions.

    The protocol-object table every scale-out lane drives — see
    :mod:`repro.core.reducers` for the protocol and
    ``engine.ShardedSweepPlan`` / ``engine.AccumulatedSweepPlan`` for the
    drivers.  (Pre-protocol callers compared the values against strings;
    compare ``reduce_spec(...)[name].name`` instead.)
    """
    return {e.name: e.reduce for e in extensions}


@dataclasses.dataclass(frozen=True)
class FusedMask:
    """Static extension mask for the fused first-order kernel.

    Maps 1:1 onto the fused kernel's outputs: ``l2`` ↔ BatchL2, ``moment`` ↔
    SecondMoment/Variance (both reduce the summed squared gradient).  An
    unset flag means that output is never allocated or computed inside the
    kernel.  BatchDot's pairwise dots read BatchGrad's per-sample
    gradients when both are requested, else the ``cross_dot`` kernel.
    """

    l2: bool = False
    moment: bool = False

    def any(self) -> bool:
        return self.l2 or self.moment

    def wants(self):
        """Kwargs for ``kernels.ops.fused_first_order``."""
        return dict(want_l2=self.l2, want_moment=self.moment)


def first_order_mask(exts_or_names) -> FusedMask:
    """Fused-kernel mask for a set of extensions (or extension names)."""
    names = {e if isinstance(e, str) else e.name for e in exts_or_names}
    return FusedMask(
        l2="batch_l2" in names,
        moment=bool(names & {"second_moment", "variance"}),
    )


@dataclasses.dataclass(frozen=True)
class FusedSecondMask:
    """Static extension mask for the fused second-order (curvature) kernel.

    Maps 1:1 onto the fused kernel's outputs: ``diag`` ↔ DiagGGN/DiagGGNMC,
    ``kron`` ↔ the KFLR/KFAC output-side B-factor, ``trace`` ↔ GGNTrace.
    An unset flag means that output is never allocated or computed inside
    the kernel.
    """

    diag: bool = False
    kron: bool = False
    trace: bool = False

    def any(self) -> bool:
        return self.diag or self.kron or self.trace

    def wants(self):
        """Kwargs for ``kernels.ops.fused_second_order``."""
        return dict(want_diag=self.diag, want_kron=self.kron,
                    want_trace=self.trace)


def second_order_mask(exts_or_names) -> FusedSecondMask:
    """Fused-curvature-kernel mask for a set of extensions (or names).

    Pure, like :func:`first_order_mask`: the engine's plan and the layer
    stat hooks derive the same mask independently.  Works per sweep — the
    exact sweep's names ({diag_ggn, kflr, ggn_trace}) and the MC sweep's
    ({diag_ggn_mc, kfac}) both land on the same kernel outputs.
    """
    names = {e if isinstance(e, str) else e.name for e in exts_or_names}
    return FusedSecondMask(
        diag=bool(names & {"diag_ggn", "diag_ggn_mc"}),
        kron=bool(names & {"kflr", "kfac"}),
        trace="ggn_trace" in names,
    )


@dataclasses.dataclass(frozen=True)
class ExtensionConfig:
    """Knobs shared by the engine's sweeps.

    Parameters
    ----------
    mc_samples : int
        Number of Monte-Carlo columns C̃ for the MC loss-Hessian
        factorization (paper Eq. 20).  Cost is ~1 gradient-like sweep per
        sample; variance of DiagGGNMC/KFAC shrinks as 1/C̃.
    mc_seed : int, optional
        Deterministic PRNG seed for the MC sweep when no explicit ``rng``
        is passed to :func:`repro.core.run`.
    class_chunk : int, optional
        Chunk size over the exact factor's leading U·C axis — exact
        curvature at LM-vocabulary scale with bounded memory.
    use_kernels : bool
        Route moment formulas through the Pallas kernels in
        ``repro.kernels`` (interpret mode on CPU); pure-jnp einsums
        otherwise.
    use_fused : bool
        With ``use_kernels``: one fused kernel launch per layer per sweep
        (the default) vs the per-extension legacy path (the benchmark
        baseline).
    microbatch_size : int, optional
        Stream the sweep over microbatches of at most this many samples
        *per device* (the accumulated lane, ``SweepPlan.accumulate``):
        consumers — ``make_extended_train_step``, ``train.loop.fit``,
        the Laplace ``fit`` methods — compose lanes via
        ``engine.plan_for_batch``, which folds each extension's
        ``reduce`` spec sequentially over ``ceil(N_device /
        microbatch_size)`` slices, serving effective batches far beyond
        device memory.  Under a mesh the bound applies to the
        shard-local rows (the grid already splits the batch spatially).
    shard_axes : tuple of str, optional
        Mesh axis names the batch is sharded over — set by the sharded
        sweep lane for the body it runs under ``shard_map``; never set
        this by hand.
    """

    mc_samples: int = 1          # C̃ for the MC factorization (paper Eq. 20)
    # Explicit PRNG seed for the MC sweep (DiagGGNMC / KFAC).  When the
    # caller passes no ``rng`` to ``engine.run``, the sweep derives its key
    # from this seed — repeated runs with the same config are then
    # deterministic (required by the marglik tests; previously every MC
    # caller had to thread its own key or the run failed).  An explicit
    # ``rng`` argument still takes precedence.
    mc_seed: Optional[int] = None
    class_chunk: Optional[int] = None  # chunk size over C for exact factors
    # When True, first-order moment formulas route through the Pallas kernels
    # in repro.kernels (interpret=True on CPU); pure-jnp einsums otherwise.
    use_kernels: bool = False
    # With use_kernels=True: route all requested reductions — first-order
    # stats AND the curvature-sweep stats (GGN diag, Kronecker B-factors,
    # GGN trace) — through ONE fused kernel launch per layer per sweep (the
    # default).  False falls back to the seed's per-extension path (a
    # separate kernel or einsum per statistic) — kept as the baseline the
    # fused paths are benchmarked against.
    use_fused: bool = True
    # Stream the sweep over microbatches of at most this many samples (the
    # accumulated lane).  Consumed by make_extended_train_step /
    # train.loop.fit / the Laplace fits, which route through
    # ``SweepPlan.accumulate(ceil(N / microbatch_size))``.
    microbatch_size: Optional[int] = None
    # Mesh axis names the batch is sharded over, set by the sharded sweep
    # lane (``SweepPlan.shard``) for the body it runs under
    # ``jax.shard_map``.  When set, the engine corrects the loss's 1/M
    # normalization from shard-local to global, layer hooks compute
    # cross-shard statistics (pairwise dots, KFRA expectations) against
    # all-gathered factors, and the per-extension ``reduce`` specs are
    # applied before results leave the shard body.  None = single-device
    # semantics (the default; never set this by hand outside shard_map).
    shard_axes: Optional[tuple] = None
    # --- accumulation-driver fields -----------------------------------------
    # Set by ``AccumulatedSweepPlan.run`` for the microbatch bodies it
    # drives; never set these by hand.  ``total_units`` is the mask-aware
    # global unit count M over the WHOLE accumulated batch (the engine's
    # loss adapter rescales microbatch-local factors to the global 1/M
    # normalization), ``total_batch`` the global raw sample count N (the
    # batch-size scale of SecondMoment/Variance), ``sample_offset`` the
    # global index of this microbatch's first sample (per-sample MC PRNG
    # streams), and ``accum_stats`` makes the engine emit mergeable raw
    # accumulators (Chan (count, mean, M2) triples for Variance) instead
    # of finalized statistics.
    total_units: Optional[Any] = None
    total_batch: Optional[int] = None
    sample_offset: Any = 0
    accum_stats: bool = False
    # Streaming-Gram pair passes (single-device): the batch the hooks see
    # is the concatenation of two microbatch slices, and pairwise stats
    # (batch_dot / ntk*) should emit ONLY the cross block rows[:cross_split]
    # × rows[cross_split:] — computed through the fused cross-block kernel
    # (``kernels.ops.cross_dot``) when kernels are on, or for batch_dot
    # from BatchGrad's per-sample gradients when both are requested.
    # Ignored under ``shard_axes`` (sharded pairwise stats compute full
    # gathered-column rows; the driver slices the blocks).  Set by the
    # accumulated driver's pair passes; never set this by hand.
    cross_split: Optional[int] = None

"""Kernel dispatch registry — the single entry point for reduction kernels.

Every BackPACK *reduction* kernel (the first-order/curvature statistics) is
published through one :class:`KernelSpec` table instead of ad-hoc
per-kernel wrappers.  (The sequence-mixing kernels — flash attention, WKV —
keep their own entry points in their modules: their call signatures are
layer-shaped, not reduction-shaped.)  The registry owns, in one place:

* **Padding** to block multiples.  Feature axes pad to the (shape-clamped)
  block size, sample axes to the sample block, sequence axes to sublane
  multiples of 8.  Zeros are exact for every reduction here (they
  contribute nothing to a sum of products), so wrappers pad inputs and
  slice outputs.
* **Backend selection** — ``interpret=True`` on CPU (kernel bodies run under
  the Pallas interpreter: the correctness path for this container), compiled
  Mosaic on TPU.  Decided once in :func:`_interpret`, injected into every
  wrapper.
* **Jit caching** — :func:`dispatch` memoizes one jitted callable per
  ``(kernel, static options, backend)`` configuration; ``jax.jit``'s own
  shape-keyed cache then handles per-shape retracing, so hot training
  loops never re-trace and :func:`cache_stats` reports what has been set
  up.

Registered kernels (see :func:`registered`):

``sq_matmul``          (A∘A)ᵀ(B∘B) — rank-1 second moment (App. A.1)
``per_sample_moment``  Σ_n (A_nᵀB_n)∘² — sequence second moment
``batch_l2``           per-sample gradient norms via the Gram trick
``ggn_diag``           GGN diagonal from backpropagated factors (Eq. 19/22)
``fused_first_order``  ONE pass emitting {l2, moment} under a static
                       extension mask: ``want_l2`` ↔ BatchL2,
                       ``want_moment`` ↔ SecondMoment/Variance (BatchDot
                       is ``cross_dot``'s).  Unrequested outputs cost
                       nothing.  A leading group
                       axis batches MoE experts.
``fused_second_order`` ONE pass over (A, S) emitting {diag, kron, trace}
                       under a static mask: ``want_diag`` ↔ DiagGGN(MC),
                       ``want_kron`` ↔ KFLR/KFAC B-factor, ``want_trace`` ↔
                       per-sample GGN trace.  The class axis is folded into
                       the grid in ``class_chunk``-sized chunks (exact
                       curvature at LM-vocabulary scale with bounded VMEM).
``predictive_var``     GLM predictive variance diag(J Σ Jᵀ) [C, N] from the
                       Jacobian-factor pair (A, S) in one pass — diag Σ via
                       an elementwise ``Sigma [a, b]`` weight, Kronecker Σ
                       via caller-side half-transforms (see the kernel
                       module doc).  The Laplace serving hot path.

Adding a kernel: write the Pallas body in its own module, then register a
wrapper here with ``@register("name", ref=ref.name)``; the wrapper receives
``interpret=`` from the registry and owns only its pad/slice policy.  Public
module-level functions (``ops.batch_l2`` etc.) stay thin aliases over
:func:`dispatch`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref
from repro.kernels.batch_l2 import batch_l2_pallas
from repro.kernels.cross_dot import cross_dot_pallas
from repro.kernels.fused_first_order import fused_first_order_pallas
from repro.kernels.fused_second_order import fused_second_order_pallas
from repro.kernels.ggn_diag import ggn_diag_pallas
from repro.kernels.per_sample_moment import per_sample_moment_pallas
from repro.kernels.predictive_var import predictive_var_pallas
from repro.kernels.sq_matmul import sq_matmul_pallas


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: padded wrapper + its pure-jnp oracle."""

    name: str
    wrapper: Callable  # (*arrays, interpret=..., **static) -> outputs
    ref: Optional[Callable]
    description: str


_REGISTRY: Dict[str, KernelSpec] = {}
_JIT_CACHE: Dict[Tuple, Callable] = {}

# dispatch-time telemetry (host side — nothing lands inside jitted code):
# per-kernel jit-config cache hits/misses, and the padding-waste bytes one
# call pays, measured once per (config, arg shapes) while the wrapper
# traces and replayed from _PAD_WASTE on every cached-shape dispatch.
_CACHE_HITS: Dict[str, int] = {}
_CACHE_MISSES: Dict[str, int] = {}
_PAD_WASTE: Dict[Tuple, int] = {}
_PAD_NOTE: List[List[int]] = []  # active accumulation cells (see _pad_to)


def register(name: str, *, ref: Optional[Callable] = None,
             description: str = ""):
    """Decorator adding a padded kernel wrapper to the dispatch table.

    Parameters
    ----------
    name : str
        Registry key.  :func:`dispatch` and the public aliases resolve
        kernels by this name; benchmark lanes and the differential tests
        enumerate :func:`registered` to find it.
    ref : callable, optional
        Pure-jnp oracle with the same signature — the correctness
        baseline the differential suite compares the kernel against.
    description : str, optional
        One-line summary for tooling (defaults to the wrapper's first
        docstring line).

    Returns
    -------
    callable
        The decorator.  The wrapped function receives ``interpret=``
        from the registry (CPU interpreter vs compiled Mosaic) and owns
        only its pad/slice policy; blocks it does not pin are auto-sized
        from the shapes it is *called* with — under the sharded sweep
        lane that is the shard-local batch, under the accumulated lane
        the microbatch slice, so streaming a batch automatically shrinks
        the per-launch working set (see ``_auto_class_chunk``).

    Examples
    --------
    >>> @register("my_stat", ref=ref.my_stat)
    ... def _my_stat(A, B, *, block_a=128, interpret=True):
    ...     '''stat[n] = reduce(A_n, B_n): A [N, R, a], B [N, R, b].'''
    ...     ...
    """

    def deco(fn):
        _REGISTRY[name] = KernelSpec(
            name, fn, ref, description or (fn.__doc__ or "").strip())
        return fn

    return deco


def registered() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_spec(name: str) -> KernelSpec:
    return _REGISTRY[name]


def _interpret() -> bool:
    """CPU → Pallas interpreter (correctness path); TPU → compiled Mosaic."""
    return jax.default_backend() == "cpu"


def dispatch(name: str, *args, **static) -> Any:
    """Run a registered kernel through the jit cache, under the named
    scope ``kernel.<name>``.

    One jitted callable per (kernel, static opts, backend) config;
    per-shape compilation caching is jax.jit's own.
    """
    spec = _REGISTRY[name]
    interpret = _interpret()
    key = (name, tuple(sorted(static.items())), interpret)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(partial(spec.wrapper, interpret=interpret, **static))
        _JIT_CACHE[key] = fn
        _CACHE_MISSES[name] = _CACHE_MISSES.get(name, 0) + 1
    else:
        _CACHE_HITS[name] = _CACHE_HITS.get(name, 0) + 1
    shapes = tuple(
        (tuple(a.shape), str(a.dtype)) for a in args if hasattr(a, "shape")
    )
    waste = _PAD_WASTE.get((key, shapes))
    # the kernel's pads, casts, slices and its Pallas call carry its name
    # in their op_name, inside whatever scopes the caller has open
    with jax.named_scope(f"kernel.{name}"):
        if waste is None:
            # first time this config sees these shapes: the wrapper is
            # about to trace (jax.jit's shape cache is cold), so _pad_to
            # calls run now — collect their waste into a fresh cell
            _PAD_NOTE.append([0])
            try:
                out = fn(*args)
            finally:
                waste = _PAD_NOTE.pop()[0]
            _PAD_WASTE[(key, shapes)] = waste
        else:
            out = fn(*args)
    if waste:
        obs.count(f"kernel.padding_waste_bytes.{name}", waste)
    return out


def cache_stats() -> Dict[str, Any]:
    """Per-kernel count of cached jit configurations (plus the total),
    and per-kernel dispatch hit/miss counters under ``"hits"``/``"misses"``
    (a retrace storm shows up as misses outrunning hits)."""
    out: Dict[str, Any] = {"total": len(_JIT_CACHE)}
    for key in _JIT_CACHE:
        out[key[0]] = out.get(key[0], 0) + 1
    out["hits"] = dict(_CACHE_HITS)
    out["misses"] = dict(_CACHE_MISSES)
    return out


def clear_cache() -> None:
    _JIT_CACHE.clear()
    _CACHE_HITS.clear()
    _CACHE_MISSES.clear()
    _PAD_WASTE.clear()


# ---------------------------------------------------------------------------
# shared padding policy
# ---------------------------------------------------------------------------


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    if _PAD_NOTE:
        # dispatch is tracing this wrapper for the first time with these
        # shapes: note the zero-fill bytes this pad costs per call.  Pure
        # shape arithmetic — works identically on tracers.
        per_row = x.size // x.shape[axis] if x.shape[axis] else 0
        _PAD_NOTE[-1][0] += pad * per_row * x.dtype.itemsize
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _clamp_block(block, dim):
    """Shrink an oversized feature block to the (≥8) padded dimension."""
    return min(block, max(dim, 8))


def _sub(x):
    """Round up to the 8-row sublane tile."""
    return -(-x // 8) * 8


def _lane(x):
    """Round up to the 128-wide lane tile."""
    return -(-x // 128) * 128


# Working set one grid step may hold: double-buffered input tiles plus the
# in-kernel contraction tiles, inside the scoped VMEM Mosaic gives a
# kernel by default (16 MiB on v5e).
_STEP_BYTES = 8 << 20


def _tile_bytes(r, *widths):
    """Double-buffered float32 VMEM bytes of one sample's [R, w] input
    tiles (VMEM pads sublanes to 8 and lanes to 128)."""
    return 2 * 4 * _sub(r) * sum(_lane(w) for w in widths)


def _gram_bytes(ba, bb):
    """Float32 VMEM bytes of one sample's [ba, bb] contraction tile and
    its square."""
    return 2 * 4 * _sub(ba) * _lane(bb)


def _sample_block(n, unit_bytes):
    """Samples per grid step: as many as fit :data:`_STEP_BYTES` at
    ``unit_bytes`` each, split evenly over the blocks ``n`` needs.  Sized
    from the batch the kernel is *called* with: the shard-local batch under
    ``SweepPlan.shard``, the microbatch slice under
    ``SweepPlan.accumulate``."""
    fit = max(1, _STEP_BYTES // max(unit_bytes, 1))
    return -(-n // -(-n // fit))


def _auto_block(dim, cap):
    """Feature tile for ``dim``: the whole (sublane-padded) dim when it
    fits ``cap``, else the largest even split into lane-aligned tiles.

    Mosaic takes a lane-axis tile only as a multiple of 128 or as the full
    dimension, so split tiles round up to 128 (576 → 5×128 with cap 128);
    splitting evenly first keeps padding under one lane tile per tile
    (520 → 2×384 with cap 512, where plain ``min(cap, dim)`` gives 2×512).
    """
    if dim <= cap:
        return _sub(dim)
    n_tiles = -(-dim // cap)
    return _lane(-(-dim // n_tiles))


def _feature_blocks(a, b, block_a, block_b, interpret):
    """(ba, bb) feature tiles: caller-pinned (clamped) or auto-sized —
    MXU-native 128 caps under Mosaic, 512 under the CPU interpreter,
    where per-grid-step overhead dominates and bigger tiles amortize it."""
    cap = 512 if interpret else 128
    ba = (_clamp_block(block_a, a) if block_a is not None
          else _auto_block(a, cap))
    bb = (_clamp_block(block_b, b) if block_b is not None
          else _auto_block(b, cap))
    return ba, bb


def _auto_class_chunk(S2, ba, bb, *, bn, mxu_intermediate, kron_view=False):
    """VMEM-budgeted class chunk for ``bn``-sample grid steps.

    The per-(class, sample) working set of one grid step: the
    double-buffered S tile (and the A tile it is broadcast against), plus
    the [C'·bn, ba, bb] MXU contraction tile and its square when
    requested, plus the full-width second S view for the Kronecker
    output.  ``bn`` comes from :func:`_sample_block` on the batch the
    kernel actually sees — the *shard-local* N under the batch-sharded
    sweep lane (``SweepPlan.shard``), the *microbatch* slice under the
    streaming accumulated lane (``SweepPlan.accumulate``) — so smaller
    shards or microbatches take larger class chunks (fewer grid steps)
    inside the same :data:`_STEP_BYTES` budget.
    """
    r, b = S2.shape[2], S2.shape[3]
    per_c = _tile_bytes(r, bb)
    if mxu_intermediate:
        per_c += _tile_bytes(r, ba) + _gram_bytes(ba, bb)
    if kron_view:
        per_c += _tile_bytes(r, b)
    return max(1, _STEP_BYTES // max(per_c * bn, 1))


def _pad_factor_pair(A, S, block_a, block_b, interpret, *, kron_view=False):
    """Shared block-sizing + padding policy for the ``(A, S)`` kernels
    (``fused_second_order``, ``predictive_var``): A [N, R, a] and
    S [C, N, R, b] padded to (auto- or caller-chosen) feature blocks, a
    sample-block multiple and a sublane multiple of R.  Returns
    ``(A2, S2, ba, bb, bn)``; auto ``class_chunk`` budgets live in
    :func:`_auto_class_chunk` (per-kernel flags select which working-set
    terms apply)."""
    a, b = A.shape[-1], S.shape[-1]
    ba, bb = _feature_blocks(a, b, block_a, block_b, interpret)
    r = A.shape[1]
    unit = _tile_bytes(r, ba, bb) + _gram_bytes(ba, bb)
    if kron_view:
        unit += _tile_bytes(r, b)
    bn = _sample_block(A.shape[0], unit)
    A2 = _pad_to(_pad_to(_pad_to(A, 2, ba), 1, 8), 0, bn)
    S2 = _pad_to(_pad_to(_pad_to(S, 3, bb), 2, 8), 1, bn)
    return A2, S2, ba, bb, bn


# ---------------------------------------------------------------------------
# registered wrappers
# ---------------------------------------------------------------------------


@register("sq_matmul", ref=ref.sq_matmul)
def _sq_matmul(A, B, *, block_a=128, block_b=128, block_n=256,
               interpret=True):
    """C = (A∘A)ᵀ(B∘B): A [N, a], B [N, b] → [a, b]."""
    a, b = A.shape[1], B.shape[1]
    ba, bb = _clamp_block(block_a, a), _clamp_block(block_b, b)
    bn = min(block_n, _sub(A.shape[0]))
    A2 = _pad_to(_pad_to(A, 1, ba), 0, bn)
    B2 = _pad_to(_pad_to(B, 1, bb), 0, bn)
    out = sq_matmul_pallas(A2, B2, block_a=ba, block_b=bb, block_n=bn,
                           interpret=interpret)
    return out[:a, :b]


@register("per_sample_moment", ref=ref.per_sample_moment)
def _per_sample_moment(A, B, *, block_a=128, block_b=128, interpret=True):
    """M = Σ_n (A_nᵀB_n)∘²: A [N, R, a], B [N, R, b] → [a, b]."""
    a, b = A.shape[-1], B.shape[-1]
    ba, bb = _clamp_block(block_a, a), _clamp_block(block_b, b)
    A2 = _pad_to(_pad_to(A, 2, ba), 1, 8)
    B2 = _pad_to(_pad_to(B, 2, bb), 1, 8)
    out = per_sample_moment_pallas(A2, B2, block_a=ba, block_b=bb,
                                   interpret=interpret)
    return out[:a, :b]


@register("batch_l2", ref=ref.batch_l2)
def _batch_l2(A, B, *, block_r=128, interpret=True):
    """l2[n] = ‖A_nᵀB_n‖²: A [N, R, a], B [N, R, b] → [N]."""
    r = A.shape[1]
    br = _clamp_block(block_r, r)
    A2 = _pad_to(A, 1, br)
    B2 = _pad_to(B, 1, br)
    return batch_l2_pallas(A2, B2, block_r=br, interpret=interpret)


@register("ggn_diag", ref=ref.ggn_diag)
def _ggn_diag(A, S, *, block_a=128, block_b=128, interpret=True):
    """GGN diag: A [N, R, a], S [C, N, R, b] → [a, b]."""
    a, b = A.shape[-1], S.shape[-1]
    ba, bb = _clamp_block(block_a, a), _clamp_block(block_b, b)
    A2 = _pad_to(_pad_to(A, 2, ba), 1, 8)
    S2 = _pad_to(_pad_to(S, 3, bb), 2, 8)
    out = ggn_diag_pallas(A2, S2, block_a=ba, block_b=bb,
                          interpret=interpret)
    return out[:a, :b]


@register("fused_first_order", ref=ref.fused_first_order)
def _fused_first_order(A, B, *, want_l2=True, want_moment=False,
                       block_a=None, block_b=None, interpret=True):
    """One pass over (A, B) emitting the masked first-order stats.

    A: [E, N, R, a], B: [E, N, R, b] → dict of
    l2 [E, N] / moment [E, a, b] (requested keys only).  Zero-padding N
    and R is exact; padded l2 rows are sliced off, moment is unaffected.
    Pairwise dots (BatchDot) are the ``cross_dot`` kernel's.

    Default blocks are backend-aware (``None`` = auto, see
    :func:`_feature_blocks`); samples are blocked to the step budget.
    """
    if not (want_l2 or want_moment):
        raise ValueError("fused_first_order: empty extension mask")
    e, n, r, a = A.shape
    b = B.shape[-1]
    ba, bb = _feature_blocks(a, b, block_a, block_b, interpret)
    bn = _sample_block(n, _tile_bytes(r, ba, bb) + _gram_bytes(ba, bb))
    A2 = _pad_to(_pad_to(_pad_to(A, 3, ba), 2, 8), 1, bn)
    B2 = _pad_to(_pad_to(_pad_to(B, 3, bb), 2, 8), 1, bn)
    out = fused_first_order_pallas(
        A2, B2, want_l2=want_l2, want_moment=want_moment,
        block_a=ba, block_b=bb, block_n=bn, interpret=interpret)
    if "l2" in out:
        out["l2"] = out["l2"].reshape(e, -1)[:, :n]
    if "moment" in out:
        out["moment"] = out["moment"][:, :a, :b]
    return out


@register("cross_dot", ref=ref.cross_dot)
def _cross_dot(A1, B1, A2, B2, *, block_a=None, block_b=None,
               interpret=True):
    """Cross-block pairwise dots: out[e,n,m] = ⟨A1ᵀB1[n], A2ᵀB2[m]⟩.

    A1/B1: [E, N1, R, a/b], A2/B2: [E, N2, R, a/b] → [E, N1, N2] float32
    — the off-diagonal Gram / empirical-NTK row-block tile.  Zero-padding
    N1, N2 and R is exact (padded per-sample gradients are zero and
    contribute nothing to any dot); padded output rows/cols are sliced
    off.
    """
    e, n1, r, a = A1.shape
    n2 = A2.shape[1]
    b = B1.shape[-1]
    ba, bb = _feature_blocks(a, b, block_a, block_b, interpret)
    # Two sample sets share one step's budget.
    unit = 2 * (_tile_bytes(r, ba, bb) + _gram_bytes(ba, bb))
    bn1, bn2 = _sample_block(n1, unit), _sample_block(n2, unit)

    def prep(x, blk, bn):
        return _pad_to(_pad_to(_pad_to(x, 3, blk), 2, 8), 1, bn)

    out = cross_dot_pallas(prep(A1, ba, bn1), prep(B1, bb, bn1),
                           prep(A2, ba, bn2), prep(B2, bb, bn2),
                           block_a=ba, block_b=bb, block_n1=bn1,
                           block_n2=bn2, interpret=interpret)
    nb1, nb2 = out.shape[1:3]
    out = out.transpose(0, 1, 3, 2, 4).reshape(e, nb1 * bn1, nb2 * bn2)
    return out[:, :n1, :n2]


@register("fused_second_order", ref=ref.fused_second_order)
def _fused_second_order(A, S, *, want_diag=True, want_kron=False,
                        want_trace=False, block_a=None, block_b=None,
                        class_chunk=None, interpret=True):
    """One pass over (A, S) emitting the masked second-order stats.

    A: [N, R, a], S: [C, N, R, b] → dict of diag [a, b] / kron [b, b]
    (unscaled SᵀS) / trace [N] (requested keys only).  Zero-padding N, R
    and C is exact (padded entries contribute nothing to any sum of
    products); padded trace entries are sliced off, diag/kron rows and
    columns likewise.

    ``class_chunk`` bounds the VMEM-resident working set per grid step
    (``None`` = auto: the whole class axis when it fits the step budget,
    chunked otherwise) — the grid folds the class axis so the per-class
    contribution tensor never materializes.  Samples are blocked to the
    same budget.
    """
    c, n, r, b = S.shape
    a = A.shape[-1]
    A2, S2, ba, bb, bn = _pad_factor_pair(A, S, block_a, block_b, interpret,
                                          kron_view=want_kron)
    if class_chunk is None:
        class_chunk = _auto_class_chunk(
            S2, ba, bb, bn=bn, mxu_intermediate=want_diag or want_trace,
            kron_view=want_kron)
    cc = max(1, min(class_chunk, c))
    S2 = _pad_to(S2, 0, cc)
    out = fused_second_order_pallas(
        A2, S2, want_diag=want_diag, want_kron=want_kron,
        want_trace=want_trace, block_a=ba, block_b=bb, class_chunk=cc,
        block_n=bn, interpret=interpret)
    if "diag" in out:
        out["diag"] = out["diag"][:a, :b]
    if "kron" in out:
        out["kron"] = out["kron"][:b, :b]
    if "trace" in out:
        out["trace"] = out["trace"].reshape(-1)[:n]
    return out


@register("predictive_var", ref=ref.predictive_var)
def _predictive_var(A, S, *maybe_sigma, want_sigma=False, block_a=None,
                    block_b=None, class_chunk=None, interpret=True):
    """GLM predictive variance from Jacobian-factor tiles, in one pass.

    A: [N, R, a], S: [C, N, R, b] (+ Sigma [a, b] when ``want_sigma``) →
    var [C, N] float32.  Zero-padding N, R, C and the feature axes is
    exact: padded A/S entries zero the contraction tile, so the squared
    (optionally Sigma-weighted) contributions vanish; padded var rows and
    columns are sliced off.

    ``class_chunk`` bounds the VMEM-resident working set per grid step
    (``None`` = auto, same step budget as ``fused_second_order``).
    """
    c, n, r, b = S.shape
    A2, S2, ba, bb, bn = _pad_factor_pair(A, S, block_a, block_b, interpret)
    Sigma2 = None
    if want_sigma:
        (Sigma,) = maybe_sigma
        Sigma2 = _pad_to(_pad_to(Sigma, 1, bb), 0, ba)
    if class_chunk is None:
        class_chunk = _auto_class_chunk(S2, ba, bb, bn=bn,
                                        mxu_intermediate=True)
    cc = max(1, min(class_chunk, c))
    S2 = _pad_to(S2, 0, cc)
    out = predictive_var_pallas(
        A2, S2, Sigma2, block_a=ba, block_b=bb, class_chunk=cc,
        block_n=bn, interpret=interpret)
    return out.reshape(out.shape[0], -1)[:c, :n]


# ---------------------------------------------------------------------------
# public API (thin aliases over dispatch)
# ---------------------------------------------------------------------------


def sq_matmul(A, B, block_a=128, block_b=128, block_n=256):
    return dispatch("sq_matmul", A, B, block_a=block_a, block_b=block_b,
                    block_n=block_n)


def per_sample_moment(A, B, block_a=128, block_b=128):
    return dispatch("per_sample_moment", A, B, block_a=block_a,
                    block_b=block_b)


def batch_l2(A, B, block_r=128):
    return dispatch("batch_l2", A, B, block_r=block_r)


def ggn_diag(A, S, block_a=128, block_b=128):
    return dispatch("ggn_diag", A, S, block_a=block_a, block_b=block_b)


def fused_second_order(A, S, want_diag=True, want_kron=False,
                       want_trace=False, block_a=None, block_b=None,
                       class_chunk=None):
    """Fused second-order stats: A [N, R, a], S [C, N, R, b]."""
    return dispatch("fused_second_order", A, S, want_diag=want_diag,
                    want_kron=want_kron, want_trace=want_trace,
                    block_a=block_a, block_b=block_b,
                    class_chunk=class_chunk)


def predictive_var(A, S, Sigma=None, block_a=None, block_b=None,
                   class_chunk=None):
    """GLM predictive variance [C, N]: A [N, R, a], S [C, N, R, b].

    ``Sigma [a, b]`` weights the squared Jacobian elementwise (diagonal
    posterior); without it the output is ``‖J[c,n]‖²_F`` (the Kronecker
    path on half-transformed inputs — see kernels/predictive_var.py).
    """
    if Sigma is None:
        return dispatch("predictive_var", A, S, want_sigma=False,
                        block_a=block_a, block_b=block_b,
                        class_chunk=class_chunk)
    return dispatch("predictive_var", A, S, Sigma, want_sigma=True,
                    block_a=block_a, block_b=block_b,
                    class_chunk=class_chunk)


def fused_first_order(A, B, want_l2=True, want_moment=False,
                      block_a=None, block_b=None):
    """Fused first-order stats; A/B may be [N, R, a] (a leading group axis
    of 1 is added and stripped) or [E, N, R, a]."""
    squeeze = A.ndim == 3
    if squeeze:
        A, B = A[None], B[None]
    out = dispatch("fused_first_order", A, B, want_l2=want_l2,
                   want_moment=want_moment, block_a=block_a, block_b=block_b)
    if squeeze:
        out = {k: v[0] for k, v in out.items()}
    return out


def cross_dot(A1, B1, A2, B2, block_a=None, block_b=None):
    """Cross-block pairwise dots [E, N1, N2] (Gram / NTK row-block tile);
    inputs may be [N, R, a] (a leading group axis of 1 is added and the
    output squeezed to [N1, N2]) or [E, N, R, a]."""
    squeeze = A1.ndim == 3
    if squeeze:
        A1, B1, A2, B2 = A1[None], B1[None], A2[None], B2[None]
    out = dispatch("cross_dot", A1, B1, A2, B2,
                   block_a=block_a, block_b=block_b)
    return out[0] if squeeze else out

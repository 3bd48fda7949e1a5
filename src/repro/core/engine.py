"""Generalized backprop engine — one forward pass, K extension sweeps.

``run(model, params, batch, loss, extensions, ...)`` returns

  ``Results(loss, grads, ext)`` with ``ext[name]`` a pytree mirroring the
  params structure (per-module stats), plus the raw per-sweep byproducts the
  optimizers consume (Kronecker factor pairs, GGN diagonals, ...).

Sweep plan (decided statically from the requested extensions):

  first      cotangent sweep — batch gradient + all first-order stats +
             KFAC/KFLR A-factors (they only need layer inputs).  Always runs.
  ggn_exact  exact loss-Hessian factor ``S`` (Eq. 15/18).  When
             ``cfg.class_chunk`` is set, the factor's leading axis is
             processed in chunks of that size under ``lax.scan`` — exact
             curvature at LM-vocabulary scale with bounded memory
             (beyond-paper: the paper stops at C=100).
  ggn_mc     Monte-Carlo factor ``S̃`` (Eq. 20) — the KFAC trick; cost is
             ~1 extra gradient-like sweep per MC sample.
  kfra       averaged ``Ḡ`` recursion (Eq. 24); chain models only.
  hess       exact Hessian diagonal with residual ± factors (Eq. 25/26);
             chain models only.

The whole engine is pure-functional and jit/pjit-compatible: the caller may
wrap ``run`` in ``jax.jit`` with sharded inputs.

Scale-out lanes (both driven by the extensions' declared ``reduce`` specs):

  ``SweepPlan.shard(mesh, axes)``      split the batch over devices
                                       (``shard_map``; cross-shard
                                       collectives per reduce spec)
  ``SweepPlan.accumulate(k)``          stream the batch over k sequential
                                       microbatches (``lax.scan``; the same
                                       reduce specs as running accumulators)
  ``plan.shard(mesh).accumulate(k)``   both: the shard × accumulate grid

The accumulated lane additionally has a preemption-safe form: the plan's
``stream(...)`` method returns a :class:`SweepStream` — the identical
slice schedule driven step by step from the host, whose accumulator state
is a checkpointable pytree of arrays.  ``run_checkpointed(...)`` /
``resume(...)`` drive it with snapshots through a checkpointer (see
``repro.train.checkpoint.SweepCheckpointer``), restart-exact and elastic
across device-mesh changes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs

from jax import shard_map as _shard_map

from .extensions import (
    Extension,
    ExtensionConfig,
    FusedMask,
    FusedSecondMask,
    by_name,
    first_order_mask,
    reduce_spec,
    second_order_mask,
    sweeps_needed,
)
from .module import Module
from .reducers import (
    PSUM,
    Reducer,
    _chan_merge,
    merge_stat_trees as _merge_stat_trees,
)
from ..sharding.rules import GRAM_ASSEMBLY_MODES, gram_assembly_spec


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Static per-call sweep plan, decided once from the extension set.

    ``fused_mask`` is the fused first-order kernel's extension mask and
    ``fused_second_mask`` the fused curvature kernel's — the reductions
    each kernel emits for this extension set; ``fused_active`` says whether
    the config actually routes through them (kernels on AND fused on).
    Together they make the paper's "K quantities, one backward pass" claim
    explicit and inspectable (``plan_sweeps(...)`` is public for
    tests/benchmarks).

    The plan is extension-level *intent*: layer stat hooks re-derive the
    same masks (``first_order_mask`` / ``second_order_mask`` are pure) but
    may specialize on tape shapes the plan cannot see — rank-1 (R==1)
    layers skip both fused launches for the cheaper closed forms (see
    ``dense_first_order_stats`` / ``dense_curv_stats``).
    """

    names: frozenset
    sweeps: frozenset
    first_exts: tuple
    kron_exts: tuple
    fused_mask: FusedMask
    fused_active: bool
    fused_second_mask: FusedSecondMask = FusedSecondMask()

    def describe(self) -> str:
        passes = 1 + sum(s in self.sweeps
                         for s in ("ggn_exact", "ggn_mc", "jac", "kfra",
                                   "hess"))
        fused = [k for k in ("l2", "moment")
                 if getattr(self.fused_mask, k)]
        lane = fused if self.fused_active and fused else None
        # The second-order lane reports the *planned* kernel outputs for the
        # extension set regardless of config (the curvature lane is what a
        # plan is usually inspected for); `fused_active` says whether this
        # config routes both lanes through the fused kernels.
        second = [k for k in ("diag", "kron", "trace")
                  if getattr(self.fused_second_mask, k)]
        structures = list(self.posterior_structures())
        return (f"sweeps={sorted(self.sweeps) or ['first']} "
                f"passes={passes} fused_first_order={lane} "
                f"fused_second_order={second or None} "
                f"fused_active={self.fused_active} "
                f"laplace={structures or None}")

    def posterior_structures(self) -> tuple:
        """Laplace posterior structures this sweep plan can fit.

        ``'diag'`` needs a GGN diagonal (DiagGGN / DiagGGNMC), ``'kron'``
        Kronecker factors (KFLR / KFAC); ``'last_layer'`` restricts either
        to the final Dense layer, so it is available whenever any structure
        is.  ``repro.laplace`` validates fits against this — a misconfigured
        fit fails with this list in the message instead of a shape error.
        """
        out = []
        if self.names & {"diag_ggn", "diag_ggn_mc"}:
            out.append("diag")
        if self.names & {"kflr", "kfac"}:
            out.append("kron")
        if out:
            out.append("last_layer")
        return tuple(out)


    def shard(self, mesh, axes=("data",),
              gram_assembly: str = "split") -> "ShardedSweepPlan":
        """Bind this plan to a device mesh: the batch-sharded sweep lane.

        ``axes`` names the mesh axis (or axes) the batch is split over;
        the returned :class:`ShardedSweepPlan` runs the same sweeps under
        ``shard_map`` — fused kernels on each shard's local batch, then
        the per-extension ``reduce`` specs combine the shards (see
        ``ShardedSweepPlan.describe()`` for the placement report).

        ``gram_assembly`` picks the distributed layout of pairwise (Gram /
        empirical NTK) outputs: ``'split'`` leaves each shard its row
        block (sharded axis 0, no extra communication), ``'all'``
        all-gathers the full [N, N] matrix onto every shard, ``'master'``
        materializes it on the first shard only (the others hold zeros
        under a leading device axis).
        """
        if isinstance(axes, str):
            axes = (axes,)
        gram_assembly_spec(gram_assembly, axes)  # validate the mode early
        return ShardedSweepPlan(plan=self, mesh=mesh, axes=tuple(axes),
                                gram_assembly=gram_assembly)

    def accumulate(self, num_microbatches: int) -> "AccumulatedSweepPlan":
        """Bind this plan to a microbatch schedule: the streaming lane.

        The returned :class:`AccumulatedSweepPlan` runs the identical
        sweep once per microbatch slice under a ``lax.scan`` driver,
        folding results through each extension's ``reduce`` spec
        reinterpreted as a *sequential* accumulator — effective batches
        far beyond device memory, matching the monolithic sweep.
        Composes with sharding: ``plan.shard(mesh).accumulate(k)`` is the
        shard × accumulate grid.

        Parameters
        ----------
        num_microbatches : int
            Number of sequential slices the batch is split into (each of
            ``ceil(N / num_microbatches)`` samples; the final slice may
            be smaller).
        """
        return AccumulatedSweepPlan(plan=self,
                                    num_microbatches=int(num_microbatches))

    def run(self, model, params, inputs, targets, loss,
            cfg: Optional[ExtensionConfig] = None,
            rng: Optional[jax.Array] = None) -> Results:
        """Run the monolithic sweep for this plan's extensions — the
        plan-object counterpart of :func:`run`, giving all three lanes
        (monolithic / sharded / accumulated) one calling convention."""
        extensions = tuple(by_name(n) for n in sorted(self.names))
        return run(model, params, inputs, targets, loss,
                   extensions=extensions, cfg=cfg, rng=rng)


def plan_sweeps(extensions: Sequence[Extension],
                cfg: Optional[ExtensionConfig] = None) -> SweepPlan:
    """Build the static sweep plan for a set of requested extensions.

    Parameters
    ----------
    extensions : sequence of Extension
        The quantities to extract (``repro.core.BatchGrad`` etc.).
    cfg : ExtensionConfig, optional
        Only ``use_kernels`` / ``use_fused`` are consulted (they decide
        ``fused_active``); sweep structure depends on the extensions
        alone.

    Returns
    -------
    SweepPlan
        The static schedule: which backward sweeps run, which fused
        kernel outputs they request, and the scale-out entry points
        (:meth:`SweepPlan.shard`, :meth:`SweepPlan.accumulate`).
        ``plan.describe()`` renders it for inspection.
    """
    cfg = cfg or ExtensionConfig()
    first_exts = tuple(e for e in extensions if e.sweep == "first")
    return SweepPlan(
        names=frozenset(e.name for e in extensions),
        sweeps=frozenset(sweeps_needed(extensions)),
        first_exts=first_exts,
        # KFAC/KFLR A-factors are harvested during the first sweep:
        kron_exts=tuple(e for e in extensions if e.name in ("kfac", "kflr")),
        fused_mask=first_order_mask(first_exts),
        fused_active=cfg.use_kernels and cfg.use_fused,
        fused_second_mask=second_order_mask(extensions),
    )


def plan_for_batch(extensions, cfg, n, mesh=None, shard_axes=("data",),
                   microbatch_size=None):
    """Compose the right sweep lane for a batch of ``n`` samples.

    The single place consumers (the extended train step, the Laplace
    fits) derive their lane composition from: shard over ``mesh`` when
    one is given, accumulate when a microbatch size (argument, or
    ``cfg.microbatch_size``) asks for more than one slice.
    ``microbatch_size`` bounds the rows a *device* sweeps per sequential
    slice — under a mesh the grid already splits the batch over shards,
    so the count comes from the shard-local batch (a shard whose rows
    already fit the bound accumulates nothing).  Returns a plan object
    with the uniform ``.run(model, params, inputs, targets, loss, cfg=,
    rng=)`` contract — a plain :class:`SweepPlan`, a
    :class:`ShardedSweepPlan`, an :class:`AccumulatedSweepPlan`, or the
    shard × accumulate grid.
    """
    cfg = cfg or ExtensionConfig()
    plan = plan_sweeps(extensions, cfg)
    n_dev = n
    if mesh is not None:
        plan = plan.shard(mesh, shard_axes)
        n_dev = max(1, n // plan.n_shards)
    mb = microbatch_size or cfg.microbatch_size
    k = -(-n_dev // mb) if mb else 1
    if k > 1:
        plan = plan.accumulate(k)
    return plan


@dataclasses.dataclass
class Results:
    loss: jnp.ndarray
    grads: Any
    logits: Any
    ext: Dict[str, Any]

    def __getitem__(self, k):
        return self.ext[k]


def _tree_add(a, b):
    if a is None:
        return b
    return jax.tree.map(jnp.add, a, b)


def _zip_stats(fn, st, gr):
    """Map fn over (stats, grads) in parallel, tolerating () stat holes
    (buffers / raw mixer params that have gradients but no per-sample
    statistics)."""
    if st is None or (isinstance(st, tuple) and len(st) == 0):
        return ()
    if isinstance(st, dict):
        return {
            k: _zip_stats(fn, v, gr.get(k) if isinstance(gr, dict) else None)
            for k, v in st.items()
        }
    if isinstance(st, (tuple, list)):
        gr_t = gr if isinstance(gr, (tuple, list)) else (None,) * len(st)
        return tuple(_zip_stats(fn, s, g) for s, g in zip(st, gr_t))
    return fn(st, gr)


# ---------------------------------------------------------------------------
# batch-sharded sweep lane (SweepPlan.shard)
# ---------------------------------------------------------------------------


def _axis_count(axes):
    """Number of shards over the named mesh axes (inside shard_map)."""
    return jax.lax.psum(1, tuple(axes))


def _global_sample_offset(axes, n_local):
    """Global index of this shard's first sample.

    ``shard_map`` splits axis 0 major-to-minor over ``axes``; the linear
    shard index times the local batch recovers the single-device sample
    numbering (what the per-sample MC streams are keyed on).
    """
    idx = 0
    for ax in axes:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return idx * n_local


class _ScaledLoss:
    """Loss adapter correcting a partial batch's 1/M normalization.

    Every loss here normalizes by the number M of sample units; a body
    that only sees part of the batch — a shard's rows under ``shard_map``
    (the sharded lane), a microbatch slice (the accumulated lane), or
    both — gets cotangents/factors scaled by 1/M_local instead of
    1/M_global.  This adapter rescales by ``ml / mg``:

    * ``axes`` set, no ``total_units``: the sharded lane — M_global is
      the psum of the raw local counts over the data axes, and MC factors
      get the shard's global sample offset so the per-sample PRNG streams
      line up with the single-device draws.
    * ``total_units`` set: the accumulated lane — M_global over the whole
      accumulated batch is computed once by the driver from the full
      targets and passed in (a psum inside one microbatch could only see
      that microbatch's units).  The driver also supplies the complete
      ``sample_offset`` (shard base + microbatch start), so no implicit
      shard offset is added.

    ``value``/``hessian_mean`` return the partial batch's *contribution*
    (already psum'd across shards when ``axes`` is set); under the
    accumulated lane the driver sums contributions over microbatches.
    Per-sample quantities then match their monolithic single-device
    counterparts exactly, even when padding masks leave unit counts
    uneven across shards or microbatches.
    """

    def __init__(self, base, axes=(), total_units=None, sample_offset=0):
        self.base = base
        self.axes = tuple(axes or ())
        self.total_units = total_units
        self.sample_offset = sample_offset

    def __getattr__(self, name):
        return getattr(self.base, name)

    def _psum(self, x):
        return jax.lax.psum(x, self.axes) if self.axes else x

    def _m(self, y):
        # num_units is the *raw* count — a fully padded shard reports 0.
        # The local clamp must mirror the base loss's own ≥1 clamp (that
        # is what its outputs were divided by); the global clamp only
        # guards the degenerate everything-masked batch.
        raw = self.base.num_units(y)
        ml = jnp.maximum(raw, 1.0)
        if self.total_units is not None:
            mg = jnp.maximum(self.total_units, 1.0)
        else:
            mg = jnp.maximum(self._psum(raw), 1.0)
        return ml, mg

    def value(self, z, y):
        ml, mg = self._m(y)
        return self._psum(self.base.value(z, y) * ml) / mg

    def grad(self, z, y):
        ml, mg = self._m(y)
        g = self.base.grad(z, y)
        return (g.astype(jnp.float32) * (ml / mg)).astype(g.dtype)

    def n_exact_cols(self, z):
        return self.base.n_exact_cols(z)

    def _offset(self, z):
        off = self.sample_offset
        if self.axes and self.total_units is None:
            off = off + _global_sample_offset(self.axes, z.shape[0])
        return off

    def sqrt_hessian(self, z, y):
        return self.sqrt_hessian_chunk(z, y, 0, self.n_exact_cols(z))

    def sqrt_hessian_chunk(self, z, y, lo, size):
        ml, mg = self._m(y)
        S = self.base.sqrt_hessian_chunk(z, y, lo, size)
        return (S.astype(jnp.float32) * jnp.sqrt(ml / mg)).astype(S.dtype)

    def sqrt_hessian_mc(self, rng, z, y, k=1, sample_offset=0):
        ml, mg = self._m(y)
        off = sample_offset + self._offset(z)
        S = self.base.sqrt_hessian_mc(rng, z, y, k, sample_offset=off)
        return (S.astype(jnp.float32) * jnp.sqrt(ml / mg)).astype(S.dtype)

    def hessian_mean(self, z, y):
        ml, mg = self._m(y)
        return self._psum(self.base.hessian_mean(z, y) * ml) / mg

    def hessian_vec(self, z, y, v):
        # Per-sample like ``grad``: rescale this partial batch's 1/M_local
        # to 1/M_global, no psum (matrix-free products psum the final
        # parameter-space result themselves).
        ml, mg = self._m(y)
        hv = self.base.hessian_vec(z, y, v)
        return (hv.astype(jnp.float32) * (ml / mg)).astype(hv.dtype)


def _default_rng(sweeps, cfg, rng):
    """MC-sweep rng defaulting shared by every lane: an explicit key wins,
    else ``cfg.mc_seed`` (deterministic sweeps), else an error when an MC
    extension actually needs draws — and an unused placeholder key when
    none does."""
    if rng is not None:
        return rng
    if "ggn_mc" in sweeps:
        if cfg.mc_seed is None:
            raise ValueError(
                "MC extensions need an rng key: pass rng= or set "
                "ExtensionConfig(mc_seed=...) for deterministic sweeps")
        return jax.random.PRNGKey(cfg.mc_seed)
    return jax.random.PRNGKey(0)  # unused without an MC sweep


def _moment_triple(sum_g2, grad_sum, n):
    """(count, mean, M2) triple from a partial batch's (Σg², Σg)."""
    nl = jnp.float32(n)
    g1 = grad_sum.astype(jnp.float32)
    return nl, g1 / nl, sum_g2 - g1 ** 2 / nl


def _sharded_moment_triple(sum_g2, grad_local, n_local, axes):
    """Global (count, mean, M2) triple across shards, moment-merge style.

    Each shard contributes its local (Σg, Σg²) as a (count, mean, M2)
    triple, combined by the k-way form of Chan's merge,
    ``M2 = Σ_s M2_s + Σ_s n_s (mean_s − mean)²``, in two psums: the
    deviations are taken around the global mean, so the catastrophically
    cancelling global Σg² − (Σg)²/n difference between large
    intermediates never forms, and no shard holds another shard's
    parameter-sized moments (an all-gather of them costs 2·shards
    parameter copies per device).  ``n·M2`` of the result equals the
    engine's single-device ``n·Σg² − (Σg)²`` in exact arithmetic.
    """
    axes = tuple(axes)
    nl, mean_l, m2_l = _moment_triple(sum_g2, grad_local, n_local)
    n = jax.lax.psum(nl, axes)
    mean = jax.lax.psum(grad_local.astype(jnp.float32), axes) / n
    m2 = jax.lax.psum(m2_l + nl * (mean_l - mean) ** 2, axes)
    return n, mean, m2


def _sharded_variance(sum_g2, grad_local, n_local, axes):
    """Global gradient variance across shards: ``n·M2`` of the merged
    triple (see :func:`_sharded_moment_triple`)."""
    n, _, m2 = _sharded_moment_triple(sum_g2, grad_local, n_local, axes)
    return n * m2


def _reduce_sharded(grads, ext, extensions, axes):
    """Apply each extension's declared cross-shard reducer (inside
    shard_map) — one :meth:`Reducer.shard_reduce` call per extension;
    gradients are always psum'd.  Local-row reducers (concat / gram) are
    identity here: the sharded out-specs concatenate their sample rows,
    and moment-merge outputs are already global (see
    :func:`_sharded_variance`)."""
    red = reduce_spec(extensions)
    out = {name: red.get(name, PSUM).shard_reduce(tree, axes)
           for name, tree in ext.items()}
    grads = jax.tree.map(lambda x: jax.lax.psum(x, axes), grads)
    return grads, out


def _assemble_gram(tree, mode, axes):
    """Distributed assembly of a pairwise row-block tree inside shard_map.

    ``'split'`` keeps each shard's row block (sharded axis 0 — the
    default, zero extra communication).  ``'all'`` all-gathers the row
    blocks so every shard holds the full [N, N, ...] matrix.
    ``'master'`` gathers too but zeros every shard except linear shard 0,
    under a fresh leading device axis: stacked by the sharded out-spec,
    ``out[0]`` is the full matrix and the other entries are zeros (the
    asdfghjkl-style master layout, without broadcasting the O(N²) result
    back to every host).
    """
    if mode == "split":
        return tree

    def asm(x):
        full = jax.lax.all_gather(x, tuple(axes), axis=0, tiled=True)
        if mode == "all":
            return full
        idx = 0
        for ax in axes:
            idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
        return jnp.where(idx == 0, full, jnp.zeros_like(full))[None]

    return jax.tree.map(asm, tree)


def _assemble_pairwise_ext(ext, red, mode, axes):
    """Apply :func:`_assemble_gram` to every pairwise extension entry."""
    if mode == "split":
        return ext
    return {nm: (_assemble_gram(t, mode, axes)
                 if red.get(nm, PSUM).pairwise else t)
            for nm, t in ext.items()}


@dataclasses.dataclass(frozen=True)
class ShardedSweepPlan:
    """A :class:`SweepPlan` bound to a device mesh — the batch-sharded lane.

    ``run`` wraps the whole engine sweep in ``shard_map`` over the data
    axes: the forward/backward (and the fused Pallas kernel launches
    inside it) run on each device's local batch shard, then the
    per-extension ``reduce`` specs combine the shards — psum for
    batch-summed curvature, pmean/psum factor pairs for KFAC/KFLR,
    all-gathered Gram rows for pairwise dots, a pairwise moment merge for
    the variance, and plain row concatenation (via the sharded out-specs)
    for per-sample statistics.  Results are numerically equivalent to the
    single-device sweep (exactly, up to accumulation order).
    """

    plan: SweepPlan
    mesh: Any
    axes: tuple
    gram_assembly: str = "split"

    @property
    def n_shards(self) -> int:
        s = 1
        for ax in self.axes:
            s *= self.mesh.shape[ax]
        return s

    def reduce_specs(self) -> dict:
        """``{extension name: cross-shard reducer}`` for this plan."""
        return reduce_spec([by_name(n) for n in sorted(self.plan.names)])

    def check_batch(self, n: int) -> None:
        """Raise unless the global batch splits evenly over the shards."""
        if n % self.n_shards:
            raise ValueError(
                f"global batch {n} is not divisible by {self.n_shards} "
                f"shards over mesh axes {self.axes}")

    def describe(self) -> str:
        red = self.reduce_specs()
        _, gram_place = gram_assembly_spec(self.gram_assembly, self.axes)
        placement = ", ".join(
            f"{n}:{r.name}->" + (gram_place if r.pairwise else r.placement)
            for n, r in sorted(red.items()))
        mesh_shape = dict(zip(self.mesh.axis_names,
                              self.mesh.devices.shape))
        return (f"{self.plan.describe()} | shard_axes={list(self.axes)} "
                f"shards={self.n_shards} mesh={mesh_shape} "
                f"reduce=[{placement}] "
                f"grads:psum->replicated logits:concat->sharded(axis0)")

    def run(self, model, params, inputs, targets, loss,
            cfg: Optional[ExtensionConfig] = None,
            rng: Optional[jax.Array] = None) -> Results:
        """The sharded analogue of :func:`run` — same signature minus
        ``extensions`` (the plan carries them), same Results contract."""
        cfg = dataclasses.replace(cfg or ExtensionConfig(),
                                  shard_axes=tuple(self.axes))
        extensions = tuple(by_name(n) for n in sorted(self.plan.names))
        self.check_batch(jax.tree.leaves(inputs)[0].shape[0])
        rng = _default_rng(self.plan.sweeps, cfg, rng)

        batch = P(tuple(self.axes))
        red = self.reduce_specs()
        gram_spec, _ = gram_assembly_spec(self.gram_assembly, self.axes)
        ext_specs = {}
        for name in self.plan.names:
            r = red[name]
            ext_specs[name] = (gram_spec if r.pairwise
                               else batch if r.local_rows else P())

        def body(p, x, y, key):
            res = run(model, p, x, y, loss, extensions=extensions, cfg=cfg,
                      rng=key)
            ext = _assemble_pairwise_ext(res.ext, red, self.gram_assembly,
                                         self.axes)
            return res.loss, res.grads, res.logits, ext

        # jit: an un-jitted shard_map runs its body op by op.
        fn = jax.jit(_shard_map(body, mesh=self.mesh,
                                in_specs=(P(), batch, batch, P()),
                                out_specs=(P(), P(), batch, ext_specs),
                                check_vma=False))
        loss_val, grads, logits, ext = fn(params, inputs, targets, rng)
        return Results(loss=loss_val, grads=grads, logits=logits, ext=ext)

    def accumulate(self, num_microbatches: int) -> "AccumulatedSweepPlan":
        """Stack the sequential lane on top of this sharded plan: the
        shard × accumulate grid.  Each device scans over
        ``num_microbatches`` slices of its local batch rows; see
        :meth:`SweepPlan.accumulate`."""
        return AccumulatedSweepPlan(plan=self.plan,
                                    num_microbatches=int(num_microbatches),
                                    sharded=self)


# ---------------------------------------------------------------------------
# streaming accumulated sweep lane (SweepPlan.accumulate)
# ---------------------------------------------------------------------------

def _run_accumulated(model, params, inputs, targets, loss, extensions,
                     cfg, rng, num_microbatches, base_offset=0, n_shards=1):
    """Sequential microbatch driver: the identical sweep per slice, folded
    through the extensions' :class:`Reducer` protocols as sequential
    accumulators (``init`` / ``update`` per slice, ``finalize`` once).

    Runs either at top level (single-device accumulated lane) or inside a
    ``shard_map`` shard body (``cfg.shard_axes`` set — the shard ×
    accumulate grid, where ``inputs`` are this shard's local rows,
    ``base_offset`` its first global sample index and ``n_shards`` the
    grid width).  ``cfg`` must already carry ``total_units`` /
    ``total_batch`` / ``accum_stats``.

    The batch splits into ``ceil(n / k)``-row slices: every full slice
    runs under one ``lax.scan`` (bounded memory, one trace), an uneven
    final slice runs as a separate step.  Reducers dispatch by
    capability: ``streams_rows`` outputs ride the scan stack and
    concatenate in sample order; ``pairwise`` (Gram / NTK) outputs
    stream as row blocks — the main scan yields each slice's *diagonal*
    block, extra pair passes (one per slice pair, also scanned) fill the
    off-diagonal blocks, and every block is scattered into a zero
    [n, S·n, ...] accumulator, so peak factor memory stays at two
    microbatches; everything else folds through ``update``.  Returns
    ``(loss, grads, logits, ext)``.
    """
    red = reduce_spec(extensions)
    pair_names = [e.name for e in extensions if red[e.name].pairwise]
    concat_names = [e.name for e in extensions if red[e.name].streams_rows]
    carry_names = [e.name for e in extensions
                   if not (red[e.name].pairwise or red[e.name].streams_rows)]
    n = jax.tree.leaves(inputs)[0].shape[0]
    k = max(1, min(int(num_microbatches), n))
    m = -(-n // k)          # slice rows (ceil); last slice may be smaller
    k_full = n // m
    rem = n - k_full * m
    sharded = bool(cfg.shard_axes)

    def slice_run(p, key, x_i, y_i, off):
        cfg_i = dataclasses.replace(cfg, sample_offset=off)
        res = run(model, p, x_i, y_i, loss, extensions=extensions,
                  cfg=cfg_i, rng=key)
        carry_ext = {nm: res.ext[nm] for nm in carry_names}
        cat_ext = {nm: res.ext[nm] for nm in concat_names}
        pair_ext = {nm: res.ext[nm] for nm in pair_names}
        return (res.loss, res.grads, carry_ext, res.logits, cat_ext,
                pair_ext)

    def head(a):
        return a[:m]

    zshape = jax.eval_shape(slice_run, params, rng,
                            jax.tree.map(head, inputs),
                            jax.tree.map(head, targets), 0)
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), zshape[:3])
    zero = (zeros[0], zeros[1],
            {nm: red[nm].init(zeros[2][nm]) for nm in carry_names})

    # Pairwise (Gram-family) accumulators: one [n, S, n, ...] buffer per
    # stat leaf (S = n_shards), scatter-filled block by block and reshaped
    # to the row-block layout [n, S·n, ...] at the end.  A streamed
    # block's column axis is already the shard-gathered S·rows, so the
    # middle shard axis lines the scattered columns up with the
    # shard-major global sample order.
    def pair_zero(s):
        return jnp.zeros((n, n_shards, n) + s.shape[2:], s.dtype)

    pair_acc = {nm: jax.tree.map(pair_zero, zshape[5][nm])
                for nm in pair_names}

    def split(a):
        return a[:k_full * m].reshape((k_full, m) + a.shape[1:])

    xs = (jax.tree.map(split, inputs), jax.tree.map(split, targets),
          base_offset + m * jnp.arange(k_full))

    def body(carry, xs_i):
        x_i, y_i, off = xs_i
        lv, g, cext, z, yext, pext = slice_run(params, rng, x_i, y_i, off)
        a_lv, a_g, a_ext = carry
        meta = {"weight": float(m)}
        carry = (a_lv + lv, jax.tree.map(jnp.add, a_g, g),
                 {nm: red[nm].update(a_ext[nm], cext[nm], meta)
                  for nm in carry_names})
        return carry, (z, yext, pext)

    with jax.named_scope(f"accumscan_T{k_full}"):
        (lv, grads, c_ext), (zs, ys, ps) = jax.lax.scan(body, zero, xs)

    def unstack(a):
        return a.reshape((k_full * a.shape[1],) + a.shape[2:])

    logits = jax.tree.map(unstack, zs)
    cat_ext = {nm: jax.tree.map(unstack, ys[nm]) for nm in concat_names}

    # Diagonal blocks rode the scan stack: block t is rows
    # [t·m, (t+1)·m) against its own gathered columns.
    def scatter_diag(acc, blocks):
        for t in range(k_full):
            b = blocks[t].reshape((m, n_shards, m) + blocks.shape[3:])
            acc = acc.at[t * m:(t + 1) * m, :, t * m:(t + 1) * m].set(
                b.astype(acc.dtype))
        return acc

    pair_acc = {nm: jax.tree.map(scatter_diag, pair_acc[nm], ps[nm])
                for nm in pair_names}

    if rem:
        def tail(a):
            return a[k_full * m:]

        lv_r, g_r, cext_r, z_r, yext_r, pext_r = slice_run(
            params, rng, jax.tree.map(tail, inputs),
            jax.tree.map(tail, targets), base_offset + k_full * m)
        lv = lv + lv_r
        grads = jax.tree.map(jnp.add, grads, g_r)
        meta_r = {"weight": float(rem)}
        c_ext = {nm: red[nm].update(c_ext[nm], cext_r[nm], meta_r)
                 for nm in carry_names}
        cat = partial(jax.tree.map, lambda a, b: jnp.concatenate([a, b], 0))
        logits = cat(logits, z_r)
        cat_ext = {nm: cat(cat_ext[nm], yext_r[nm]) for nm in concat_names}

        def scatter_rem(acc, blk):
            b = blk.reshape((rem, n_shards, rem) + blk.shape[2:])
            o = k_full * m
            return acc.at[o:o + rem, :, o:o + rem].set(b.astype(acc.dtype))

        pair_acc = {nm: jax.tree.map(scatter_rem, pair_acc[nm], pext_r[nm])
                    for nm in pair_names}

    # Off-diagonal row blocks: one extra 2-slice sweep per (p, q) pair,
    # scanned over the pair index.  Single-device, cfg.cross_split makes
    # the layer hooks emit only the [m, rows_q, ...] cross block (half the
    # pair-pass FLOPs); sharded, the hooks gather as usual and the cross
    # blocks are cut out of the gathered columns (the within-pair diagonal
    # sub-blocks are redundant with the main scan and discarded).
    if pair_names and (k_full > 1 or (rem and k_full)):
        pair_exts = tuple(e for e in extensions if e.name in pair_names)

        def pair_run(off_p, off_q, rows_q):
            def cut(a):
                ap = jax.lax.dynamic_slice_in_dim(a, off_p, m, 0)
                aq = jax.lax.dynamic_slice_in_dim(a, off_q, rows_q, 0)
                return jnp.concatenate([ap, aq], 0)

            cfg_p = dataclasses.replace(
                cfg, sample_offset=0,
                cross_split=None if sharded else m)
            res = run(model, params, jax.tree.map(cut, inputs),
                      jax.tree.map(cut, targets), loss,
                      extensions=pair_exts, cfg=cfg_p, rng=rng)
            return res.ext

        def scatter_pair(acc, blk, off_p, off_q, rows_q, reducer):
            if sharded:
                b = blk.reshape((m + rows_q, n_shards, m + rows_q)
                                + blk.shape[2:])
                top = b[:m, :, m:]             # [m, S, rows_q, ...]
                bot = b[m:, :, :m]             # [rows_q, S, m, ...]
            else:
                top = blk[:, None]
                bot = reducer.transpose_block(blk)[:, None]
            tail0 = (0,) * (top.ndim - 3)
            acc = jax.lax.dynamic_update_slice(
                acc, top.astype(acc.dtype), (off_p, 0, off_q) + tail0)
            return jax.lax.dynamic_update_slice(
                acc, bot.astype(acc.dtype), (off_q, 0, off_p) + tail0)

        def pair_step(rows_q):
            def step(acc_tree, offs):
                off_p, off_q = offs[0], offs[1]
                pext = pair_run(off_p, off_q, rows_q)
                acc_tree = {
                    nm: jax.tree.map(
                        lambda a, b, r=red[nm]: scatter_pair(
                            a, b, off_p, off_q, rows_q, r),
                        acc_tree[nm], pext[nm])
                    for nm in pair_names}
                return acc_tree, None

            return step

        pairs = [(p * m, q * m)
                 for p in range(k_full) for q in range(p + 1, k_full)]
        if pairs:
            with jax.named_scope(f"gramscan_T{len(pairs)}"):
                pair_acc, _ = jax.lax.scan(
                    pair_step(m), pair_acc, jnp.asarray(pairs, jnp.int32))
        if rem:
            offs = jnp.stack(
                [m * jnp.arange(k_full, dtype=jnp.int32),
                 jnp.full((k_full,), k_full * m, jnp.int32)], axis=1)
            with jax.named_scope(f"gramscan_rem_T{k_full}"):
                pair_acc, _ = jax.lax.scan(pair_step(rem), pair_acc, offs)

    ext = {}
    meta_fin = {"total_batch": float(n), "total_units": cfg.total_units}
    if "kfra" in carry_names:
        # The reducer accumulates KFRA's global batch expectations
        # ({'gbar', 'partials'}); replaying the Ḡ recursion through the
        # layer stack is model structure, so the driver provides it.
        meta_fin["replay"] = lambda gbar, parts: _merge_stat_trees(
            model.kfra_apply(params, gbar, parts, extensions, cfg)[1],
            "kfra")
    for nm in carry_names:
        ext[nm] = red[nm].finalize(c_ext[nm], meta_fin)
    ext.update(cat_ext)
    for nm in pair_names:
        ext[nm] = jax.tree.map(
            lambda a: a.reshape((n, n_shards * n) + a.shape[3:]),
            pair_acc[nm])
    return lv, grads, logits, ext


@dataclasses.dataclass(frozen=True)
class AccumulatedSweepPlan:
    """A :class:`SweepPlan` bound to a microbatch schedule — the streaming
    accumulated lane (optionally stacked on a :class:`ShardedSweepPlan`:
    the shard × accumulate grid).

    ``run`` executes the identical fused-kernel sweep once per microbatch
    slice under a ``lax.scan`` driver and folds results through each
    extension's ``reduce`` spec reinterpreted as a *sequential*
    accumulator (``Reducer.init`` / ``update`` / ``finalize``): running
    sums for psum, running sample-count-weighted A / summed B factors for
    kron, in-order row appends for concat, the pairwise Chan moment merge
    for moment_merge, streamed row-block scatters for the pairwise Gram
    family (BatchDot / NTK — diagonal blocks from the main scan, one
    extra sweep per slice pair for the off-diagonal blocks), and weighted
    partial means plus a final chain replay for KFRA's pmean.  The loss's
    1/M normalization is corrected with the mask-aware *global* unit
    count (computed once from the full targets), and MC factor draws
    stay keyed per global sample index — so results match the monolithic
    sweep up to accumulation order while peak activation/factor memory
    scales with the microbatch, serving effective batches far beyond
    device memory.

    Third-party reducers that genuinely need the whole batch resident
    declare ``supports_streaming = False`` and are rejected with an
    actionable error.
    """

    plan: SweepPlan
    num_microbatches: int
    sharded: Optional[ShardedSweepPlan] = None

    def __post_init__(self):
        # Both construction paths (SweepPlan.accumulate and
        # ShardedSweepPlan.accumulate) land here — a bad count must raise
        # on either, not silently clamp to a monolithic sweep.
        if self.num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1 "
                             f"(got {self.num_microbatches})")

    def describe(self) -> str:
        base = (self.sharded or self.plan).describe()
        red = reduce_spec([by_name(nm) for nm in sorted(self.plan.names)])
        accs = ", ".join(f"{nm}:{r.name}({r.streaming_form})"
                         for nm, r in sorted(red.items()))
        return (f"{base} | accumulate={self.num_microbatches} microbatches "
                f"(sequential reduce: {accs})")

    def _check_extensions(self, extensions):
        red = reduce_spec(extensions)
        bad = sorted(nm for nm, r in red.items() if not r.supports_streaming)
        if bad:
            kinds = ", ".join(f"{nm} ({red[nm].name})" for nm in bad)
            raise ValueError(
                f"extensions [{kinds}] have no sequential accumulator: "
                "their reducers declare supports_streaming=False — the "
                "whole batch must be resident at once.  Run them on a "
                "monolithic or sharded sweep, implement the streaming "
                "protocol on the reducer, or drop them from the "
                "accumulated plan.")
        return red

    def run(self, model, params, inputs, targets, loss,
            cfg: Optional[ExtensionConfig] = None,
            rng: Optional[jax.Array] = None) -> Results:
        """The accumulated analogue of :func:`run` — same signature minus
        ``extensions`` (the plan carries them), same Results contract."""
        cfg = cfg or ExtensionConfig()
        extensions = tuple(by_name(nm) for nm in sorted(self.plan.names))
        red = self._check_extensions(extensions)
        n = jax.tree.leaves(inputs)[0].shape[0]
        rng = _default_rng(self.plan.sweeps, cfg, rng)
        # Mask-aware global unit count over the WHOLE batch, computed once
        # from the full targets — each microbatch body rescales its local
        # factors to this 1/M (see _ScaledLoss).
        mg = loss.num_units(targets)

        if self.sharded is None:
            cfg2 = dataclasses.replace(
                cfg, shard_axes=None, total_units=mg, total_batch=n,
                accum_stats=True, cross_split=None)
            lv, grads, logits, ext = _run_accumulated(
                model, params, inputs, targets, loss, extensions, cfg2,
                rng, self.num_microbatches)
            return Results(loss=lv, grads=grads, logits=logits, ext=ext)

        sp = self.sharded
        sp.check_batch(n)
        n_local = n // sp.n_shards
        batch = P(tuple(sp.axes))
        gram_spec, _ = gram_assembly_spec(sp.gram_assembly, sp.axes)
        ext_specs = {}
        for nm in self.plan.names:
            r = red[nm]
            ext_specs[nm] = (gram_spec if r.pairwise
                             else batch if r.streams_rows else P())
        cfg2 = dataclasses.replace(cfg, shard_axes=tuple(sp.axes),
                                   total_batch=n, accum_stats=True,
                                   cross_split=None)
        k = self.num_microbatches

        def body(p, x, y, key, mg_):
            cfg_b = dataclasses.replace(cfg2, total_units=mg_)
            base = _global_sample_offset(sp.axes, n_local)
            lv, grads, logits, ext = _run_accumulated(
                model, p, x, y, loss, extensions, cfg_b, key, k,
                base_offset=base, n_shards=sp.n_shards)
            ext = _assemble_pairwise_ext(ext, red, sp.gram_assembly,
                                         sp.axes)
            return lv, grads, logits, ext

        fn = jax.jit(_shard_map(body, mesh=sp.mesh,
                                in_specs=(P(), batch, batch, P(), P()),
                                out_specs=(P(), P(), batch, ext_specs),
                                check_vma=False))
        lv, grads, logits, ext = fn(params, inputs, targets, rng,
                                    jnp.asarray(mg, jnp.float32))
        return Results(loss=lv, grads=grads, logits=logits, ext=ext)

    # -- preemption-safe streaming (SweepStream) ----------------------------

    def stream(self, model, params, inputs, targets, loss,
               cfg: Optional[ExtensionConfig] = None,
               rng: Optional[jax.Array] = None) -> "SweepStream":
        """Build the checkpointable stepwise executor for this plan.

        Returns a :class:`SweepStream` over the same microbatch schedule
        as :meth:`run`, but driven one work unit at a time from the host
        so its accumulator state can be snapshotted between units (and
        restored — possibly in a different process, on a different device
        mesh).  Most callers want :meth:`run_checkpointed` /
        :meth:`resume`, which wrap the drive loop.
        """
        return SweepStream(self, model, params, inputs, targets, loss,
                           cfg=cfg, rng=rng)

    def run_checkpointed(self, model, params, inputs, targets, loss,
                         cfg: Optional[ExtensionConfig] = None,
                         rng: Optional[jax.Array] = None, *,
                         checkpointer=None, checkpoint_every: int = 1,
                         injector=None, resume: bool = False) -> Results:
        """Run the accumulated sweep preemption-safely.

        Drives a :class:`SweepStream` work unit by work unit, saving its
        accumulator state through ``checkpointer`` every
        ``checkpoint_every`` units (plus once at completion).  A process
        killed mid-sweep restarts with ``resume=True`` (or via
        :meth:`resume`) and continues from the last snapshot, producing
        results identical to an uninterrupted run — mask-aware 1/M
        scaling and per-global-sample-index MC keying included.

        Parameters
        ----------
        checkpointer : object, optional
            Duck-typed snapshot store (``repro.train.checkpoint.
            SweepCheckpointer``): ``save(cursor, state, meta)`` and
            ``restore_latest(state_like) -> (cursor, state, meta) | None``.
            ``None`` runs the stream without snapshots.
        checkpoint_every : int
            Save cadence in work units (clamped to >= 1).
        injector : object, optional
            Fault hook called as ``injector.check(cursor)`` before each
            work unit (``repro.train.fault.FailureInjector``) — lets
            tests kill the sweep mid-stream deterministically.
        resume : bool
            When True, restore the latest snapshot from ``checkpointer``
            before driving (a missing snapshot is a cold start, not an
            error; :meth:`resume` is the strict variant).
        """
        stream = self.stream(model, params, inputs, targets, loss,
                             cfg=cfg, rng=rng)
        if resume and checkpointer is not None:
            snap = checkpointer.restore_latest(stream.state_arrays())
            if snap is not None:
                stream.load_state(*snap)
        return _drive_stream(stream, checkpointer, checkpoint_every,
                             injector)

    def resume(self, model, params, inputs, targets, loss, checkpointer,
               cfg: Optional[ExtensionConfig] = None,
               rng: Optional[jax.Array] = None, *,
               checkpoint_every: int = 1, injector=None) -> Results:
        """Restart an interrupted checkpointed sweep — strict.

        The restart counterpart of :meth:`run_checkpointed`: restores the
        latest snapshot from ``checkpointer`` and drives the remaining
        work units.  Raises ``FileNotFoundError`` when no snapshot exists
        (a restart driver that silently recomputes from scratch would
        mask a broken checkpoint path).  The caller must rebuild the
        stream inputs identically (same batch, extensions, loss, cfg and
        rng/``mc_seed``) — the snapshot's schedule metadata is validated
        against the rebuilt stream and mismatches raise with the first
        offending field.  The device mesh may differ: restored
        accumulators are replicated host-side values, so a sweep
        checkpointed on N devices resumes on M unchanged (elastic
        re-sharding).
        """
        stream = self.stream(model, params, inputs, targets, loss,
                             cfg=cfg, rng=rng)
        snap = checkpointer.restore_latest(stream.state_arrays())
        if snap is None:
            raise FileNotFoundError(
                "resume(...) found no sweep snapshot to restore — run "
                "run_checkpointed(...) first, or call it with resume=True "
                "to tolerate a cold start")
        stream.load_state(*snap)
        return _drive_stream(stream, checkpointer, checkpoint_every,
                             injector)


def _drive_stream(stream, checkpointer, checkpoint_every, injector):
    """Drive a :class:`SweepStream` to completion with periodic snapshots.

    ``injector.check(cursor)`` runs *before* each work unit, so a fault
    injected at cursor j leaves units 0..j-1 done and their last snapshot
    on disk — exactly the state a preempted process would leave behind.
    """
    every = max(1, int(checkpoint_every))
    while not stream.done:
        if injector is not None:
            injector.check(stream.cursor)
        stream.step()
        if checkpointer is not None and (stream.done
                                         or stream.cursor % every == 0):
            checkpointer.save(stream.cursor, stream.state_arrays(),
                              stream.schedule_meta())
    return stream.result()


class SweepStream:
    """Stepwise, checkpointable executor of an accumulated sweep.

    The preemption-safe form of :class:`AccumulatedSweepPlan`: the same
    microbatch schedule, but instead of folding every slice inside one
    ``lax.scan`` trace, the schedule is materialized as a host-driven
    list of *work units* — one per microbatch slice, then one per
    off-diagonal Gram/NTK slice pair — and :meth:`step` executes them one
    at a time, folding each result into ``self.state``: a pytree of
    arrays only (summed loss/grads, per-reducer accumulators, preallocated
    per-sample row buffers, monolithic ``[n, n, ...]`` pairwise blocks).

    Between any two units the pair ``(cursor, state)`` is a complete
    snapshot: :meth:`state_arrays` serializes every reducer accumulator
    (``Reducer.serialize``), :meth:`load_state` restores it, and
    :meth:`schedule_meta` carries the schedule invariants a restore is
    validated against.  Because each work unit covers a *global*
    contiguous row range ``[t·m, (t+1)·m)`` and MC factors are keyed per
    global sample index, an interrupted-and-resumed stream reproduces the
    uninterrupted run exactly — and because the folded accumulators are
    replicated host-side values combined through the reducers'
    merge algebra, a snapshot taken on an N-device mesh resumes on an
    M-device mesh unchanged (elastic re-sharding; only per-slice compute
    is re-sharded, never the accumulator state).

    When the plan is sharded, full slices whose rows split evenly over
    the mesh run under ``shard_map`` (pairwise extensions and the uneven
    remainder slice run single-device); pairwise outputs always use the
    monolithic ``[n, n, ...]`` layout regardless of the plan's
    ``gram_assembly``.

    Reducers opt out via ``supports_checkpoint = False`` (accumulator
    state that does not round-trip through ``serialize``/``deserialize``)
    and are rejected at stream construction with an actionable error.
    """

    def __init__(self, plan: "AccumulatedSweepPlan", model, params, inputs,
                 targets, loss, cfg: Optional[ExtensionConfig] = None,
                 rng: Optional[jax.Array] = None):
        cfg = cfg or ExtensionConfig()
        self.plan = plan
        self.model = model
        self.params = params
        self.inputs = inputs
        self.targets = targets
        self.loss = loss
        # Resolve through the plan-carried extension objects first so
        # custom (unregistered) first-sweep extensions stream too; the
        # registry covers the built-in curvature names.
        local = {e.name: e for e in (plan.plan.first_exts
                                     + plan.plan.kron_exts)}
        self.extensions = tuple(local[nm] if nm in local else by_name(nm)
                                for nm in sorted(plan.plan.names))
        self.red = plan._check_extensions(self.extensions)
        bad = sorted(nm for nm, r in self.red.items()
                     if not r.supports_checkpoint)
        if bad:
            kinds = ", ".join(f"{nm} ({self.red[nm].name})" for nm in bad)
            raise ValueError(
                f"extensions [{kinds}] cannot be checkpointed: their "
                "reducers declare supports_checkpoint=False — the "
                "accumulator state does not round-trip through "
                "serialize/deserialize.  Run them on an uncheckpointed "
                "sweep, implement serialize/deserialize on the reducer, "
                "or drop them from the checkpointed plan.")
        self.rng = _default_rng(plan.plan.sweeps, cfg, rng)
        self.pair_names = [e.name for e in self.extensions
                           if self.red[e.name].pairwise]
        self.concat_names = [e.name for e in self.extensions
                             if self.red[e.name].streams_rows]
        self.carry_names = [e.name for e in self.extensions
                            if not (self.red[e.name].pairwise
                                    or self.red[e.name].streams_rows)]
        self._pair_exts = tuple(e for e in self.extensions
                                if e.name in self.pair_names)

        n = jax.tree.leaves(inputs)[0].shape[0]
        k = max(1, min(int(plan.num_microbatches), n))
        self.n = n
        self.m = m = -(-n // k)   # slice rows; last slice may be smaller
        self.k_full = n // m
        self.rem = n - self.k_full * m
        self.n_slices = self.k_full + (1 if self.rem else 0)
        self.n_shards = (plan.sharded.n_shards
                         if plan.sharded is not None else 1)

        # The canonical schedule is mesh-independent: slices cover global
        # contiguous row ranges, so the global sample index of batch row
        # r is r in every lane — the invariant MC-draw exactness and
        # elastic resume both rest on.
        mg = loss.num_units(targets)
        self.cfg = dataclasses.replace(
            cfg, shard_axes=None, total_units=jnp.asarray(mg, jnp.float32),
            total_batch=n, accum_stats=True, cross_split=None)

        units = [("slice", t) for t in range(self.n_slices)]
        if self.pair_names:
            units += [("pair", p * m, q * m, m)
                      for p in range(self.k_full)
                      for q in range(p + 1, self.k_full)]
            if self.rem:
                units += [("pair", p * m, self.k_full * m, self.rem)
                          for p in range(self.k_full)]
        self.units = units
        self._cursor = 0
        self._jit_cache = {}
        self._slice_jit = jax.jit(self._slice_results)
        self.state = self._init_state()

    # -- schedule -----------------------------------------------------------

    @property
    def cursor(self) -> int:
        """Index of the next work unit to execute (== the snapshot step)."""
        return self._cursor

    @property
    def num_units(self) -> int:
        """Total work units: slices, then off-diagonal pair passes."""
        return len(self.units)

    @property
    def done(self) -> bool:
        return self._cursor >= len(self.units)

    def describe(self) -> str:
        pairs = len(self.units) - self.n_slices
        return (f"{self.plan.describe()} | stream: {self.n_slices} slice "
                f"units ({self.m} rows each) + {pairs} pair units, "
                f"cursor={self._cursor}/{len(self.units)}")

    # -- per-unit execution -------------------------------------------------

    def _slice_results(self, params, rng, x_i, y_i, off):
        cfg_i = dataclasses.replace(self.cfg, sample_offset=off)
        res = run(self.model, params, x_i, y_i, self.loss,
                  extensions=self.extensions, cfg=cfg_i, rng=rng)
        return (res.loss, res.grads,
                {nm: res.ext[nm] for nm in self.carry_names},
                res.logits,
                {nm: res.ext[nm] for nm in self.concat_names},
                {nm: res.ext[nm] for nm in self.pair_names})

    def _init_state(self):
        def head(a):
            return a[:self.m]

        shapes = jax.eval_shape(
            self._slice_results, self.params, self.rng,
            jax.tree.map(head, self.inputs),
            jax.tree.map(head, self.targets), 0)
        lv_s, g_s, carry_s, z_s, rows_s, pair_s = shapes

        def zeros(s):
            return jnp.zeros(s.shape, s.dtype)

        def rows_buf(s):
            return jnp.zeros((self.n,) + s.shape[1:], s.dtype)

        def pair_buf(s):
            return jnp.zeros((self.n, self.n) + s.shape[2:], s.dtype)

        return {
            "loss": zeros(lv_s),
            "grads": jax.tree.map(zeros, g_s),
            "carry": {nm: self.red[nm].init(
                          jax.tree.map(zeros, carry_s[nm]))
                      for nm in self.carry_names},
            "logits": jax.tree.map(rows_buf, z_s),
            "rows": {nm: jax.tree.map(rows_buf, rows_s[nm])
                     for nm in self.concat_names},
            "pair": {nm: jax.tree.map(pair_buf, pair_s[nm])
                     for nm in self.pair_names},
        }

    def step(self) -> int:
        """Execute the next work unit; returns the advanced cursor."""
        if self.done:
            raise ValueError("sweep stream already complete — result() "
                             "holds the finalized Results")
        unit = self.units[self._cursor]
        if unit[0] == "slice":
            t = unit[1]
            rows = self.m if t < self.k_full else self.rem
            with obs.span("engine/stream/slice", t=t, rows=rows):
                self._do_slice(t)
        else:
            with obs.span("engine/stream/pair", off_p=unit[1],
                          off_q=unit[2], rows_q=unit[3]):
                self._do_pair(*unit[1:])
        self._cursor += 1
        obs.gauge("engine.stream.cursor", self._cursor)
        return self._cursor

    def _use_shard_map(self, rows) -> bool:
        return (self.plan.sharded is not None and self.n_shards > 1
                and rows % self.n_shards == 0)

    def _sharded_slice(self):
        if "sharded" not in self._jit_cache:
            sp = self.plan.sharded
            axes = tuple(sp.axes)
            batch = P(axes)
            main_exts = tuple(e for e in self.extensions
                              if e.name not in self.pair_names)
            cfg_s = dataclasses.replace(self.cfg, shard_axes=axes)

            def body(p, x, y, key, t_off):
                n_local = jax.tree.leaves(x)[0].shape[0]
                off = t_off + _global_sample_offset(axes, n_local)
                cfg_i = dataclasses.replace(cfg_s, sample_offset=off)
                res = run(self.model, p, x, y, self.loss,
                          extensions=main_exts, cfg=cfg_i, rng=key)
                return (res.loss, res.grads,
                        {nm: res.ext[nm] for nm in self.carry_names},
                        res.logits,
                        {nm: res.ext[nm] for nm in self.concat_names})

            out_specs = (P(), P(), {nm: P() for nm in self.carry_names},
                         batch, {nm: batch for nm in self.concat_names})
            self._jit_cache["sharded"] = jax.jit(_shard_map(
                body, mesh=sp.mesh, in_specs=(P(), batch, batch, P(), P()),
                out_specs=out_specs, check_vma=False))
        return self._jit_cache["sharded"]

    def _pair_diag(self):
        if "pair_diag" not in self._jit_cache:
            def f(params, rng, x_i, y_i, off):
                cfg_i = dataclasses.replace(self.cfg, sample_offset=off)
                res = run(self.model, params, x_i, y_i, self.loss,
                          extensions=self._pair_exts, cfg=cfg_i, rng=rng)
                return {nm: res.ext[nm] for nm in self.pair_names}

            self._jit_cache["pair_diag"] = jax.jit(f)
        return self._jit_cache["pair_diag"]

    def _do_slice(self, t):
        lo = t * self.m
        rows = self.m if t < self.k_full else self.rem

        def cut(a):
            return a[lo:lo + rows]

        x_i = jax.tree.map(cut, self.inputs)
        y_i = jax.tree.map(cut, self.targets)
        off = jnp.int32(lo)
        if self._use_shard_map(rows):
            lv, g, carry, z, rows_ext = self._sharded_slice()(
                self.params, x_i, y_i, self.rng, off)
            pair = (self._pair_diag()(self.params, self.rng, x_i, y_i, off)
                    if self.pair_names else {})
        else:
            lv, g, carry, z, rows_ext, pair = self._slice_jit(
                self.params, self.rng, x_i, y_i, off)

        st = self.state
        # Weights are *global* slice rows against a global total batch —
        # the same w_t / N ratios as the in-scan lanes, but independent of
        # the mesh, so folds commute with elastic re-sharding.
        meta = {"weight": float(rows)}
        st["loss"] = st["loss"] + lv
        st["grads"] = jax.tree.map(jnp.add, st["grads"], g)
        st["carry"] = {nm: self.red[nm].update(st["carry"][nm], carry[nm],
                                               meta)
                       for nm in self.carry_names}

        def put(buf, v):
            return buf.at[lo:lo + rows].set(v.astype(buf.dtype))

        st["logits"] = jax.tree.map(put, st["logits"], z)
        st["rows"] = {nm: jax.tree.map(put, st["rows"][nm], rows_ext[nm])
                      for nm in self.concat_names}

        def put_diag(buf, blk):
            return buf.at[lo:lo + rows, lo:lo + rows].set(
                blk.astype(buf.dtype))

        st["pair"] = {nm: jax.tree.map(put_diag, st["pair"][nm], pair[nm])
                      for nm in self.pair_names}

    def _pair_fn(self, rows_q):
        key = ("pair", rows_q)
        if key not in self._jit_cache:
            m = self.m

            def f(params, rng, inputs, targets, off_p, off_q):
                def cut(a):
                    ap = jax.lax.dynamic_slice_in_dim(a, off_p, m, 0)
                    aq = jax.lax.dynamic_slice_in_dim(a, off_q, rows_q, 0)
                    return jnp.concatenate([ap, aq], 0)

                cfg_p = dataclasses.replace(self.cfg, sample_offset=0,
                                            cross_split=m)
                res = run(self.model, params, jax.tree.map(cut, inputs),
                          jax.tree.map(cut, targets), self.loss,
                          extensions=self._pair_exts, cfg=cfg_p, rng=rng)
                return {nm: res.ext[nm] for nm in self.pair_names}

            self._jit_cache[key] = jax.jit(f)
        return self._jit_cache[key]

    def _do_pair(self, off_p, off_q, rows_q):
        pext = self._pair_fn(rows_q)(self.params, self.rng, self.inputs,
                                     self.targets, jnp.int32(off_p),
                                     jnp.int32(off_q))
        st = self.state

        def put(buf, blk, reducer):
            tail0 = (0,) * (buf.ndim - 2)
            buf = jax.lax.dynamic_update_slice(
                buf, blk.astype(buf.dtype), (off_p, off_q) + tail0)
            bot = reducer.transpose_block(blk).astype(buf.dtype)
            return jax.lax.dynamic_update_slice(
                buf, bot, (off_q, off_p) + tail0)

        st["pair"] = {nm: jax.tree.map(
                          lambda a, b, r=self.red[nm]: put(a, b, r),
                          st["pair"][nm], pext[nm])
                      for nm in self.pair_names}

    # -- snapshots ----------------------------------------------------------

    def state_arrays(self):
        """The checkpoint payload: ``self.state`` with every reducer
        accumulator passed through :meth:`Reducer.serialize` — a pytree
        of arrays with stable structure and leaf shapes across the whole
        stream lifetime (what the checkpoint layer validates against)."""
        st = dict(self.state)
        st["carry"] = {nm: self.red[nm].serialize(self.state["carry"][nm])
                       for nm in self.carry_names}
        return st

    def schedule_meta(self) -> dict:
        """JSON-able schedule invariants saved next to each snapshot.

        Everything a resumed stream must rebuild identically — batch
        rows, slice schedule, extension set, loss, MC configuration and
        the PRNG key data.  ``n_shards`` is informational only: elastic
        resume legitimately changes it.
        """
        try:
            key_data = jax.random.key_data(self.rng)
        except (TypeError, AttributeError):
            key_data = self.rng
        return {
            "n": int(self.n),
            "num_microbatches": int(self.plan.num_microbatches),
            "slice_rows": int(self.m),
            "work_units": len(self.units),
            "extensions": sorted(self.plan.plan.names),
            "loss": type(self.loss).__name__,
            "mc_samples": int(self.cfg.mc_samples),
            "rng": [int(v) for v in
                    jax.device_get(key_data).ravel().tolist()],
            "n_shards": int(self.n_shards),
        }

    _ELASTIC_META = ("n_shards",)

    def check_meta(self, meta: dict) -> None:
        """Validate a snapshot's schedule metadata against this stream —
        raises ``ValueError`` naming the first mismatching field."""
        here = self.schedule_meta()
        for field, now in here.items():
            if field in self._ELASTIC_META or field not in meta:
                continue
            if meta[field] != now:
                raise ValueError(
                    "sweep snapshot does not match this stream: field "
                    f"{field!r} was {meta[field]!r} at save time but is "
                    f"{now!r} now — resume must rebuild the stream with "
                    "the identical batch, microbatch schedule, "
                    "extensions, loss and rng/mc_seed (only the device "
                    "mesh may change)")

    def load_state(self, cursor, arrays, meta: Optional[dict] = None):
        """Restore a snapshot: cursor + serialized state (+ validated
        schedule metadata, when the checkpointer kept it)."""
        if meta is not None:
            self.check_meta(meta)
        cursor = int(cursor)
        if not 0 <= cursor <= len(self.units):
            raise ValueError(
                f"sweep snapshot cursor {cursor} outside this stream's "
                f"schedule of {len(self.units)} work units")
        # Snapshots come back as host (numpy) arrays — re-ingest onto the
        # current backend before folding continues.
        arrays = dict(jax.tree.map(jnp.asarray, arrays))
        arrays["carry"] = {nm: self.red[nm].deserialize(
                               arrays["carry"][nm])
                           for nm in self.carry_names}
        self.state = arrays
        self._cursor = cursor

    # -- finalize -----------------------------------------------------------

    def result(self) -> Results:
        """Finalize every accumulator — only valid once ``done``."""
        if not self.done:
            raise ValueError(
                f"sweep stream incomplete ({self._cursor}/"
                f"{len(self.units)} work units) — drive step() to "
                "completion (or use run_checkpointed) before result()")
        st = self.state
        meta_fin = {"total_batch": float(self.n),
                    "total_units": self.cfg.total_units}
        if "kfra" in self.carry_names:
            meta_fin["replay"] = lambda gbar, parts: _merge_stat_trees(
                self.model.kfra_apply(self.params, gbar, parts,
                                      self.extensions, self.cfg)[1],
                "kfra")
        ext = {}
        for nm in self.carry_names:
            with obs.span("engine/finalize", ext=nm,
                          reducer=self.red[nm].name):
                ext[nm] = self.red[nm].finalize(st["carry"][nm], meta_fin)
        ext.update(st["rows"])
        for nm in self.pair_names:
            ext[nm] = st["pair"][nm]
        return Results(loss=st["loss"], grads=st["grads"],
                       logits=st["logits"], ext=ext)


def run(
    model: Module,
    params,
    inputs,
    targets,
    loss,
    extensions: Sequence[Extension] = (),
    cfg: Optional[ExtensionConfig] = None,
    rng: Optional[jax.Array] = None,
) -> Results:
    """One generalized backward pass: batch gradient + K extensions.

    The engine's front door (re-exported as ``repro.core.run``).  A
    single forward pass is followed by the sweeps the extension set
    needs — the cotangent sweep always runs (it produces the batch
    gradient and every first-order statistic), plus at most one factor
    sweep per curvature family: the exact loss-Hessian factorization
    ``S`` with ``S Sᵀ = ∇²_z L`` (Eq. 15/18), its Monte-Carlo counterpart
    (Eq. 20), the averaged Ḡ recursion (Eq. 24), or the signed residual
    factors of the exact Hessian diagonal (Eq. 25/26).

    Parameters
    ----------
    model : Module
        A ``repro.core`` module tree (e.g. ``Sequential`` of layers).
    params
        Parameter pytree, as returned by ``model.init``.
    inputs : array or pytree
        Batch inputs, leading sample axis N.
    targets : array
        Loss targets; ``CrossEntropyLoss`` masks positions with
        ``targets < 0``.
    loss
        ``CrossEntropyLoss`` or ``MSELoss`` (anything exposing the
        ``repro.core.loss_hessian`` derivative protocol).
    extensions : sequence of Extension
        Quantities to extract, e.g. ``(BatchL2, Variance, KFAC)``.
    cfg : ExtensionConfig, optional
        Kernel routing, MC sample count/seed, class chunking,
        microbatch size; see :class:`ExtensionConfig`.
    rng : jax.Array, optional
        PRNG key for the MC factor sweep.  Optional when
        ``cfg.mc_seed`` is set; required (or the seed) whenever an MC
        extension (DiagGGNMC / KFAC) is requested.

    Returns
    -------
    Results
        ``loss`` (scalar mean loss), ``grads`` (params-shaped pytree),
        ``logits`` ``[N, ..., C]``, and ``ext[name]`` — one entry per
        requested extension mirroring the params structure: per-sample
        rows ``[N, ...]`` for BatchGrad/BatchL2/GGNTrace, ``[N, N]``
        Gram matrices for BatchDot, parameter-shaped reductions for the
        moments and GGN/Hessian diagonals (Eq. 19), and per-layer
        ``{'A': [a, a], 'B': [b, b]}`` Kronecker blocks (Eq. 23) for
        KFAC/KFLR/KFRA.

    Notes
    -----
    Pure-functional and jit-compatible; wrap in ``jax.jit`` freely.  For
    batches beyond device memory or multi-device execution, bind the
    plan first: ``plan_sweeps(exts, cfg).shard(mesh).accumulate(k).run(...)``.
    """
    cfg = cfg or ExtensionConfig()
    plan = plan_sweeps(extensions, cfg)
    sweeps = plan.sweeps
    first_exts, kron_exts = plan.first_exts, plan.kron_exts
    # Inside a shard_map body (the ShardedSweepPlan lane) and/or a
    # microbatch body (the AccumulatedSweepPlan lane): correct the loss
    # normalization from partial-batch to global so every per-sample
    # quantity below matches its monolithic single-device value.
    axes = cfg.shard_axes
    if axes or cfg.total_units is not None:
        loss = _ScaledLoss(loss, axes or (), cfg.total_units,
                           cfg.sample_offset)

    # ---- forward ----------------------------------------------------------
    with jax.named_scope("fwd_tape"):
        z, tape = model.forward_tape(params, inputs)
        loss_val = loss.value(z, targets)

    # ---- first-order sweep -------------------------------------------------
    # Each layer's stat hook recomputes plan.fused_mask from `first_exts`
    # (the mapping is pure), so with cfg.use_kernels the whole sweep is one
    # fused kernel launch per parameterized layer.
    with jax.named_scope("first_order_sweep"):
        g = loss.grad(z, targets)
        g_in, grads, stats = model.backward(
            params, tape, g, first_exts + kron_exts, cfg
        )

    ext: Dict[str, Any] = {}
    names = plan.names
    if "batch_grad" in names:
        ext["batch_grad"] = _merge_stat_trees(stats, "batch_grad")
    if "batch_l2" in names:
        ext["batch_l2"] = _merge_stat_trees(stats, "batch_l2")
    if "batch_dot" in names:
        ext["batch_dot"] = _merge_stat_trees(stats, "batch_dot")
    if "second_moment" in names or "variance" in names:
        sum_g2 = _merge_stat_trees(stats, "_sum_grad2")
        n = jax.tree.leaves(inputs)[0].shape[0]
        if cfg.total_batch is not None:
            # Accumulated lane: SecondMoment/Variance scale with the raw
            # batch size of the WHOLE accumulated batch, not this
            # microbatch's slice.
            n_total = jnp.float32(cfg.total_batch)
        else:
            n_total = (jnp.float32(n) * _axis_count(axes) if axes
                       else float(n))
        if "second_moment" in names:
            ext["second_moment"] = jax.tree.map(
                lambda s: s * n_total, sum_g2
            )
        if "variance" in names:
            if cfg.accum_stats:
                # Accumulation-driver body: emit the mergeable raw
                # (count, mean, M2) triple for this partial batch — the
                # driver folds triples across microbatches with the
                # pairwise Chan merge and finalizes n·M2 at the end.
                # Under a sharded microbatch the triple is already merged
                # across shards (and replicated).
                def triple(s, gr):
                    t = (_sharded_moment_triple(s, gr, n, axes) if axes
                         else _moment_triple(s, gr, n))
                    return {"n": t[0], "mean": t[1], "m2": t[2]}

                ext["variance"] = _zip_stats(triple, sum_g2, grads)
            elif axes:
                # moment-merge reducer: local (Σg, Σg²) pairs combine
                # across shards via stable pairwise Chan merges; the
                # result is already global (reducer 'moment_merge').
                ext["variance"] = _zip_stats(
                    lambda s, gr: _sharded_variance(s, gr, n, axes),
                    sum_g2, grads)
            else:
                def var(s, gr):
                    return s * float(n) - gr.astype(jnp.float32) ** 2

                ext["variance"] = _zip_stats(var, sum_g2, grads)
    kron_a = _merge_stat_trees(stats, "_kron_a") if kron_exts else None

    # ---- GGN sweeps ---------------------------------------------------------
    if "ggn_exact" in sweeps:
        exact_exts = tuple(e for e in extensions if e.sweep == "ggn_exact")
        C = loss.n_exact_cols(z)  # U·C columns for token-factored losses
        chunk = cfg.class_chunk
        if "ggn_gram" in names and chunk is not None and chunk < C:
            # Cross-column Gram entries K[·,·,c,c'] pair columns across
            # chunks — a chunked scan only ever sees one chunk's columns.
            raise ValueError(
                "GGNGram is incompatible with class_chunk: the logit-space "
                "Gram needs all C̃ columns of the sqrt-Hessian factor at "
                "once (cross-chunk column pairs are unformable)")
        if chunk is None or chunk >= C:
            with jax.named_scope("ggn_exact_sweep"):
                S = loss.sqrt_hessian(z, targets)
                _, curv = model.curv_backward(params, tape, S, exact_exts,
                                              cfg, "exact")
        else:
            n_chunks = -(-C // chunk)

            def body(acc, i):
                Sc = loss.sqrt_hessian_chunk(z, targets, i * chunk, chunk)
                _, cv = model.curv_backward(params, tape, Sc, exact_exts, cfg, "exact")
                return _tree_add(acc, cv), None

            S0 = loss.sqrt_hessian_chunk(z, targets, 0, chunk)
            _, curv0 = model.curv_backward(params, tape, S0, exact_exts, cfg, "exact")
            zero = jax.tree.map(jnp.zeros_like, curv0)
            with jax.named_scope(f"chunkscan_T{n_chunks}"):
                curv, _ = jax.lax.scan(body, zero, jnp.arange(n_chunks))
        if "diag_ggn" in names:
            ext["diag_ggn"] = _merge_stat_trees(curv, "diag_ggn")
        if "kflr" in names:
            ext["kflr"] = _combine_kron(curv, kron_a, "kflr")
        if "ggn_trace" in names:
            ext["ggn_trace"] = _merge_stat_trees(curv, "ggn_trace")
        if "ggn_gram" in names:
            ext["ggn_gram"] = _merge_stat_trees(curv, "ggn_gram")

    if "ggn_mc" in sweeps:
        mc_exts = tuple(e for e in extensions if e.sweep == "ggn_mc")
        rng = _default_rng(sweeps, cfg, rng)
        with jax.named_scope("ggn_mc_sweep"):
            S = loss.sqrt_hessian_mc(rng, z, targets, cfg.mc_samples)
            _, curv = model.curv_backward(params, tape, S, mc_exts, cfg,
                                          "mc")
        if "diag_ggn_mc" in names:
            ext["diag_ggn_mc"] = _merge_stat_trees(curv, "diag_ggn_mc")
        if "kfac" in names:
            ext["kfac"] = _combine_kron(curv, kron_a, "kfac")

    # ---- raw-Jacobian sweep (empirical NTK family) --------------------------
    if "jac" in sweeps:
        jac_exts = tuple(e for e in extensions if e.sweep == "jac")
        if z.ndim != 2:
            raise ValueError(
                "NTK extensions need flat [N, C] model outputs, got logits "
                f"of shape {z.shape} — reduce the sequence axis before the "
                "head or restrict the NTK to a flat-output model")
        C = z.shape[-1]
        # Identity cotangents per class: S0[c, n, :] = e_c.  The transposed-
        # Jacobian sweep then yields raw per-sample Jacobian factors — no
        # loss curvature, no 1/M scaling, no MC draws.
        S0 = jnp.broadcast_to(jnp.eye(C, dtype=jnp.float32)[:, None, :],
                              (C, z.shape[0], C))
        _, jcurv = model.curv_backward(params, tape, S0, jac_exts, cfg, "ntk")
        if "ntk" in names:
            ext["ntk"] = _merge_stat_trees(jcurv, "ntk")
        if "ntk_classwise" in names:
            ext["ntk_classwise"] = _merge_stat_trees(jcurv, "ntk_classwise")

    # ---- chain-only sweeps ---------------------------------------------------
    if "kfra" in sweeps:
        Gbar = loss.hessian_mean(z, targets)
        if cfg.accum_stats:
            # Accumulation-driver body: emit the streamable halves of the
            # recursion — the global Ḡ contribution plus the per-layer
            # batch-expectation partials.  The driver's MeanReducer folds
            # both across microbatches and replays the chain recursion
            # once at the end (exact: every batch-dependent quantity in
            # Eq. 24 is a batch mean).
            ext["kfra"] = {"gbar": Gbar,
                           "partials": model.kfra_partials(params, tape,
                                                           cfg)}
        else:
            _, kstats = model.kfra_backward(params, tape, Gbar, extensions,
                                            cfg)
            ext["kfra"] = _merge_stat_trees(kstats, "kfra")

    if "hess" in sweeps:
        S = loss.sqrt_hessian(z, targets)
        g0 = loss.grad(z, targets)
        _, _, hstats = model.hess_backward(
            params, tape, g0, [(S, 1.0)], extensions, cfg
        )
        ext["diag_hessian"] = _merge_stat_trees(hstats, "diag_hessian")

    if axes:
        grads, ext = _reduce_sharded(grads, ext, extensions, axes)
    return Results(loss=loss_val, grads=grads, logits=z, ext=ext)


def _combine_kron(curv_stats, kron_a_stats, name):
    """Zip B-factors (curvature sweep) with A-factors (first sweep)."""
    b_tree = _merge_stat_trees(curv_stats, name)

    def rec(b_node, a_node):
        if b_node is None:
            return None
        if isinstance(b_node, dict) and b_node and set(b_node) <= {"w", "b", "g"}:
            # module-level stats dict ({'w': {'B': ...}, 'b': ...})
            out = {}
            for k, v in b_node.items():
                entry = dict(v) if isinstance(v, dict) else {"B": v}
                if a_node is not None and isinstance(a_node, dict) and k in a_node:
                    entry["A"] = a_node[k]
                out[k] = entry
            return out
        if isinstance(b_node, dict):
            # structural dict (Wired child names) — recurse
            return {
                k: rec(v, a_node.get(k) if isinstance(a_node, dict) else None)
                for k, v in b_node.items()
            }
        if isinstance(b_node, (tuple, list)):
            a_children = a_node if isinstance(a_node, (tuple, list)) else (None,) * len(b_node)
            return tuple(rec(bc, ac) for bc, ac in zip(b_node, a_children))
        return b_node

    return rec(b_tree, kron_a_stats)


def ntk_total(ext_tree):
    """Sum a per-parameter NTK stats tree into the total kernel.

    ``run(...).ext['ntk']`` mirrors the params structure with one
    ``[N, N]`` block per parameter leaf (``[N, N, C]`` for
    ``ntk_classwise``) — the empirical NTK Θ(x, x') = J Jᵀ is their sum.
    Works on sharded row-block layouts too (the leaves just carry the
    lane's row/assembly shape).
    """
    leaves = jax.tree.leaves(ext_tree)
    if not leaves:
        raise ValueError("empty NTK stats tree — was the extension run?")
    out = leaves[0].astype(jnp.float32)
    for leaf in leaves[1:]:
        out = out + leaf.astype(jnp.float32)
    return out


def gram_total(ext_tree):
    """Sum a per-parameter ``ggn_gram`` stats tree into the total kernel.

    ``run(...).ext['ggn_gram']`` mirrors the params structure with one
    ``[N, N, C̃, C̃]`` loss-scaled logit-Gram block per parameter leaf;
    their sum is the full half-sandwich kernel ``K = J' J'ᵀ`` with
    ``J' = √Hᵀ J`` — exactly the ``[N·C̃, N·C̃]`` operator kernel-space
    natural gradients invert.  Layout matches :func:`ntk_total` (sample
    axes leading), so sharded/streamed row-block leaves sum the same way.
    """
    leaves = jax.tree.leaves(ext_tree)
    if not leaves:
        raise ValueError("empty GGN-Gram stats tree — was the extension "
                         "run?")
    out = leaves[0].astype(jnp.float32)
    for leaf in leaves[1:]:
        out = out + leaf.astype(jnp.float32)
    return out


def loss_and_grad(model, params, inputs, targets, loss):
    """Plain training objective — the baseline backward pass."""
    res = run(model, params, inputs, targets, loss, extensions=())
    return res.loss, res.grads


def local_loss_and_grad(model, params, inputs, targets, loss, axes):
    """Inside ``shard_map``: global mean loss + this shard's *unreduced*
    gradient contribution, already carrying the global 1/M normalization.

    The seam the compressed-DP step needs — it compresses the local
    contribution (with error feedback) *before* the explicit psum, which
    the engine's own sharded lane would otherwise have performed
    internally.  ``psum(local grads) == run(...).grads`` exactly.
    """
    sloss = _ScaledLoss(loss, axes)
    z, tape = model.forward_tape(params, inputs)
    lv = sloss.value(z, targets)
    g = sloss.grad(z, targets)
    _, grads, _ = model.backward(params, tape, g, (), ExtensionConfig())
    return lv, grads

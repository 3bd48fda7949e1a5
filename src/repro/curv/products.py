"""Forward-over-reverse curvature-vector products.

The GGN-vector product is the half-sandwich contraction

    G v = Jᵀ H (J v)

evaluated matrix-free: one ``jax.linearize`` through the network gives
``J v`` (forward mode), the exact loss Hessian applies in logit space via
``loss.hessian_vec`` (closed form, :mod:`repro.core.loss_hessian`), and
the transposed linearization carries it back to parameter space.  Cost is
~2 gradient evaluations per product, memory is O(P) — no factor is ever
materialized, so every architecture the explicit lanes can't touch
(LM heads with 10⁵-class vocabularies, full transformers) is in scope.

The Hessian-vector product is plain forward-over-reverse through the
scalar objective: ``H v = ∂/∂ε ∇L(θ + εv)|₀``.

Scale composition mirrors the engine's sweep lanes: ``microbatch_size``
streams the product over batch slices and ``mesh`` shards the batch rows,
each partial batch corrected from 1/M_local to 1/M_global by the
mask-aware ``_ScaledLoss`` adapter — products are *linear* in the loss,
so the corrected contributions sum to the monolithic value exactly, even
with padding masks leaving unit counts uneven across slices or shards.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.engine import _ScaledLoss, _shard_map
from repro.core.extensions import ExtensionConfig


def _slice_bounds(n: int, microbatch: Optional[int]):
    """Static (offset, rows) schedule over ``n`` samples — uneven final
    slice allowed (the streamed lanes' schedule, in miniature)."""
    if not microbatch or microbatch >= n:
        return [(0, n)]
    return [(o, min(microbatch, n - o)) for o in range(0, n, microbatch)]


def _take_rows(tree, off, rows):
    return jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, off, rows,
                                                               0), tree)


def _ggn_vp_block(model, params, inputs, targets, loss, v):
    """Single-block product: linearize once, transpose the linearization."""
    def f(p):
        return model.apply(p, inputs)

    z, jvp_fn = jax.linearize(f, params)
    Jv = jvp_fn(v)
    Hv = loss.hessian_vec(z, targets, Jv)
    vjp_fn = jax.linear_transpose(jvp_fn, params)
    (out,) = vjp_fn(Hv)
    return out


def _hvp_block(model, params, inputs, targets, loss, v):
    def obj(p):
        return loss.value(model.apply(p, inputs), targets)

    return jax.jvp(jax.grad(obj), (params,), (v,))[1]


def _streamed(block_fn, model, params, inputs, targets, loss, v,
              microbatch, total_units=None):
    """Sum the per-slice contributions under the 1/M_global correction.

    ``total_units`` overrides the global unit count (the sharded body
    passes the psum'd global count so the shard × accumulate composition
    applies exactly one correction).
    """
    n = jax.tree.leaves(inputs)[0].shape[0]
    bounds = _slice_bounds(n, microbatch)
    if len(bounds) == 1 and total_units is None:
        return block_fn(model, params, inputs, targets, loss, v)
    # raw mask-aware unit count over this lane's full batch
    mg = total_units if total_units is not None else loss.num_units(targets)
    out = None
    for off, rows in bounds:
        sloss = _ScaledLoss(loss, total_units=mg)
        o = block_fn(model, params, _take_rows(inputs, off, rows),
                     _take_rows(targets, off, rows), sloss, v)
        out = o if out is None else jax.tree.map(jnp.add, out, o)
    return out


def _product(block_fn, model, params, inputs, targets, loss, v, *,
             cfg: Optional[ExtensionConfig] = None, mesh=None,
             shard_axes: Sequence[str] = ("data",)):
    cfg = cfg or ExtensionConfig()
    microbatch = cfg.microbatch_size
    if mesh is None:
        return _streamed(block_fn, model, params, inputs, targets, loss, v,
                         microbatch)
    axes = tuple(shard_axes)
    batch = P(axes)

    def body(params, inputs, targets, v):
        # Global unit count first (a psum sees every shard's rows), then
        # stream this shard's rows against it — the shard × accumulate
        # composition applies exactly one 1/M_global correction.
        raw = loss.num_units(targets)
        mg = jnp.maximum(jax.lax.psum(raw, axes), 1.0)
        out = _streamed(block_fn, model, params, inputs, targets, loss, v,
                        microbatch, total_units=mg)
        return jax.lax.psum(out, axes)

    fn = jax.jit(_shard_map(body, mesh=mesh,
                            in_specs=(P(), batch, batch, P()),
                            out_specs=P()))
    return fn(params, inputs, targets, v)


def ggn_vp(model, params, inputs, targets, loss, v, *, cfg=None, mesh=None,
           shard_axes=("data",)):
    """Matrix-free GGN-vector product ``(Jᵀ H J) v`` of the mean loss.

    ``v`` is a params-like tangent pytree; the result has the same
    structure.  ``cfg=ExtensionConfig(microbatch_size=k)`` streams the
    contraction over batch slices; ``mesh`` runs it batch-sharded over
    ``shard_axes`` — both exact, per the ``_ScaledLoss`` correction.
    """
    return _product(_ggn_vp_block, model, params, inputs, targets, loss, v,
                    cfg=cfg, mesh=mesh, shard_axes=shard_axes)


def hvp(model, params, inputs, targets, loss, v, *, cfg=None, mesh=None,
        shard_axes=("data",)):
    """Matrix-free Hessian-vector product ``∇²L(θ) v`` of the mean loss
    (forward-over-reverse: jvp of the gradient).  Same composition knobs
    as :func:`ggn_vp`."""
    return _product(_hvp_block, model, params, inputs, targets, loss, v,
                    cfg=cfg, mesh=mesh, shard_axes=shard_axes)


class _CurvOperator:
    """A curvature matrix as a linear operator on params-like pytrees.

    ``mv`` applies ``(C + damping·I) v``; ``mv_stacked`` maps it over a
    leading probe/RHS axis on every leaf (the batched-CG and SLQ
    callers).  Instances close over one batch — build a new operator per
    batch, reuse it across products (CG iterations re-trace nothing
    under jit).
    """

    _block = None  # subclass hook

    def __init__(self, model, params, inputs, targets, loss, *,
                 damping: float = 0.0, cfg: Optional[ExtensionConfig] = None,
                 mesh=None, shard_axes: Sequence[str] = ("data",)):
        self.model = model
        self.params = params
        self.inputs = inputs
        self.targets = targets
        self.loss = loss
        self.damping = damping
        self.cfg = cfg
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes)

    def mv(self, v):
        out = _product(type(self)._block, self.model, self.params,
                       self.inputs, self.targets, self.loss, v,
                       cfg=self.cfg, mesh=self.mesh,
                       shard_axes=self.shard_axes)
        if self.damping:
            d = jnp.float32(self.damping)
            out = jax.tree.map(
                lambda o, t: o + d * t.astype(o.dtype), out, v)
        return out

    def mv_stacked(self, V):
        return jax.vmap(self.mv)(V)

    @property
    def dim(self) -> int:
        """Number of parameters the operator acts on."""
        return sum(l.size for l in jax.tree.leaves(self.params))


class GGNOperator(_CurvOperator):
    """``(G + damping·I)`` with ``G`` the GGN of the mean loss."""

    _block = staticmethod(_ggn_vp_block)


class HessianOperator(_CurvOperator):
    """``(H + damping·I)`` with ``H`` the full Hessian of the mean loss."""

    _block = staticmethod(_hvp_block)

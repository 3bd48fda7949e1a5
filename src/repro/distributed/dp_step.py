"""Explicit data-parallel step via shard_map: compressed all-reduce with
error feedback.

The implicit-SPMD path (jit + sharded batch) reduces gradients in f32
inside XLA's backward — there is no seam to compress at.  This step makes
the DP reduction *explicit*, and it is built on the engine's sharded-sweep
machinery (``engine.local_loss_and_grad``): the shard body runs the
scale-corrected local backward — each shard's gradient contribution
already carries the *global* 1/M normalization, exactly as inside
``SweepPlan.shard``'s lane — so the compressed psum is the only
distributed arithmetic left here.  Per-shard gradients are compressed to
bf16 with a per-shard error-feedback residual, psum'd over the data axes,
and decompressed — halving the dominant DP collective's bytes while the
accumulated update stays unbiased (error feedback, Karimireddy et al.
2019).  Riding the engine seam also fixes the mean-of-local-means loss:
``local_loss_and_grad`` psums the mask-aware unit counts, so the reported
loss is the exact global mean even with uneven padding across shards.

Scope: pure-DP over ('data',) / ('pod','data'); TP-sharded params use the
implicit path (their activation collectives are latency-bound, not
bandwidth-bound).  The error-feedback tree carries a leading shard axis
([D, *param_shape]) so each data shard keeps its own residual.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engine as eng
from repro.distributed.compress import compress_with_ef
from repro.optim.optimizers import apply_updates


def init_ef_sharded(params, n_shards):
    return jax.tree.map(
        lambda p: jnp.zeros((n_shards,) + p.shape, jnp.float32), params)


def make_compressed_dp_step(model, loss, opt, mesh, data_axes=("data",)):
    batch_spec = jax.tree.map(lambda _: P(data_axes), {"inputs": 0, "labels": 0})

    def shard_body(params, ef, batch):
        # Scale-corrected local sweep (the sharded lane's seam): lv is the
        # exact global mean loss, g the shard's unreduced contribution to
        # the global gradient.
        lv, g = eng.local_loss_and_grad(
            model, params, batch["inputs"], batch["labels"], loss, data_axes)
        ef_local = jax.tree.map(lambda e: e[0], ef)
        comp, new_ef = compress_with_ef(g, ef_local)
        g_sum = jax.tree.map(
            lambda c: jax.lax.psum(c, data_axes).astype(jnp.float32), comp)
        new_ef = jax.tree.map(lambda e: e[None], new_ef)
        return lv, g_sum, new_ef

    smapped = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), jax.tree.map(lambda _: P(data_axes), 0), batch_spec),
        out_specs=(P(), P(), jax.tree.map(lambda _: P(data_axes), 0)),
        check_vma=False,
    )

    def step(params, opt_state, ef, batch):
        lv, g_sum, new_ef = smapped(params, ef, batch)
        ups, opt_state = opt.update(g_sum, opt_state, params)
        params = apply_updates(params, ups)
        return params, opt_state, new_ef, lv

    return step

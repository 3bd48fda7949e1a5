"""Flash-attention forward Pallas kernel (causal + sliding window, GQA).

Online-softmax over k-blocks with the running (m, l, acc) state held in
VMEM scratch — the [T×S] logits/probability matrices never exist in HBM.
This is the kernel the §Perf "Pallas-fused" accounting models: per q-block
the HBM traffic is (q block in, k/v blocks streamed, out block written).

Grid: (batch, kv_head, q_blocks) with the k-loop INSIDE the kernel body
(lax.fori_loop over k blocks) so the accumulators stay resident.
Backward on TPU would recompute per-block (standard flash bwd); training
uses the jnp `sdpa_chunked` path whose checkpointed q-blocks implement the
same recompute schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, window, scale,
            seq_q, seq_k):
    # q_ref: [1, bq, g, dh]; k_ref/v_ref: [1, S, dh]; o_ref: [1, bq, g, dh]
    qi = pl.program_id(2)
    bq = q_ref.shape[1]
    g = q_ref.shape[2]
    dh = q_ref.shape[3]
    q = q_ref[0].astype(jnp.float32) * scale
    q2 = q.reshape(bq * g, dh)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]

    n_kb = pl.cdiv(seq_k, block_k)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q2, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(bq, g, block_k)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2)
        mask = k_pos < seq_k
        if causal:
            mask &= q_pos[:, None, None] >= k_pos
        if window is not None:
            mask &= (q_pos[:, None, None] - k_pos) < window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.reshape(bq * g, block_k), v_blk,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).reshape(bq, g, dh)
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, g), jnp.float32)
    a0 = jnp.zeros((bq, g, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           block_q=128, block_k=128, interpret=True):
    """q: [N, T, H, dh]; k/v: [N, S, KV, dh] → [N, T, H, dh]."""
    n, t, h, dh = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = dh ** -0.5
    bq = min(block_q, t)
    grid = (n * kv, 1, pl.cdiv(t, bq))
    qg = q.reshape(n, t, kv, g, dh)
    qg = jnp.moveaxis(qg, 2, 1).reshape(n * kv, t, g, dh)
    kg = jnp.moveaxis(k, 2, 1).reshape(n * kv, s, dh)
    vg = jnp.moveaxis(v, 2, 1).reshape(n * kv, s, dh)
    kern = functools.partial(
        _kernel, block_k=min(block_k, s), causal=causal, window=window,
        scale=scale, seq_q=t, seq_k=s)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, g, dh), lambda b, _, i: (b, i, 0, 0)),
            pl.BlockSpec((1, s, dh), lambda b, _, i: (b, 0, 0)),
            pl.BlockSpec((1, s, dh), lambda b, _, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, g, dh), lambda b, _, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n * kv, t, g, dh), q.dtype),
        interpret=interpret,
    )(qg, kg, vg)
    out = out.reshape(n, kv, t, g, dh)
    return jnp.moveaxis(out, 1, 2).reshape(n, t, h, dh)

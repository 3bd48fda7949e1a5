"""Chunked WKV (RWKV6/SSD) forward Pallas kernel.

The chunk-parallel linear-attention recurrence with the running state
``S [dk, dv]`` held in VMEM scratch across chunk iterations — the kernel
behind the `kernelize` roofline accounting for the `wkvchunk_` scans.

Grid: (batch·heads,) with the chunk loop inside the kernel body; per chunk
the intra-chunk work is two MXU matmuls + the carry update (see
`nn/functional.wkv_chunked` for the algebra; this kernel is its fused
single-(batch,head) instantiation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, *, chunk, n_chunks):
    # refs: [1, T, dk|dv]; u_ref: [1, dk]
    dk = r_ref.shape[2]
    dv = v_ref.shape[2]
    strict = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)
    u = u_ref[0].astype(jnp.float32)

    def body(c, S):
        sl = (0, pl.ds(c * chunk, chunk), slice(None))
        rc = r_ref[sl].astype(jnp.float32)
        kc = k_ref[sl].astype(jnp.float32)
        vc = v_ref[sl].astype(jnp.float32)
        lwc = jnp.clip(lw_ref[sl].astype(jnp.float32), -60.0, -1e-6)
        P = jnp.cumsum(lwc, axis=0)
        E = P - lwc
        r_t = rc * jnp.exp(E)
        k_t = kc * jnp.exp(-P)
        A = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * strict
        y = jax.lax.dot_general(A, vc, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        diag = jnp.sum(rc * u[None] * kc, axis=-1)
        y = y + diag[:, None] * vc
        y = y + jax.lax.dot_general(r_t, S, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        y_ref[sl] = y.astype(y_ref.dtype)
        decay_end = jnp.exp(P[-1])
        k_end = kc * jnp.exp(P[-1][None] - P)
        S_new = decay_end[:, None] * S + jax.lax.dot_general(
            k_end, vc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return S_new

    S = jnp.zeros((dk, dv), jnp.float32)
    S = jax.lax.fori_loop(0, n_chunks, body, S)


def wkv_pallas(r, k, v, log_w, u, *, chunk=64, interpret=True):
    """r,k: [N,T,H,dk]; v: [N,T,H,dv]; log_w like r; u: [H,dk] → y [N,T,H,dv]."""
    n, t, h, dk = r.shape
    dv = v.shape[-1]
    while t % chunk:
        chunk //= 2
    chunk = max(chunk, 1)
    nc = t // chunk

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(n * h, t, x.shape[-1])

    lw = jnp.broadcast_to(log_w, r.shape)
    uu = jnp.broadcast_to(u, (h, dk))
    u_flat = jnp.tile(uu, (n, 1))
    kern = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y = pl.pallas_call(
        kern,
        grid=(n * h,),
        in_specs=[
            pl.BlockSpec((1, t, dk), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, t, dk), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, t, dk), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, dv), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n * h, t, dv), r.dtype),
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(lw), u_flat)
    return jnp.moveaxis(y.reshape(n, h, t, dv), 1, 2)

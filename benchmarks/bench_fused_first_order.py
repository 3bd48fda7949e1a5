"""Fused first-order kernel vs per-extension kernels vs pure jnp.

The tentpole claim: computing {batch_l2, second_moment, batch_dot} together
through ONE fused pass costs ≤ 1.5× batch_l2 alone, where the
one-kernel-per-extension path pays ~3× (three passes over the same
(grad_out, input) pair).  Lanes per Dense benchmark shape (N, R, a, b):

  fused/l2_only     fused kernel, mask = {l2}            (the 1× baseline)
  fused/all3        fused kernel, mask = {l2, moment}, + cross_dot for dot
  per_ext/all3      the seed's per-extension path: batch_l2 kernel +
                    per_sample_moment kernel + jnp Gram-einsum batch_dot
  jnp/all3          pure-jnp einsum oracles

``derived`` carries the ratio vs fused/l2_only.  Numbers here are
interpret-mode (CPU correctness path) — on TPU the same dispatch compiles
Mosaic, and the HBM-traffic argument only gets stronger.

Scaling note: the dot output adds N²·a·b FLOPs on top of the N·R·a·b the
baseline already spends forming G, i.e. a marginal cost of ~N/R of the
baseline; moment and l2 are O(N·a·b) elementwise.  The shapes below are
sequence workloads (R ≥ 4N, the regime per-sample statistics target —
DP-SGD / gradient telemetry over tokens or conv patches), where all three
together stay well under 1.5×.  Batch-dominant shapes (N ≳ R) pay up to
~1 + N/R for the Gram matrix — unavoidable work, not kernel overhead.
"""
from __future__ import annotations

import jax

from benchmarks.common import emit, quick_mode, time_group
from repro.kernels import ops, ref

# Dense benchmark shapes: (N, R, a, b) — batch, sequence, fan-in, fan-out.
SHAPES = [(16, 128, 256, 256), (32, 128, 512, 256)]
QUICK_SHAPES = [(8, 32, 128, 128)]


def _fused(A, B, wl, wm, wd):
    out = ops.fused_first_order(A, B, want_l2=wl, want_moment=wm)
    if wd:
        out["dot"] = ops.cross_dot(A, B, A, B)
    return out


_batch_dot_jnp = jax.jit(lambda A, B: ref.batch_dot(A, B))


def _per_ext(A, B):
    return (ops.batch_l2(A, B),
            ops.per_sample_moment(A, B),
            _batch_dot_jnp(A, B))


def _jnp_all(A, B):
    return (ref.batch_l2(A, B), ref.per_sample_moment(A, B),
            ref.batch_dot(A, B))


def main():
    shapes = QUICK_SHAPES if quick_mode() else SHAPES
    k = jax.random.PRNGKey(0)
    for n, r, a, b in shapes:
        tag = f"N{n}xR{r}x{a}x{b}"
        A = jax.random.normal(k, (n, r, a))
        B = jax.random.normal(jax.random.fold_in(k, 1), (n, r, b))
        jnp_all = jax.jit(_jnp_all)
        times = time_group({
            "fused/l2_only": lambda: _fused(A, B, True, False, False),
            "fused/all3": lambda: _fused(A, B, True, True, True),
            "per_ext/all3": lambda: _per_ext(A, B),
            "jnp/all3": lambda: jnp_all(A, B),
        })
        base = times["fused/l2_only"]
        for lane, t in times.items():
            emit(f"fused_first_order/{tag}/{lane}", t,
                 f"ratio={t / base:.2f}")


if __name__ == "__main__":
    main()

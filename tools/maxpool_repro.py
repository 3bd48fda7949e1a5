"""Reproduce, on a TPU, the zeroed first-pool GGN factor of 3C3D.

``MaxPool2d.jac_t_mat`` once used the generic ``vmap(vjp)`` transpose,
which lowers to a ``select_and_scatter`` batched over the sqrt-GGN
columns.  On a TPU v5e, with the exact and MC GGN sweeps in one jitted
``run`` of 3C3D at N=128, XLA returned zeros for conv1's DiagGGN, KFLR
``B`` and GGNTrace, while each sweep alone, the host CPU and the kernel
route gave the right numbers.  This script prints, for each program,
max|conv1 DiagGGN| and its error relative to the one-extension program:

* the library's 3C3D programs on the jnp route, generic transpose,
  for growing extension sets;
* bare pool transposes (no model) at conv1's shapes, generic transpose,
  against the one-hot form the layer now uses;
* the full program with the layer's own transpose;
* BatchGrad on the device against the host CPU, at default and at
  ``highest`` matmul precision.

Run it where JAX sees the accelerator, from the repository root::

    python tools/maxpool_repro.py [--n 128]

It exits 1 when the layer's own transpose disagrees with the reference;
a zeroed program under the generic transpose is reported, not fatal.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import papernets  # noqa: E402
from repro.core import CrossEntropyLoss, ExtensionConfig, by_name, run  # noqa: E402
from repro.core.module import Module  # noqa: E402
from repro.nn.layers import MaxPool2d  # noqa: E402

FIRST_ORDER = ("batch_grad", "batch_l2", "second_moment", "variance")
EXACT = ("diag_ggn", "kflr", "ggn_trace")
MC = ("diag_ggn_mc", "kfac")
PROGRAMS = {
    "diag_ggn": ("diag_ggn",),
    "exact": EXACT,
    "diag_ggn+diag_ggn_mc": ("diag_ggn", "diag_ggn_mc"),
    "diag_ggn+batch_grad": ("diag_ggn", "batch_grad"),
    "exact+mc": EXACT + MC,
    "first+exact": FIRST_ORDER + EXACT,
    "all": FIRST_ORDER + EXACT + MC,
}


def sweep(model, names):
    exts = tuple(by_name(n) for n in names)
    cfg = ExtensionConfig(use_kernels=False)

    def f(p, x, y, key):
        return run(model, p, x, y, CrossEntropyLoss(), extensions=exts,
                   cfg=cfg, rng=key).ext

    return jax.jit(f)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def pool_programs(n, seed):
    """Bare pool transposes at conv1's shapes: 10 columns, 10 + 1, and
    10 + 1 + the unbatched cotangent, all from one x."""
    mp = MaxPool2d(2)
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.nn.relu(jax.random.normal(k[0], (n, 32, 32, 64)))
    s10 = jax.random.normal(k[1], (10, n, 16, 16, 64))
    s1 = jax.random.normal(k[2], (1, n, 16, 16, 64))
    g = jax.random.normal(k[3], (n, 16, 16, 64))
    want = [mp.jac_t_mat(None, x, s10), mp.jac_t_mat(None, x, s1),
            mp.jac_t_mat(None, x, g[None])[0]]

    def generic(xx, m):
        return Module.jac_t_mat(mp, None, xx, m)

    progs = {
        "pool 10": lambda xx, a, b, c: (generic(xx, a),),
        "pool 10+1": lambda xx, a, b, c: (generic(xx, a), generic(xx, b)),
        "pool 10+1+g": lambda xx, a, b, c: (generic(xx, a), generic(xx, b),
                                            generic(xx, c[None])[0]),
    }
    for label, prog in progs.items():
        got = jax.jit(prog)(x, s10, s1, g)
        errs = ", ".join(f"{rel(a, b):.3g}" for a, b in zip(got, want))
        print(f"{label}: max|out| {float(jnp.max(jnp.abs(got[0]))):.4g}, "
              f"error vs one-hot form {errs}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    model = papernets.c3d3(n_classes=10, in_ch=3, img=32)
    k = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    params = model.init(k[0])
    x = jax.random.normal(k[1], (args.n, 32, 32, 3), jnp.float32)
    y = jax.random.randint(k[2], (args.n,), 0, 10)
    key = jax.random.PRNGKey(args.seed + 1)
    own = MaxPool2d.jac_t_mat
    with jax.default_matmul_precision("highest"):
        MaxPool2d.jac_t_mat = Module.jac_t_mat
        try:
            ref = None
            for label, names in PROGRAMS.items():
                t0 = time.perf_counter()
                d = sweep(model, names)(params, x, y, key)["diag_ggn"][0]["w"]
                ref = d if ref is None else ref
                print(f"generic {label} ({len(names)} extensions): max|conv1 "
                      f"diag_ggn| {float(jnp.max(jnp.abs(d))):.4g}, error "
                      f"{rel(d, ref):.3g} ({time.perf_counter() - t0:.1f} s)",
                      flush=True)
            pool_programs(args.n, args.seed)
        finally:
            MaxPool2d.jac_t_mat = own
        d = sweep(model, PROGRAMS["all"])(params, x, y, key)["diag_ggn"][0]["w"]
        fixed = rel(d, ref)
        print(f"one-hot all: max|conv1 diag_ggn| "
              f"{float(jnp.max(jnp.abs(d))):.4g}, error {fixed:.3g}",
              flush=True)
    cpu = jax.devices("cpu")[0]
    bg = sweep(model, ("batch_grad",))
    for prec in ("default", "highest"):
        with jax.default_matmul_precision(prec):
            a = bg(params, x, y, key)["batch_grad"]
            b = bg(*jax.device_put((params, x, y, key), cpu))["batch_grad"]
        errs = [rel(p, q) for p, q in zip(jax.tree.leaves(a),
                                          jax.tree.leaves(b))]
        print(f"batch_grad {dev.platform} vs cpu at {prec} precision: worst "
              f"error/max {max(errs):.3g}", flush=True)
    return 0 if fixed < 1e-3 else 1


if __name__ == "__main__":
    sys.exit(main())

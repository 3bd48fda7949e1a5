#!/usr/bin/env python3
"""Bring-up smoke test: the extended backward pass on one TPU chip.

    python chip_smoke.py               # phases 1 and 2, one chip
    python chip_smoke.py --four-chips  # phase 3 only, four chips

Phase 1, the paper's path: ``repro.core.run`` on 3C3D (CIFAR-10 shapes
32x32x3, 10 classes) at the paper's batch N=128, with every extension one
plan accepts for it, jitted with the Pallas kernels on and again on the jnp
route.  Every output must agree within ``RTOL``/``ATOL_REL``, and the
compiled kernel program must hold Mosaic calls (``tpu_custom_call``), which
shows the kernels ran compiled and not under the interpreter.

Phase 2, the trainer: ``repro.train.loop.fit`` (what ``launch/train.py``
calls) on stablelm-1.6b at its published widths, with the diag_ggn_mc
curvature optimizer tracking Variance, kernels on, for a few steps.  Every
loss must be finite.

Phase 3 (``--four-chips``, and nothing else then): the batch-sharded sweep
``SweepPlan.shard`` over a four-device data mesh for every Gram assembly
mode, and one ``fit(..., mesh=...)`` step, each compared with the same work
on one device of this process.

Weights and data come from fixed seeds; nothing is read from disk.  Step
times printed on the way are bring-up timings, not benchmark numbers.  The
script exits non-zero, without the result line, when JAX finds no TPU or
any phase fails; on success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Kernel route vs jnp route, both at float32 matmul precision: a leaf
# passes when |kernel - jnp| <= ATOL_REL * max|jnp| + RTOL * |jnp|.
RTOL = 1e-3
ATOL_REL = 1e-4
# 3C3D: KFRA and DiagHessian are left out, as the plan refuses both for
# the Flatten layer between the conv and dense stacks.
PAPER_EXTENSIONS = ("batch_grad", "batch_l2", "batch_dot", "second_moment",
                    "variance", "diag_ggn", "diag_ggn_mc", "kfac", "kflr",
                    "ggn_trace")
# stablelm-1.6b step: all 24 layers and 8 x 256 = 2048 tokens, the largest
# cut a v5e's 16 GB holds by the compiled step's memory analysis.
LM_LAYERS, LM_BATCH, LM_SEQ, LM_STEPS = 24, 8, 256, 5
# The batch-sharded step keeps psum'd copies of every parameter-sized
# statistic per chip: 12 layers (12.4 GB by the same analysis) is the cut
# its four-chip comparison runs at.
LM_MESH_LAYERS = 12


def require(ok, message):
    """Fail the phase (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise AssertionError(message)


def _extensions(names):
    from repro.core import by_name

    return tuple(by_name(n) for n in names)


def _paper_setup(n, img, seed):
    from repro.configs import papernets

    model = papernets.c3d3(n_classes=10, in_ch=3, img=img)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = model.init(k[0])
    x = jax.random.normal(k[1], (n, img, img, 3), jnp.float32)
    y = jax.random.randint(k[2], (n,), 0, 10)
    return model, params, x, y


def _leaves(tree):
    return [(jax.tree_util.keystr(p), np.asarray(v, np.float32))
            for p, v in jax.tree_util.tree_leaves_with_path(tree)]


def compare(label, got, want, *, rtol=RTOL, atol_rel=ATOL_REL):
    """Assert two pytrees agree leaf by leaf; returns the worst ratio of
    error to allowance (<= 1 passes)."""
    g, w = _leaves(got), _leaves(want)
    require([p for p, _ in g] == [p for p, _ in w],
            f"{label}: tree mismatch")
    worst, bad = 0.0, []
    for (path, a), (_, b) in zip(g, w):
        require(a.shape == b.shape,
                f"{label}{path}: {a.shape} vs {b.shape}")
        if not np.all(np.isfinite(a)):
            bad.append(f"{path} not finite")
            continue
        allow = atol_rel * float(np.max(np.abs(b), initial=0.0)) \
            + rtol * np.abs(b)
        ratio = float(np.max(np.abs(a - b) / np.maximum(allow, 1e-30),
                             initial=0.0))
        worst = max(worst, ratio)
        if ratio > 1.0:
            bad.append(f"{path} error/allowance {ratio:.3g}")
    print(f"[compare] {label}: {len(g)} leaves, worst error/allowance "
          f"{worst:.3g}", flush=True)
    require(not bad, f"{label}: " + "; ".join(bad[:10]))
    return worst


def paper_sweep(model, names, use_kernels):
    """The jitted phase-1 sweep: ``(params, x, y, rng) -> (loss, grads,
    ext)``."""
    from repro.core import CrossEntropyLoss, ExtensionConfig, run

    exts = _extensions(names)
    cfg = ExtensionConfig(use_kernels=use_kernels)

    def f(p, x, y, key):
        res = run(model, p, x, y, CrossEntropyLoss(), extensions=exts,
                  cfg=cfg, rng=key)
        return res.loss, res.grads, res.ext

    return jax.jit(f)


def paper_path(n=128, img=32, seed=0, names=PAPER_EXTENSIONS):
    """Phase 1: jitted ``run`` on 3C3D, kernel route vs jnp route.

    Returns the number of ``tpu_custom_call`` ops in the compiled kernel
    program (0 where the kernels run under the interpreter)."""
    model, params, x, y = _paper_setup(n, img, seed)
    rng = jax.random.PRNGKey(seed + 1)
    # BatchDot's jnp form builds [N, N, R, R] products (64 GiB for conv1
    # at N=128), so its reference is the Gram of the per-sample gradients,
    # which is what BatchDot is.
    require("batch_dot" not in names or "batch_grad" in names,
            "batch_dot needs batch_grad")
    ref_names = tuple(nm for nm in names if nm != "batch_dot")
    with jax.default_matmul_precision("highest"):
        runs = {}
        for route, sel in (("kernels", names), ("jnp", ref_names)):
            t0 = time.perf_counter()
            compiled = paper_sweep(model, sel, route == "kernels").lower(
                params, x, y, rng).compile()
            t1 = time.perf_counter()
            runs[route] = jax.block_until_ready(compiled(params, x, y, rng))
            if route == "kernels":
                customs = compiled.as_text().count("tpu_custom_call")
            print(f"[phase1] 3C3D N={n} img={img} {route} route, {len(sel)} "
                  f"extensions in one program: compile {t1 - t0:.3f} s, "
                  f"first run {time.perf_counter() - t1:.3f} s (bring-up "
                  f"timings)", flush=True)
    loss, grads, ext = runs["jnp"]
    ext = dict(ext)
    if "batch_dot" in names:
        ext["batch_dot"] = jax.tree.map(
            lambda g: (lambda f: f @ f.T)(
                np.asarray(g, np.float64).reshape(n, -1)),
            ext["batch_grad"])
    compare("phase1 kernels vs jnp", runs["kernels"], (loss, grads, ext))
    return customs


def lm_config(layers=LM_LAYERS):
    """stablelm-1.6b at its published widths, depth cut to ``layers``."""
    from repro.configs import get_config

    return dataclasses.replace(get_config("stablelm-1.6b"), n_layers=layers)


def _lm_fit(cfg, batch, seq, steps, seed, mesh=None, log_fn=print):
    from repro.configs import SHAPES
    from repro.core import DiagGGNMC, ExtensionConfig, Variance
    from repro.nn.models import build_model
    from repro.optim import curvature_optimizer
    from repro.train.loop import LoopConfig, fit

    model = build_model(cfg)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=seq,
                                global_batch=batch)
    opt = curvature_optimizer(0.2, 1e-1, "diag_ggn_mc")
    loop = LoopConfig(steps=steps, log_every=1, seed=seed)
    return fit(model, cfg, shape, opt, loop,
               extensions=(DiagGGNMC, Variance),
               ext_cfg=ExtensionConfig(mc_samples=1, use_kernels=True),
               track=("variance",), mesh=mesh, log_fn=log_fn)


def trainer(cfg, batch=LM_BATCH, seq=LM_SEQ, steps=LM_STEPS, seed=0):
    """Phase 2: ``fit`` with the diag_ggn_mc optimizer tracking Variance;
    returns the loss history after checking every loss is finite."""
    print(f"[phase2] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}; batch {batch} x seq {seq} = {batch * seq} tokens "
          f"per step, {steps} steps", flush=True)
    _, _, hist, _ = _lm_fit(cfg, batch, seq, steps, seed,
                            log_fn=lambda m: print(f"[phase2] {m}",
                                                   flush=True))
    losses = [h["loss"] for h in hist]
    for i, h in enumerate(hist):
        print(f"[phase2] step {i} loss {h['loss']:.6f} variance_mean "
              f"{h.get('variance_mean', float('nan')):.6g} step time "
              f"{h['dur_s']:.4f} s (bring-up timing)", flush=True)
    require(len(losses) == steps, f"phase2: {len(losses)} of {steps} steps")
    require(all(math.isfinite(v) for v in losses),
            f"phase2 losses {losses}")
    return losses


def sharded_sweeps(mesh, n=128, img=32, seed=0, names=PAPER_EXTENSIONS):
    """Phase 3a: ``SweepPlan.shard`` over ``mesh`` for every Gram assembly
    mode vs the single-device sweep on the same batch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import CrossEntropyLoss, ExtensionConfig, plan_sweeps
    from repro.sharding.rules import GRAM_ASSEMBLY_MODES

    model, params, x, y = _paper_setup(n, img, seed)
    exts = _extensions(names)
    cfg = ExtensionConfig(use_kernels=True)
    rng = jax.random.PRNGKey(seed + 1)
    n_dev = mesh.devices.size
    plan = plan_sweeps(exts, cfg)
    with jax.default_matmul_precision("highest"):
        ref = plan.run(model, params, x, y, CrossEntropyLoss(), cfg=cfg,
                       rng=rng)
        want = (ref.loss, ref.grads, ref.ext)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        ys = jax.device_put(y, NamedSharding(mesh, P("data")))
        for mode in GRAM_ASSEMBLY_MODES:
            res = plan.shard(mesh, "data", gram_assembly=mode).run(
                model, params, xs, ys, CrossEntropyLoss(), cfg=cfg, rng=rng)
            placed = len(res.logits.sharding.device_set)
            require(placed == n_dev,
                    f"logits on {placed} of {n_dev} devices")
            ext = dict(res.ext)
            if mode == "master":
                # The Gram lands on the leading device slot, zeros elsewhere.
                ext["batch_dot"] = jax.tree.map(lambda a: a[0],
                                                res.ext["batch_dot"])
                rest = jax.tree.map(
                    lambda a: np.max(np.abs(np.asarray(a[1:])), initial=0.0),
                    res.ext["batch_dot"])
                require(max(jax.tree.leaves(rest)) == 0.0,
                        "master: nonzero Gram off the leading slot")
            compare(f"phase3 shard({n_dev}, {mode}) vs one device",
                    (res.loss, res.grads, ext), want)


def sharded_fit(mesh, cfg, batch=LM_BATCH, seq=LM_SEQ, seed=0):
    """Phase 3b: one ``fit(..., mesh=mesh)`` step vs one single-device step.

    Parameters are bfloat16, so they are held to one bfloat16 rounding
    step; loss and mean variance to ``RTOL``."""
    quiet = lambda m: None  # noqa: E731
    p1, _, h1, _ = _lm_fit(cfg, batch, seq, 1, seed, log_fn=quiet)
    want = jax.device_get(p1)
    del p1
    p4, _, h4, _ = _lm_fit(cfg, batch, seq, 1, seed, mesh=mesh, log_fn=quiet)
    got = jax.device_get(p4)
    del p4
    print(f"[phase3] fit step: one device {h1[0]['dur_s']:.4f} s, mesh of "
          f"{mesh.devices.size} {h4[0]['dur_s']:.4f} s (bring-up timings, "
          f"compile included)", flush=True)
    compare("phase3 fit(mesh) metrics vs one device",
            [h4[0]["loss"], h4[0]["variance_mean"]],
            [h1[0]["loss"], h1[0]["variance_mean"]])
    compare("phase3 fit(mesh) params vs one device", got, want,
            rtol=2.0 ** -7, atol_rel=2.0 ** -9)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the batch-sharded path over four chips")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"[setup] {len(devices)} x {devices[0].device_kind}, compile "
          f"cache {cache}", flush=True)
    if args.four_chips:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh(4)
        sharded_sweeps(mesh)
        sharded_fit(mesh, lm_config(LM_MESH_LAYERS))
    else:
        customs = paper_path()
        require(customs > 0,
                "phase1: no tpu_custom_call in the kernel program")
        print(f"[phase1] ok: {customs} tpu_custom_call ops in the kernel "
              f"program", flush=True)
        print(f"[phase2] cut: {LM_LAYERS} of 24 layers, {LM_BATCH} x "
              f"{LM_SEQ} tokens per step", flush=True)
        trainer(lm_config())
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

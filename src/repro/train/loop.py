"""Training loop: jit step + synthetic data + checkpoint + watchdog.

Small enough to run on CPU for examples/tests, structured like the real
thing: deterministic step-indexed data (resume needs no iterator state),
periodic atomic checkpoints, straggler watchdog, failure injection hook,
and the restart driver from ``fault.py``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import CrossEntropyLoss, ExtensionConfig
from repro.data.synthetic import batch_for
from repro.train import checkpoint as ckpt
from repro.train.fault import FailureInjector, Watchdog
from repro.train.step import make_extended_train_step, make_train_step


@dataclasses.dataclass
class LoopConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3      # newest checkpoints retained (must be >= 1)
    log_every: int = 10
    seed: int = 0
    batch_override: Optional[int] = None
    # Online marginal-likelihood callback (repro.laplace): every
    # ``marglik_every`` steps, fit a last-layer Laplace posterior on the
    # current batch (MC curvature — LM vocabularies rule out the exact
    # factor) and tune the prior precision by evidence ascent.  The
    # evidence and tuned prior land in that step's metrics/history —
    # curvature-backed generalization telemetry riding the training loop.
    marglik_every: Optional[int] = None
    marglik_structure: str = "kron"   # 'diag' | 'kron'
    marglik_steps: int = 20           # evidence-ascent steps per callback


def fit(model, cfg, shape, opt, loop: LoopConfig,
        extensions: Sequence = (), ext_cfg: Optional[ExtensionConfig] = None,
        injector: Optional[FailureInjector] = None, resume: bool = False,
        log_fn: Callable = print, track: Sequence[str] = (),
        mesh=None, shard_axes=("data",), step_fn: Optional[Callable] = None):
    """Train `model` (built from arch config `cfg`) on synthetic data.

    With ``mesh`` the extended step runs the batch-sharded sweep lane
    (``SweepPlan.shard``) — same numbers, N devices.  With
    ``ext_cfg=ExtensionConfig(microbatch_size=...)`` the step streams each
    batch through the accumulated lane (``SweepPlan.accumulate``): the
    extended step folds every extension's sequential reducer along, and
    the plain step falls back to classic lax.scan gradient accumulation —
    either way the loop serves effective batches beyond device memory.

    With ``step_fn`` the step builders are bypassed for a prebuilt
    extended-signature step ``(params, opt_state, batch, step_idx, rng)``
    — how whole-step optimizers plug in (e.g. ``optim.make_cg_ngd_step``,
    whose implicit solve needs the batch, not just the gradient);
    ``opt.init`` still builds the state."""
    loss = CrossEntropyLoss()
    params = model.init(jax.random.PRNGKey(loop.seed))
    opt_state = opt.init(params)
    start_step = 0
    if resume and loop.ckpt_dir:
        last = ckpt.latest_step(loop.ckpt_dir)
        if last is not None:
            params, opt_state, manifest = ckpt.restore(
                loop.ckpt_dir, last, params, opt_state)
            start_step = manifest["step"]
            log_fn(f"[resume] step {start_step}")

    prebuilt = step_fn is not None
    if prebuilt:
        step_fn = jax.jit(step_fn)
    elif extensions:
        step_fn = jax.jit(make_extended_train_step(
            model, loss, opt, extensions, ext_cfg, track=track,
            mesh=mesh, shard_axes=shard_axes))
    else:
        microbatch = 1
        if ext_cfg is not None and ext_cfg.microbatch_size:
            nb = loop.batch_override or shape.global_batch
            k = max(1, -(-nb // ext_cfg.microbatch_size))
            microbatch = k
            while nb % microbatch:  # make_train_step needs even slices
                microbatch += 1
            if microbatch != k:
                # e.g. prime nb: the only even split ≥ k may be far finer
                # than asked — stay memory-safe but say so (the extended
                # path handles uneven slices exactly; this one reshapes).
                log_fn(f"[accumulate] batch {nb} has no even split into "
                       f"≤{ext_cfg.microbatch_size}-sample slices; using "
                       f"{microbatch} microbatches of {nb // microbatch}")
        step_fn = jax.jit(make_train_step(model, loss, opt,
                                          microbatch=microbatch))

    wd = Watchdog()
    history = []
    marglik_ok = True  # flips off after the first unsupported-model error
    for step in range(start_step, loop.steps):
        if injector is not None:
            injector.check(step)
        with obs.span("train/step", step=step):
            with obs.span("train/data"):
                batch = batch_for(cfg, shape, step, seed=loop.seed,
                                  batch=loop.batch_override)
            # perf_counter is the one wall clock for durations (monotonic
            # on every platform, highest resolution) — obs spans use it too
            t0 = time.perf_counter()
            args = (params, opt_state, batch, jnp.int32(step))
            if extensions or prebuilt:
                args += (jax.random.fold_in(
                    jax.random.PRNGKey(loop.seed + 1), step),)
            params, opt_state, metrics = run_step(step_fn, *args)
            dur = time.perf_counter() - t0
        if step == start_step:
            # output buffers a step, which dispatch time grows with
            obs.gauge("train.step_outputs",
                      len(jax.tree.leaves((params, opt_state, metrics))))
        stalled = wd.stalled()  # gap since the previous beat, pre-beat
        ok = wd.beat(step, dur)
        # per-step duration + watchdog state ride the history so post-hoc
        # analysis needs no log scraping
        metrics["dur_s"] = dur
        metrics["stalled"] = float(stalled)
        metrics["straggler"] = float(not ok)
        obs.count("train.steps")
        if not ok:
            obs.count("train.watchdog.straggler")
        if (loop.marglik_every and marglik_ok
                and (step + 1) % loop.marglik_every == 0):
            marglik_ok = _marglik_callback(model, params, batch, loss, loop,
                                           step, metrics, log_fn)
        history.append(metrics)
        if step % loop.log_every == 0:
            log_fn(f"step {step:5d} loss {metrics['loss']:.4f} "
                   f"({dur*1e3:.0f} ms)")
        if loop.ckpt_dir and (step + 1) % loop.ckpt_every == 0:
            ckpt.save(loop.ckpt_dir, step + 1, params, opt_state,
                      keep=loop.ckpt_keep)
    if loop.ckpt_dir:
        ckpt.save(loop.ckpt_dir, loop.steps, params, opt_state,
                  keep=loop.ckpt_keep)
    return params, opt_state, history, wd


def run_step(step_fn: Callable, *args):
    """One training step: ``step_fn(*args)`` returns ``(params, opt_state,
    metrics)``; returns them with the metrics as host floats.

    The call, until the jitted function returns, is the span
    ``train/dispatch``; the wait for the metrics and their one
    device→host read is ``train/sync``.  A jitted step writes every output
    buffer when its program ends, so the metrics are ready when the whole
    step is."""
    with obs.span("train/dispatch"):
        params, opt_state, metrics = step_fn(*args)
    with obs.span("train/sync"):
        metrics = {k: float(v) for k, v in jax.device_get(metrics).items()}
    return params, opt_state, metrics


def fit_with_restarts(model, cfg, shape, opt, loop: LoopConfig,
                      max_restarts: int = 3, on_restart=None, **kw):
    """:func:`fit` under the restart driver: any fault (injected or real)
    triggers restore-from-latest-checkpoint + retry, up to
    ``max_restarts``.  ``loop.ckpt_dir`` must be set — without it a
    restart would silently retrain from scratch.  Returns
    ``((params, opt_state, history, watchdog), restarts)``."""
    if not loop.ckpt_dir:
        raise ValueError("fit_with_restarts needs loop.ckpt_dir — a "
                         "restart without checkpoints retrains from "
                         "scratch")
    from repro.train.fault import run_with_restarts

    def make_and_run(resume):
        return fit(model, cfg, shape, opt, loop,
                   resume=resume is not None, **kw)

    return run_with_restarts(make_and_run, max_restarts=max_restarts,
                             on_restart=on_restart)


def _marglik_callback(model, params, batch, loss, loop: LoopConfig, step,
                      metrics, log_fn) -> bool:
    """Fit + tune a last-layer Laplace posterior on the current batch and
    record the evidence; returns False (disabling the callback) when the
    model structure is unsupported."""
    from repro import laplace

    try:
        post = laplace.fit_posterior(
            model, params, batch["inputs"], batch["labels"], loss,
            structure=loop.marglik_structure, last_layer=True,
            options=laplace.FitOptions(
                mc=True, cfg=ExtensionConfig(mc_seed=loop.seed + step)))
    except laplace.LaplaceStructureError as e:
        log_fn(f"[marglik] disabled: {e}")
        return False
    post, res = laplace.optimize_marglik(post, n_steps=loop.marglik_steps)
    metrics["marglik"] = float(laplace.log_marglik(post))
    metrics["prior_prec"] = res.prior_prec
    log_fn(f"[marglik] step {step:5d} log-evidence {metrics['marglik']:.1f} "
           f"prior_prec {res.prior_prec:.3g}")
    return True

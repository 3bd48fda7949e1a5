"""Job ``extended_train_step``: the extended training step against the
plain gradient step, as the trainer builds them.

Set-up
    Weights and a pool of distinct batches are made on the device from the
    seed by the configuration's reference module (the program is handed
    them).  The extended step is ``jax.jit`` of
    ``make_extended_train_step(...)`` with the cell's extensions,
    ``ExtensionConfig(use_kernels=True)`` and every requested quantity
    tracked, plus one output of the benchmark's: the signature of every
    requested quantity (``harness.signatures``), taken from the engine's
    results inside the step; the plain step is ``jax.jit`` of
    ``make_train_step(...)``.  Each step object is driven through its
    first three steps (which compile it) on three different batches; what
    they produce is recorded for the comparison.  One more step of each,
    in the window's own way, is untimed.

Window
    ``seconds / 2`` of extended steps, then ``seconds / 2`` of plain steps,
    each step ending in ``block_until_ready`` and its loss read back, as
    the trainer's loop does.  ``step_ms`` and ``grad_step_ms`` are each
    phase's wall time over its steps.  ``peak_hbm_gib`` is read when the
    window closes, before the reference touches the device.

Comparison
    The reference runs the same three steps of each object from the same
    weights, batches and keys, in float32 at ``highest`` precision, and
    ``harness.check`` compares the records.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import shutil
import tempfile
import time

import numpy as np

from harness import check, common, faults, signatures

CHECK_STEPS = 3
KEY_TABLE = 4096
RESTART_EVERY = 200
TRACE_PHASE_S = 3.0


# -- the two step objects -------------------------------------------------------


def _optimizer(spec):
    from repro.optim import curvature_optimizer, sgd

    if spec["name"] == "sgd":
        return sgd(spec["lr"])
    if spec["name"] == "curvature":
        return curvature_optimizer(spec["lr"], spec["damping"],
                                   spec["curvature"])
    raise ValueError(f"unknown optimizer {spec}")


def _named(fn, name):
    """Name the step so that its compiled module reads ``jit_<name>`` in
    the trace."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _Recording:
    """A sweep plan whose ``run`` hands its results to ``seen`` (after
    ``alter``, a planted fault, where one is given)."""

    def __init__(self, plan, seen, alter):
        self._plan, self._seen, self._alter = plan, seen, alter

    def run(self, *args, **kwargs):
        res = self._plan.run(*args, **kwargs)
        if self._alter is not None:
            res = self._alter(res)
        self._seen.append(res)
        return res

    def __getattr__(self, name):
        return getattr(self._plan, name)


def with_signatures(step, names, alter=None):
    """The trainer's extended step, unchanged, with the signatures of the
    quantities ``names`` added to its metrics under ``"_sig"``.

    While the step is traced, the engine's ``plan_for_batch`` hands out a
    plan that records the sweep's results; the signatures are computed
    from those, inside the same program.
    """
    import jax
    from repro.core import engine

    def signed(params, opt_state, batch, step_idx, rng):
        seen, plan_for_batch = [], engine.plan_for_batch

        def recording(*args, **kwargs):
            return _Recording(plan_for_batch(*args, **kwargs), seen, alter)

        engine.plan_for_batch = recording
        try:
            params, opt_state, metrics = step(params, opt_state, batch,
                                              step_idx, rng)
        finally:
            engine.plan_for_batch = plan_for_batch
        (res,) = seen
        with jax.named_scope("perfbench_signatures"):
            metrics = dict(metrics, _sig={
                q: signatures.sign(q, res.ext[q]) for q in names})
        return params, opt_state, metrics

    return signed


def program_steps(cell, model, alter=None):
    """``(ext_step, plain_step, ext_opt, plain_opt)``: the trainer's
    builders (``train.loop.fit`` jits the same), the extended one with
    the signatures added (``with_signatures``)."""
    import jax
    from repro.core import CrossEntropyLoss, ExtensionConfig, by_name
    from repro.train.step import make_extended_train_step, make_train_step

    t = cell.traffic
    ext_opt, plain_opt = _optimizer(t["optimizer"]), _optimizer(
        t["plain_optimizer"])
    exts = tuple(by_name(n) for n in t["extensions"])
    ext_cfg = ExtensionConfig(**t["ext_config"])
    ext = make_extended_train_step(model, CrossEntropyLoss(), ext_opt, exts,
                                   ext_cfg, track=tuple(t["track"]))
    ext = with_signatures(ext, tuple(t["extensions"]), alter)
    plain = make_train_step(model, CrossEntropyLoss(), plain_opt)
    return (jax.jit(_named(ext, "extended_train_step")),
            jax.jit(_named(plain, "plain_train_step")), ext_opt, plain_opt)


# -- records ----------------------------------------------------------------------


def _leaves(jax, tree):
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
            jax.tree_util.tree_leaves_with_path(tree)}


def _leaf_norms(jax, tree):
    return {k: float(v) for k, v in _leaves(jax, tree).items()}


def _diff_norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def diff_norms(a, b):
        return jax.tree.map(lambda x, y: jnp.linalg.norm(
            x.astype(jnp.float32) - y.astype(jnp.float32)), a, b)
    return diff_norms


def drive(step, state, batches, keys, init_params, *, ext, names=(),
          aux=False):
    """Run the first ``CHECK_STEPS`` steps of one object and record them.

    The first step's signatures come from its ``"_sig"`` output; a
    reference step returns its quantities whole (``"_ext"``), and they are
    signed here.
    ``init_params()`` remakes the starting weights on the device when the
    norms of the change need them, so no second copy is held meanwhile.
    Returns ``(state, record, steps_ok)``.
    """
    import jax

    diff = _diff_norms_fn()
    params, opt_state = state
    rec = {"loss": []}
    grad1 = None
    ok = 0
    for i in range(CHECK_STEPS):
        rest = (keys[i],) if ext else ()
        out = step(params, opt_state, batches[i], np.int32(i), *rest)
        params, opt_state, metrics = jax.block_until_ready(out)
        loss = float(metrics["loss"])
        ok += math.isfinite(loss)
        rec["loss"].append(loss)
        if names and i == 0:
            sig = (metrics["_sig"] if "_sig" in metrics
                   else signatures.sign_all(metrics["_ext"]))
            rec["sig"] = {q: _leaves(jax, sig[q]) for q in names}
        if aux and i == 0:
            norms = signatures.norms_all(metrics["_ext"])
            rec["norms"] = {q: _leaves(jax, norms[q]) for q in names}
            grad1 = _leaf_norms(jax, metrics["_aux"]["grad_norms"])
        del out, metrics
        if i in (0, CHECK_STEPS - 1):
            p0 = init_params()
            norms = _leaf_norms(jax, diff(params, p0))
            del p0
            rec["update1" if i == 0 else "change3"] = norms
    if aux:
        rec["grad1"] = grad1
    return (params, opt_state), rec, ok


def run_phase(step, state, batches, feed, start, seconds, *, ext, annotate,
              restart=None):
    """Steps until ``seconds`` have passed; returns
    ``(state, steps, elapsed, failed, step_times)``.

    ``feed`` is ``(step indices, keys)``, both already on the device, so
    that no step waits on a transfer from the host.  Every
    ``RESTART_EVERY`` steps the state goes back to ``restart``, a state
    still on the device (no transfer): the window cycles through a few
    batches, and thousands of SGD steps on them would run the loss to
    infinity."""
    import jax

    steps, keys = feed
    params, opt_state = state
    n = failed = 0
    name = "perfbench/" + ("extended" if ext else "plain") + "_step"
    t_start = t_last = time.perf_counter()
    times = []
    while True:
        i = start + n
        if restart is not None and n and n % RESTART_EVERY == 0:
            params, opt_state = restart
        rest = (keys[i % len(keys)],) if ext else ()
        with (jax.profiler.TraceAnnotation(name) if annotate
              else contextlib.nullcontext()):
            out = step(params, opt_state, batches[i % len(batches)],
                       steps[i % len(steps)], *rest)
            params, opt_state, metrics = jax.block_until_ready(out)
        failed += not math.isfinite(float(metrics["loss"]))
        n += 1
        now = time.perf_counter()
        times.append(now - t_last)
        t_last = now
        if now - t_start >= seconds:
            return (params, opt_state), n, now - t_start, failed, times


def _stalls(times):
    """Median step, the three longest steps (index, ms), and the time
    steps spent beyond twice the median."""
    med = float(np.median(times))
    longest = sorted(range(len(times)), key=lambda i: -times[i])[:3]
    return {"median_ms": 1e3 * med,
            "longest": [[i, 1e3 * times[i]] for i in longest],
            "beyond_2x_median_s": float(sum(t - 2 * med for t in times
                                            if t > 2 * med))}


# -- the run ----------------------------------------------------------------------


def run(ctx):
    """One run of a cell.  ``ctx``: cell, seed, seconds, trace, t0 (process
    start, wall clock), devices, mode ("program", "control" or a fault
    name), log(message).  The program runs at the configuration's matmul
    precision; the reference and the control set their own."""
    import jax

    precision = ctx.cell.config.get("matmul_precision", "default")
    with (contextlib.nullcontext() if precision == "default"
          else jax.default_matmul_precision(precision)):
        return _run(ctx)


def _run(ctx):
    import jax

    cell, log = ctx.cell, ctx.log
    ref = cell.module("reference")
    names = tuple(cell.traffic["extensions"])
    seed = ctx.seed
    k_w, k_d, k_s = (common.stream(seed, s) for s in
                     ("weights", "data", "steps"))

    def init_params():
        return jax.block_until_ready(ref.init_params(cell.config, k_w))

    batches = ref.make_batches(cell.config, cell.traffic, k_d,
                               cell.traffic["pool"])
    keys = np.asarray(jax.device_get(jax.jit(jax.vmap(
        jax.random.fold_in, in_axes=(None, 0)))(k_s, np.arange(KEY_TABLE))))

    if ctx.mode == "control":
        ext_step, plain_step = ref.make_steps(
            cell.config, cell.traffic, cell.check["control"])
        ext_opt_state = plain_opt_state = ()
    else:
        model = cell.module("build").build(cell.config)
        ext_step, plain_step, ext_opt, plain_opt = program_steps(
            cell, model, faults.SWEEP_FAULTS.get(ctx.mode))
        if ctx.mode in faults.FAULTS:
            plant = faults.FAULTS[ctx.mode]
            ext_step = jax.jit(plant(ext_step))
            plain_step = jax.jit(plant(plain_step))
        p0 = init_params()
        ext_opt_state, plain_opt_state = ext_opt.init(p0), plain_opt.init(p0)
        del p0
    log("steps built, batches made")

    ext_state, ext_rec, ok_e = drive(
        ext_step, (init_params(), ext_opt_state), batches, keys, init_params,
        ext=True, names=names)
    plain_state, plain_rec, ok_p = drive(
        plain_step, (init_params(), plain_opt_state), batches, keys,
        init_params, ext=False)
    program = {"ext": ext_rec, "plain": plain_rec}
    feed = ([jax.device_put(np.int32(i)) for i in range(KEY_TABLE)],
            [jax.device_put(k) for k in keys])
    # the states the phases go back to, kept on the device
    ext_restart, plain_restart = ext_state, plain_state
    # one untimed step of each, called as the window calls them
    ext_state, _, _, bad_we, _ = run_phase(
        ext_step, ext_state, batches, feed, CHECK_STEPS, 0.0, ext=True,
        annotate=False)
    plain_state, _, _, bad_wp, _ = run_phase(
        plain_step, plain_state, batches, feed, CHECK_STEPS, 0.0, ext=False,
        annotate=False)
    log("first steps of both objects recorded")

    half = ctx.seconds / 2
    traced = None
    if ctx.trace:
        half = min(half, TRACE_PHASE_S)
        traced = tempfile.mkdtemp(prefix="perfbench_trace_")
    # each phase is traced on its own
    trace_on = (lambda d: jax.profiler.start_trace(os.path.join(traced, d))
                ) if traced else (lambda d: None)
    trace_off = jax.profiler.stop_trace if traced else (lambda: None)
    gc.collect()
    t_window = time.time()
    setup_s = t_window - ctx.t0
    gc.disable()
    try:
        trace_on("extended")
        ext_state, n_e, t_e, bad_e, ext_times = run_phase(
            ext_step, ext_state, batches, feed, CHECK_STEPS + 1, half,
            ext=True, annotate=bool(traced), restart=ext_restart)
        trace_off()
        trace_on("plain")
        plain_state, n_p, t_p, bad_p, plain_times = run_phase(
            plain_step, plain_state, batches, feed, CHECK_STEPS + 1, half,
            ext=False, annotate=bool(traced), restart=plain_restart)
        trace_off()
    finally:
        gc.enable()
    window_s = time.time() - t_window
    peak = common.peak_bytes(ctx.devices)
    del ext_state, plain_state, ext_restart, plain_restart
    log(f"window closed: {n_e} extended steps in {t_e:.3f} s, {n_p} plain "
        f"steps in {t_p:.3f} s, peak {peak} bytes")

    summary = None
    if traced:
        summary = _reduce_trace(traced, ext_step, plain_step, batches, keys,
                                init_params, ext_opt_state, plain_opt_state,
                                t_e + t_p, log)
        shutil.rmtree(traced, ignore_errors=True)

    # the program's compiled steps go before the reference runs
    del ext_step, plain_step
    gc.collect()
    reference = reference_record(ref, cell, init_params, batches, keys, names)
    ref_peak = common.peak_bytes(ctx.devices)
    log(f"reference done; the process peak after it is {ref_peak} bytes "
        f"(the program's, read at the close of the window: {peak})")

    readings = check.numbers(program, reference)
    correct, _, checks = check.judge(readings, cell.limits)
    attempted = 2 * CHECK_STEPS + 2 + n_e + n_p
    failed = (2 * CHECK_STEPS - ok_e - ok_p) + bad_we + bad_wp + bad_e + bad_p
    gib = None if peak is None else peak / 2 ** 30
    if ctx.trace:
        from harness import layers

        metrics = layers.read_metrics(cell, summary, n_e, t_e,
                                     ctx.devices[0].device_kind)
        device = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        breakdown = {
            "device_ops": [[k, v] for k, v in sorted(
                summary.by_op.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:10]]}
    else:
        metrics = {
            "step_ms": {"value": 1e3 * t_e / n_e, "unit": "ms"},
            "grad_step_ms": {"value": 1e3 * t_p / n_p, "unit": "ms"},
            "peak_hbm_gib": {"value": gib, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        device, breakdown = {}, None
    return dict(correct=correct, attempted=attempted,
                failed=failed, metrics=metrics,
                device_extra=device, memory_peak_bytes=peak,
                breakdown=breakdown, checks=checks, readings=readings,
                counts={"extended_steps": n_e, "extended_s": t_e,
                        "plain_steps": n_p, "plain_s": t_p,
                        "window_s": window_s, "setup_s": setup_s,
                        "extended_stalls": _stalls(ext_times),
                        "plain_stalls": _stalls(plain_times),
                        "reference_peak_bytes": ref_peak})


def reference_record(ref, cell, init_params, batches, keys, names):
    """The reference's record of the same three steps of both objects."""
    ext_step, plain_step = ref.make_steps(cell.config, cell.traffic,
                                          "float32")
    _, ext, _ = drive(ext_step, (init_params(), ()), batches, keys,
                      init_params, ext=True, names=names, aux=True)
    _, plain, _ = drive(plain_step, (init_params(), ()), batches, keys,
                        init_params, ext=False)
    plain["grad1"] = ext["grad1"]     # step 1 is the same gradient
    return {"ext": ext, "plain": plain}


def _reduce_trace(trace_dir, ext_step, plain_step, batches, keys, init_params,
                  ext_opt_state, plain_opt_state, window_s, log):
    """Attribute the traced window's device time (see ``harness.trace``);
    the control's steps are no compiled programs and have no HLO."""
    from harness import trace

    p0 = init_params()
    hlo = {}
    for name, fn, args in (
            ("jit_extended_train_step", ext_step,
             (p0, ext_opt_state, batches[0], np.int32(0), keys[0])),
            ("jit_plain_train_step", plain_step,
             (p0, plain_opt_state, batches[0], np.int32(0)))):
        if hasattr(fn, "lower"):
            hlo[name] = fn.lower(*args).compile().as_text()
    del p0
    parts = [trace.summarize(trace.load(trace.find_xplane(
        os.path.join(trace_dir, phase))), hlo)
        for phase in ("extended", "plain")]
    summary = trace.merge(parts, window_s)
    log(f"trace: busy {summary.busy_s:.4f} s of {window_s:.4f} s; programs "
        f"{summary.module_runs}")
    return summary

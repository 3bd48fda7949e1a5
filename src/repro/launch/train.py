"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --steps 200 --seq 64 --batch 8 --optimizer kfac --ckpt /tmp/ckpt

Runs the reduced config on CPU; on a real pod the same entry point runs the
full config with the production mesh (--full --mesh single|multi).
"""
import argparse
import dataclasses

from repro.launch.cache import enable_compile_cache
from repro import obs
from repro.configs import SHAPES, get_config
from repro.core import DiagGGNMC, ExtensionConfig, KFAC, Variance
from repro.nn.models import build_model
from repro.optim import (adamw, curvature_optimizer, make_cg_ngd_step,
                         momentum_sgd)
from repro.train.loop import LoopConfig, fit, fit_with_restarts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "momentum", "diag_ggn_mc", "kfac",
                             "cg_ngd"])
    ap.add_argument("--damping", type=float, default=1e-1)
    ap.add_argument("--cg-iters", type=int, default=10,
                    help="cg_ngd: CG iterations per step (each costs ~2 "
                         "gradient sweeps; the implicit solve never "
                         "materializes a factor, so LM heads whose KFAC "
                         "factors exceed device memory still train)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="newest checkpoints retained in --ckpt (>= 1)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="run under the restart driver: any fault restores "
                         "the latest checkpoint and retries, up to this "
                         "many times (needs --ckpt)")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a failure at this step (exercises the "
                         "checkpoint/restart path end-to-end; pair with "
                         "--max-restarts)")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (pod-scale; not for CPU)")
    ap.add_argument("--track-variance", action="store_true")
    ap.add_argument("--shard-sweep", action="store_true",
                    help="run extension sweeps batch-sharded over all "
                         "local devices (SweepPlan.shard lane; batch must "
                         "divide the device count)")
    ap.add_argument("--microbatch-size", type=int, default=None,
                    help="stream each batch through the accumulated sweep "
                         "lane (SweepPlan.accumulate) in slices of at most "
                         "this many samples — identical numbers, activation "
                         "memory bounded by the microbatch; composes with "
                         "--shard-sweep (the shard x accumulate grid)")
    ap.add_argument("--trace-jsonl", default=None,
                    help="record an observability trace (spans / counters / "
                         "gauges, one JSON object per line) to this file; "
                         "render it with tools/obs_report.py")
    ap.add_argument("--metrics-report", action="store_true",
                    help="print the measured span tree + counters after "
                         "training (obs.report())")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace of the run "
                         "into this directory (view with TensorBoard / "
                         "Perfetto)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace_jsonl or args.metrics_report or args.profile_dir:
        obs.enable(trace_jsonl=args.trace_jsonl)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=args.seq,
                                global_batch=args.batch)

    extensions, ext_cfg, track = (), None, ()
    if args.optimizer == "adamw":
        opt = adamw(args.lr or 1e-3)
    elif args.optimizer == "momentum":
        opt = momentum_sgd(args.lr or 1e-2)
    elif args.optimizer == "diag_ggn_mc":
        opt = curvature_optimizer(args.lr or 0.2, args.damping, "diag_ggn_mc")
        extensions, ext_cfg = (DiagGGNMC,), ExtensionConfig(mc_samples=1)
    elif args.optimizer == "cg_ngd":
        opt = None  # built below, once mesh/microbatch are resolved
    else:
        opt = curvature_optimizer(args.lr or 0.3, args.damping, "kfac",
                                  stat_decay=0.9)
        extensions, ext_cfg = (KFAC,), ExtensionConfig(mc_samples=1)
    if args.track_variance:
        extensions = tuple(extensions) + (Variance,)
        track = ("variance",)
    if args.microbatch_size:
        ext_cfg = dataclasses.replace(ext_cfg or ExtensionConfig(),
                                      microbatch_size=args.microbatch_size)
        print(f"[accumulate] microbatch_size={args.microbatch_size} "
              f"({-(-args.batch // args.microbatch_size)} microbatches "
              f"per step)")

    mesh = None
    if args.shard_sweep and (extensions or args.optimizer == "cg_ngd"):
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        print(f"[shard-sweep] data mesh over {mesh.shape['data']} device(s)")

    step_fn = None
    if args.optimizer == "cg_ngd":
        from repro.core import CrossEntropyLoss

        opt, step_fn = make_cg_ngd_step(
            model, CrossEntropyLoss(), lr=args.lr or 0.3,
            damping=args.damping, cg_iters=args.cg_iters,
            ext_cfg=ext_cfg, mesh=mesh)
        print(f"[cg_ngd] matrix-free natural gradient: {args.cg_iters} CG "
              f"iterations/step, damping {args.damping:g} — no explicit "
              f"curvature factors")

    loop = LoopConfig(steps=args.steps, ckpt_dir=args.ckpt, log_every=10,
                      ckpt_keep=args.ckpt_keep)
    injector = None
    if args.fail_at_step is not None:
        from repro.train.fault import FailureInjector

        injector = FailureInjector(fail_at_step=args.fail_at_step)
        print(f"[fault] injecting failure at step {args.fail_at_step}")
    with obs.profile(args.profile_dir):
        if args.max_restarts > 0:
            (_, _, hist, wd), restarts = fit_with_restarts(
                model, cfg, shape, opt, loop,
                max_restarts=args.max_restarts,
                on_restart=lambda i, e: print(f"[restart {i}] after: {e}"),
                extensions=extensions, ext_cfg=ext_cfg, track=track,
                mesh=mesh, injector=injector, step_fn=step_fn)
            print(f"[fault] completed with {restarts} restart(s)")
        else:
            _, _, hist, wd = fit(model, cfg, shape, opt, loop,
                                 extensions=extensions, ext_cfg=ext_cfg,
                                 resume=args.resume, track=track, mesh=mesh,
                                 injector=injector, step_fn=step_fn)
    print(f"final loss {hist[-1]['loss']:.4f} "
          f"(stragglers flagged: {len(wd.straggler_steps)})")
    if args.profile_dir:
        print(f"[obs] device trace in {args.profile_dir}")
    if args.metrics_report:
        print(obs.report())
    if args.trace_jsonl:
        obs.disable()  # close the sink so the trace file is complete
        print(f"[obs] trace written to {args.trace_jsonl} — render with "
              f"'python tools/obs_report.py {args.trace_jsonl}'")


if __name__ == "__main__":
    main()

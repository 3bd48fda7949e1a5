"""NTK-consumer launcher: GP regression, influence, subset selection.

    PYTHONPATH=src python -m repro.launch.ntk_apps --gp --n-train 64
    PYTHONPATH=src python -m repro.launch.ntk_apps --influence --top 10
    PYTHONPATH=src python -m repro.launch.ntk_apps --select-subset 16 \
        --method bait --microbatches 4 --shard-sweep

Runs the requested consumer on a papernets model over synthetic data —
the CPU-scale driver for the same entry points a real pod points at a
dataset.  ``--shard-sweep`` assembles the kernel on the sharded lane
('master' mode: factorization on shard 0), ``--microbatches`` streams
the Jacobian sweep row-blockwise.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.launch.cache import enable_compile_cache
from repro import obs
from repro.configs import papernets
from repro.core import CrossEntropyLoss, ExtensionConfig


def _data(key, n, dim, n_classes):
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (n, dim), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, n_classes)
    return x, y


def main():
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--gp", action="store_true",
                      help="NTK-GP predictive mean/variance on a test split")
    mode.add_argument("--influence", action="store_true",
                      help="train→test influence scores + self-influence")
    mode.add_argument("--select-subset", type=int, metavar="K", default=None,
                      help="pick K pool points (see --method)")
    ap.add_argument("--model", default="mlp",
                    choices=["logreg", "mlp", "c2d2"])
    ap.add_argument("--n-train", type=int, default=64)
    ap.add_argument("--n-test", type=int, default=16)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--ridge", type=float, default=1e-2)
    ap.add_argument("--damping", type=float, default=1e-2)
    ap.add_argument("--solver", default="cholesky",
                    choices=["cholesky", "eigh", "lanczos"])
    ap.add_argument("--rank", type=int, default=None,
                    help="eigh truncation / lanczos preconditioner rank")
    ap.add_argument("--method", default="diversity",
                    choices=["diversity", "bait"],
                    help="--select-subset strategy")
    ap.add_argument("--top", type=int, default=5,
                    help="rows to print per result table")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="stream sweeps in this many row blocks "
                         "(accumulate lane)")
    ap.add_argument("--shard-sweep", action="store_true",
                    help="assemble kernels on the sharded sweep lane "
                         "(gram_assembly='master')")
    ap.add_argument("--trace-jsonl", default=None,
                    help="record the obs span trace to this JSONL file")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace_jsonl:
        obs.enable(trace_jsonl=args.trace_jsonl)

    if args.model == "logreg":
        model = papernets.logreg(args.classes, args.dim)
    elif args.model == "mlp":
        model = papernets.mlp(args.classes, args.dim, hidden=(64, 32))
    else:
        img = 8
        args.dim = img * img
        model = papernets.c2d2(args.classes, in_ch=1, img=img)
    params = model.init(jax.random.PRNGKey(0))
    loss = CrossEntropyLoss()
    cfg = ExtensionConfig()

    x_tr, y_tr = _data(jax.random.PRNGKey(1), args.n_train, args.dim,
                       args.classes)
    x_te, y_te = _data(jax.random.PRNGKey(2), args.n_test, args.dim,
                       args.classes)
    if args.model == "c2d2":
        x_tr = x_tr.reshape(-1, 8, 8, 1)
        x_te = x_te.reshape(-1, 8, 8, 1)

    mesh = None
    if args.shard_sweep:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        print(f"[shard-sweep] data mesh over {mesh.shape['data']} device(s)")

    from repro import ntk_apps

    if args.gp:
        gp = ntk_apps.gp_predict(
            model, params, x_tr, y_tr, x_te, loss, ridge=args.ridge,
            solver=args.solver, rank=args.rank, cfg=cfg, mesh=mesh,
            microbatches=args.microbatches)
        print(f"[gp] solver={gp.info.method} rank={gp.info.rank} "
              f"iters={gp.info.iters} resid={float(gp.info.resid):.2e}")
        pred = jnp.argmax(gp.mean, axis=-1)
        for j in range(min(args.top, args.n_test)):
            print(f"  test[{j:3d}]  pred={int(pred[j])}  "
                  f"var={float(gp.var[j]):.4f}  "
                  f"mean={[round(float(v), 3) for v in gp.mean[j]]}")
    elif args.influence:
        inf = ntk_apps.influence_scores(
            model, params, x_tr, y_tr, x_te, y_te, loss,
            damping=args.damping, cfg=cfg, mesh=mesh,
            microbatches=args.microbatches)
        si = ntk_apps.self_influence(
            model, params, x_tr, y_tr, loss, damping=args.damping,
            cfg=cfg, mesh=mesh, microbatches=args.microbatches)
        total = inf.scores.sum(axis=1)
        order = jnp.argsort(total)[::-1]
        print(f"[influence] cg iters={int(inf.iters)} "
              f"max resid={float(inf.resid.max()):.2e} — top train points "
              f"by summed influence on the test split:")
        for i in map(int, order[:args.top]):
            print(f"  train[{i:3d}]  influence={float(total[i]):+.4f}  "
                  f"self={float(si.scores[i]):.4f}")
    else:
        sel = ntk_apps.select_subset(
            model, params, x_tr, y_tr, loss, args.select_subset,
            method=args.method, lam=args.damping, cfg=cfg, mesh=mesh,
            microbatches=args.microbatches)
        print(f"[select] method={args.method} k={args.select_subset} "
              f"picks (objective per step):")
        for t, (i, s) in enumerate(zip(sel.indices, sel.scores)):
            print(f"  step {t:3d}: pool[{int(i):3d}]  score={float(s):.4f}")

    if args.trace_jsonl:
        print(f"[obs] trace written to {args.trace_jsonl}")


if __name__ == "__main__":
    main()

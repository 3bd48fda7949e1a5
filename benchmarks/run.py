"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH`` additionally
dumps all rows as JSON (the CI quick-bench artifact), and ``--quick`` runs a
short mode for smoke lanes: fewer timing iterations everywhere, plus
smaller shapes where a benchmark defines them (currently ``fused``).

  fig3  individual gradients: for-loop vs vectorized     (paper Fig. 3)
  fig6  extension overhead vs plain gradient             (paper Fig. 6)
  fig7  curvature optimizers vs SGD/Adam                 (paper Fig. 7/10/11)
  fig8  KFLR vs KFAC output-dimension scaling            (paper Fig. 8)
  fig9  Hessian diag vs GGN diag with sigmoid, plus the fused
        second-order sweep vs per-extension baseline     (paper Fig. 9 /
                                                          ISSUE 2 tentpole)
  kernels   Pallas kernels (interpret)                   (deliverable c)
  fused     fused first-order kernel vs per-extension    (ISSUE 1 tentpole)
  accumulate  streaming accumulated sweep vs monolithic,
            incl. a beyond-memory-scale batch lane       (ISSUE 5 tentpole)
  ntk       empirical NTK sweep: fused cross-block
            kernel vs einsum, streamed vs monolithic     (ISSUE 6 tentpole)
  ntk_apps  NTK consumers: GP regression (cholesky/eigh/
            Lanczos-PCG/streamed), influence, subset
            selection, vs a jacrev-materialized baseline (ISSUE 10 tentpole)
  obs       observability overhead: instrumented vs
            uninstrumented fused sweep + SweepStream,
            ratio lanes gated at 1.05x in CI             (ISSUE 8 tentpole)
  laplace   posterior fit + fused predictive-variance
            kernel vs naive Jacobian baseline; also
            refreshes BENCH_laplace.json (repo root, or
            $BENCH_OUT_DIR when set — CI artifact mode)  (ISSUE 3 tentpole)
  matfree   matrix-free curvature: GGN-vp / CG / kernel-
            NGD cost vs one gradient, plus the implicit-
            vs-explicit-factor crossover in C            (ISSUE 9 tentpole)
  roofline  dry-run roofline table                       (deliverable g)

CI's bench-smoke job gates the fused lanes against the committed
quick-mode ``BENCH_smoke_*.json`` baselines via
``benchmarks.check_regression`` (>1.5× slowdown fails the job).

Usage: ``PYTHONPATH=src python -m benchmarks.run [--quick] [--json OUT]
[names...]``
"""
import argparse
import json
import os

from benchmarks import common
from repro.launch.cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--quick", action="store_true",
                    help="short mode: fewer iters, smaller shapes")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="also write all rows as JSON to this path")
    ap.add_argument("which", nargs="*", help="benchmark names (default: all)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.json_path:
        # Fail before minutes of benchmarking, not after.
        parent = os.path.dirname(os.path.abspath(args.json_path))
        if not os.path.isdir(parent):
            ap.error(f"--json: directory does not exist: {parent}")
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"

    # Import after --quick is in the environment (modules read it lazily,
    # but keep the ordering obvious).
    from benchmarks import (
        bench_accumulate,
        bench_c_scaling,
        bench_fused_first_order,
        bench_hessian_diag,
        bench_individual,
        bench_kernels,
        bench_laplace,
        bench_matfree,
        bench_ntk,
        bench_ntk_apps,
        bench_optimizers,
        bench_overhead,
        bench_roofline,
    )

    all_benches = {
        "fig3": bench_individual.main,
        "fig6": bench_overhead.main,
        "fig7": bench_optimizers.main,
        "fig8": bench_c_scaling.main,
        "fig9": bench_hessian_diag.main,
        "kernels": bench_kernels.main,
        "fused": bench_fused_first_order.main,
        "accumulate": bench_accumulate.main,
        "matfree": bench_matfree.main,
        "ntk": bench_ntk.main,
        "ntk_apps": bench_ntk_apps.main,
        "obs": bench_overhead.obs_overhead,
        "laplace": bench_laplace.main,
        "roofline": bench_roofline.main,
    }

    which = args.which or list(all_benches)
    unknown = [w for w in which if w not in all_benches]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; "
                 f"choose from {sorted(all_benches)}")
    print("name,us_per_call,derived")
    for name in which:
        all_benches[name]()
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(common.ROWS, f, indent=2)
        print(f"# wrote {len(common.ROWS)} rows to {args.json_path}")


if __name__ == "__main__":
    main()

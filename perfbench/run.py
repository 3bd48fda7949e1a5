#!/usr/bin/env python3
"""Chip benchmark of the extended backward pass: one cell per run.

    python3 perfbench/run.py --workload c3d3.all_ext.n128 --seed 7 \\
        --seconds 20 --trace 0

Run from the root of a checkout on a machine with the chips the cell asks
for.  The cell is found by name in ``BENCHMARK.json``; its configuration,
traffic, limits, job and per-layer metrics in files under this directory
(see ``harness/discovery.py``).  ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` the per-layer ones from a profiler trace of a
shorter window.  The last stdout line is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), then ``checks``, each compared number beside its limit; the
same numbers are the last lines on stderr.

Exits non-zero without a result when JAX finds no TPU, fewer chips than
the cell asks for, or any part of the run fails.

``--mode`` and ``--seeds`` serve the setting of limits, not the
benchmark's own runs: ``--mode control`` puts the configuration's
reference, in the precision below the configuration's, in the program's
place; ``--mode frozen|half_batch|altered|permuted`` plants a fault
under the timed path (``harness/faults.py``); ``--seeds a,b,...`` makes
one run per seed (and per mode, where several are given) in this process
and prints each seed's readings as a JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import common  # noqa: E402

T0 = common.process_start()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--mode", default="program",
                    help="program, control, frozen, half_batch, altered or "
                    "permuted; comma-separated with --seeds")
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seeds, one readings line each")
    return ap.parse_args(argv)


def main(argv=None, *, root=None, require_chip=True):
    args = parse(argv)
    modes = args.mode.split(",")
    known = ("program", "control", "frozen", "half_batch", "altered",
             "permuted")
    if any(m not in known for m in modes) or (len(modes) > 1
                                               and not args.seeds):
        raise SystemExit(f"--mode: one of {known}, or several with --seeds")
    root = root or common.CHECKOUT
    sys.path.insert(0, os.path.join(common.CHECKOUT, "src"))

    def log(message):
        common.log(T0, message)

    import jax

    if require_chip:
        common.enable_compile_cache()
    from harness import discovery

    cell = discovery.find_cell(root, args.workload)
    devices = jax.devices()
    want = cell.workload["chips"]
    if require_chip and (devices[0].platform != "tpu" or len(devices) < want):
        print(f"perfbench: {args.workload} needs {want} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    devices = devices[:want]
    log(f"{cell.name}: {len(devices)} x {devices[0].device_kind}, mode "
        f"{args.mode}")
    job = cell.job()
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    for mode in modes:
        for seed in seeds:
            ctx = types.SimpleNamespace(
                cell=cell, seed=seed, seconds=args.seconds,
                trace=bool(args.trace), t0=T0, devices=devices, mode=mode,
                log=log)
            res = job.run(ctx)
            log("counts " + json.dumps(res["counts"]))
            if args.seeds:
                print(json.dumps({"seed": seed, "mode": mode,
                                  "correct": res["correct"],
                                  "readings": res["readings"],
                                  "counts": res["counts"]}), flush=True)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    device = common.device_facts(devices)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    device.update(res["device_extra"])
    print(common.result_line(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"], metrics=res["metrics"], device=device,
        breakdown=res["breakdown"], checks=res["checks"]), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # no result line: the run failed
        import traceback

        traceback.print_exc()
        print(f"perfbench: run failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        code = 1
    sys.exit(code)

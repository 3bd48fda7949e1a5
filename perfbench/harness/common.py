"""Pieces every job shares: process clock, seeds, compile cache, device
facts, and the result line.

Nothing here imports the program under test (``repro``).
"""
from __future__ import annotations

import json
import os
import sys
import time

PERFBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(PERFBENCH)
# The persistent compilation cache sits at one fixed path inside the
# checkout (the path is part of the cache key, so it never moves).
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def process_start():
    """Wall-clock time (``time.time()``) at which this process started.

    Read from ``/proc`` so that interpreter start-up and imports count in
    the set-up time; falls back to "now" where ``/proc`` is missing.
    """
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        hz = os.sysconf("SC_CLK_TCK")
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / hz
    except (OSError, ValueError, IndexError):
        return time.time()


def log(t0, message):
    """One timestamped progress line on stderr."""
    print(f"[perfbench {time.time() - t0:8.2f} s] {message}", file=sys.stderr,
          flush=True)


def enable_compile_cache():
    """Persistent compilation cache at ``<checkout>/.jax_cache``, keeping
    every program (no minimum compile time), so a cell's second run in a
    checkout compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def base_key(seed):
    """PRNG key for any whole-number seed, including those beyond 32 bits."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def stream(seed, name):
    """Independent key for one named use of the seed ("weights", "data",
    "steps" ...), the same for the program and the reference."""
    import jax

    k = base_key(seed)
    for ch in name.encode():
        k = jax.random.fold_in(k, ch)
    return k


def device_facts(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices):
    """Peak device memory in use on the fullest chip, or None where the
    backend keeps no statistics."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_json(path):
    with open(path) as f:
        return json.load(f)


def result_line(*, correct, attempted, failed, metrics, device, breakdown=None,
                checks=None):
    """The last stdout line: the contract's keys, with the compared numbers
    under their own key, last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if checks is not None:
        out["checks"] = checks
    return json.dumps(out)

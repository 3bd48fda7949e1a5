"""The comparison that decides ``correct``.

A *record* holds what one side (the program, or the reference in its
place) produced over the first three steps of each training object:

    {"ext":   {"loss": [l1, l2, l3],
               "sig": {q: {leaf: array}},          the first step's
               "update1": {leaf: ‖p1 - p0‖}, "change3": {leaf: ‖p3 - p0‖}},
     "plain": {"loss": [...], "update1": {...}, "change3": {...}}}

``sig`` holds each extension quantity's signature per leaf
(``harness.signatures``): per sample, per pair, or projected per leaf.
The reference's record also carries, per object, ``grad1`` (each leaf's
first-gradient norm) and, for the signatures, ``norms``: the scales that
a signature does not hold itself.

Numbers compared (each has its own limit in ``cells/<workload>.json``):

* ``<obj>.loss``    – largest relative gap of the three losses.
* ``<obj>.update1`` – worst leaf's gap between the norms of the first
  update, against the reference's norm of that leaf or of the median leaf,
  whichever is larger (for SGD the update is the gradient times -lr).
* ``<obj>.change3`` – the same for the change of the parameters after
  three steps.
* ``ext.<q>``       – the first step's signature of a quantity, both
  sides from the same weights: per leaf, each entry's gap against its
  scale -- a sample's reference value, or the median sample's where that
  is larger (``batch_l2``, ``ggn_trace``); ``sqrt(d_n d_m)`` for the pair
  (n, m), ``d`` the diagonal so floored (``batch_dot``); a sample's
  gradient norm so floored (``batch_grad``); the leaf's Frobenius norm,
  or the median leaf's where larger (projected tensors).  A per-sample
  quantity reads the 95th percentile over samples of each sample's
  largest gap (for pairs, of each row's 95th percentile); a projected
  tensor its largest gap; the number is the worst leaf's.

Leaves whose reference gradient is under a thousandth of the median
leaf's (a key bias under softmax moves by round-off alone) are left out
of ``update1`` and ``change3``.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NEGLIGIBLE_GRAD = 1e-3
SAMPLE_QUANTILE = 0.95


def leaf_gap(prog, ref, grad1):
    """Worst leaf's ``|‖p‖ - ‖r‖| / max(‖r‖, median ‖r‖)``."""
    keep = [k for k in ref
            if grad1[k] >= NEGLIGIBLE_GRAD * statistics.median(grad1.values())]
    med = statistics.median(ref[k] for k in keep)
    worst = 0.0
    for k in keep:
        p = prog.get(k, float("nan"))
        gap = abs(p - ref[k]) / max(ref[k], med, 1e-30)
        worst = max(worst, gap) if math.isfinite(gap) else float("inf")
    return worst


def rel_gap(prog, ref, scales=None):
    """Largest ``|p_i - r_i| / scale_i`` over the steps."""
    scales = scales or [abs(r) for r in ref]
    worst = 0.0
    for p, r, s in zip(prog, ref, scales):
        gap = abs(p - r) / max(s, 1e-30)
        worst = max(worst, gap) if math.isfinite(gap) else float("inf")
    if len(prog) != len(ref):
        return float("inf")
    return worst


def _floored(v):
    """``max(v, median v)`` elementwise."""
    v = np.abs(np.asarray(v, np.float64))
    return np.maximum(v, np.median(v)) if v.size else v


def _scales(q, ref, norms):
    """``{leaf: scale}`` broadcastable against each leaf's signature."""
    from harness.signatures import PER_SAMPLE, WHOLE

    if q == "batch_dot":
        out = {}
        for k, r in ref.items():
            d = _floored(np.diagonal(r))
            out[k] = np.sqrt(d[:, None] * d[None, :])
        return out
    if q in WHOLE:
        return {k: _floored(r) for k, r in ref.items()}
    if q in PER_SAMPLE:
        return {k: _floored(n)[:, None] for k, n in norms.items()}
    med = float(np.median([float(n) for n in norms.values()]))
    return {k: max(float(n), med) for k, n in norms.items()}


def per_sample_quantile(gaps):
    """The gap that all but the worst 5% of samples stay within (6 of
    128): one sample whose max-pool winner flips on a float32 rounding
    differs from the reference by its whole gradient, and steps on the
    same weights still do that now and then (PERF.md)."""
    return float(np.quantile(gaps, SAMPLE_QUANTILE, method="higher"))


def _leaf_gap(q, p, r, scale):
    """One leaf's reading, see ``structure_gap``."""
    from harness.signatures import PER_SAMPLE, WHOLE

    gap = np.abs(p - r) / np.maximum(scale, 1e-30)
    if not np.all(np.isfinite(gap)):
        return float("inf")
    if q == "batch_dot":            # within each row first, then over rows
        return per_sample_quantile(
            np.quantile(gap, SAMPLE_QUANTILE, axis=1, method="higher"))
    if q in WHOLE:
        return per_sample_quantile(gap)
    if q in PER_SAMPLE:
        return per_sample_quantile(np.max(gap, axis=1))
    return float(np.max(gap))


def structure_gap(q, prog, ref, norms):
    """Worst leaf's gap between the first step's signatures of one
    quantity: for the per-sample quantities the 95th percentile over
    samples of each sample's gap (for pairs, over each row first), for
    the projected tensors the largest gap.  A leaf that is missing or
    shaped otherwise reads infinity."""
    if prog is None:
        return float("inf")
    scales = _scales(q, ref, norms)
    worst = 0.0
    for k in ref:
        pk = np.asarray(prog.get(k, np.zeros(0)), np.float64)
        if pk.shape != np.shape(ref[k]):
            return float("inf")
        worst = max(worst, _leaf_gap(q, pk, np.asarray(ref[k], np.float64),
                                     scales[k]))
    return worst


def numbers(program, reference):
    """``{name: reading}`` for every number compared."""
    out = {}
    for obj in sorted(reference):
        p, r = program[obj], reference[obj]
        out[f"{obj}.loss"] = rel_gap(p["loss"], r["loss"])
        for what in ("update1", "change3"):
            out[f"{obj}.{what}"] = leaf_gap(p[what], r[what], r["grad1"])
        for q in sorted(r.get("sig", {})):
            out[f"{obj}.{q}"] = structure_gap(
                q, p.get("sig", {}).get(q), r["sig"][q], r["norms"][q])
    return out


def judge(readings, limits):
    """``(correct, failed names, {name: {"value", "limit"}})``.

    A number without a limit, or a limit without a number, fails: the
    comparison and its limits have to cover each other exactly.
    """
    checks, failed = {}, []
    for name in sorted(set(readings) | set(limits)):
        value = readings.get(name, float("nan"))
        limit = limits.get(name, float("nan"))
        checks[name] = {"value": value, "limit": limit}
        if not (math.isfinite(value) and value <= limit):
            failed.append(name)
    return not failed, failed, checks

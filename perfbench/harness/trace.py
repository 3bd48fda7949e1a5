"""Reduce a profiler trace of the window to device times by program,
named scope and kernel.

The device plane (``/device:TPU:0`` ...) of the ``.xplane.pb`` file that
``jax.profiler`` writes has an ``XLA Modules`` line (one event per program
execution, named ``<module>(<fingerprint>)``) and an ``XLA Ops`` line (one
event per HLO instruction executed, named by the instruction's text).
An op is attributed through the compiled program's HLO text:

* its named scope: the first of ``SCOPES`` in the instruction's
  ``op_name`` metadata (``jax.named_scope`` paths such as
  ``jit(step)/first_order_sweep/...``);
* its kernel, for a ``tpu_custom_call``: the ``<name>_pallas`` wrapper
  recorded in the Mosaic body's debug locations (``cross_dot_pallas`` ->
  ``cross_dot``).

Busy time is the union of the intervals of ops and of asynchronous ops
(copies in flight); an idle gap is named by the innermost host event on the
Python thread that covers its middle.
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import glob
import os
import re

SCOPES = ("fwd_tape", "first_order_sweep", "ggn_exact_sweep", "ggn_mc_sweep",
          "kfra_sweep", "hess_sweep", "jac_sweep", "perfbench_signatures")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_KERNEL = re.compile(rb"([A-Za-z][A-Za-z0-9_]*?)_pallas\b")


def _opcode(rest):
    """HLO opcode of an instruction's right-hand side (``fusion``,
    ``custom-call``, ``copy`` ...)."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r"([a-z][a-z0-9\-]*)\(", rest[i + 1:])
            if m:
                return m.group(1)
    m = re.match(r"([a-z][a-z0-9\-]*)\(", rest)
    return m.group(1) if m else "op"


def kernel_of(line):
    """Kernel name of a ``tpu_custom_call`` instruction, or None."""
    if "tpu_custom_call" not in line:
        return None
    m = _BODY.search(line)
    if not m:
        return "pallas:unnamed"
    names = _KERNEL.findall(base64.b64decode(m.group(1) + "=="))
    return "pallas:" + names[0].decode() if names else "pallas:unnamed"


def hlo_index(hlo_text):
    """``{instruction name: (scope, label)}`` for one compiled program.

    ``label`` is ``pallas:<kernel>`` for a Mosaic kernel, else the HLO
    opcode."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        op = _OP_NAME.search(rest)
        scope = "other"
        if op:
            parts = op.group(1).split("/")
            scope = next((s for s in parts if s in SCOPES), "other")
        out[name] = (scope, kernel_of(rest) or _opcode(rest))
    return out


@dataclasses.dataclass
class Event:
    name: str
    start: float     # seconds since the profile started
    dur: float       # seconds


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    by_module: dict            # module name -> device seconds
    by_scope: dict             # (module, scope) -> device seconds
    by_label: dict             # (module, label) -> device seconds
    by_op: dict                # "module:scope/label" -> device seconds
    idle_gaps: list            # [(host activity, seconds)] longest first
    module_runs: dict          # module name -> executions in the trace


def _events(line):
    out = []
    for e in line.events:
        out.append(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def load(path):
    """Planes of one ``.xplane.pb`` as ``{plane: {line: [Event]}}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return {p.name: {ln.name: _events(ln) for ln in p.lines}
            for p in pd.planes}


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def module_base(event_name):
    """``jit_extended_train_step(123)`` -> ``jit_extended_train_step``."""
    return event_name.split("(", 1)[0]


def summarize(planes, hlo_by_module, window=None):
    """Reduce device planes to a :class:`Summary`.

    ``hlo_by_module`` maps a module name (``jit_<function>``) to its
    compiled HLO text; ops of other modules count as busy time under
    their module's name.  ``window`` is ``(start, end)`` in profile
    seconds, else the span of all device ops.
    """
    index = {m: hlo_index(t) for m, t in hlo_by_module.items()}
    devices = [p for name, p in planes.items()
               if name.startswith("/device:") and "XLA Ops" in p]
    if not devices:
        raise ValueError("the trace has no device plane with XLA Ops")
    by_module, by_scope = collections.Counter(), collections.Counter()
    by_label, by_op = collections.Counter(), collections.Counter()
    runs = collections.Counter()
    busy_total, spans, gaps_all = 0.0, [], []
    host = _host_events(planes)
    for plane in devices:
        modules = sorted(plane.get("XLA Modules", []), key=lambda e: e.start)
        for mod in modules:
            runs[module_base(mod.name)] += 1
        ops = sorted(plane["XLA Ops"], key=lambda e: e.start)
        starts = [m.start for m in modules]
        # asynchronous copies keep the device busy too, between the ops
        intervals = [(e.start, e.start + e.dur)
                     for e in plane.get("Async XLA Ops", [])]
        for op in ops:
            mod = _containing(modules, starts, op.start)
            base = module_base(mod.name) if mod else "unknown"
            instr = op.name.split("=", 1)[0].strip().lstrip("%")
            scope, label = index.get(base, {}).get(instr, ("other", _label(op.name)))
            by_module[base] += op.dur
            by_scope[(base, scope)] += op.dur
            by_label[(base, label)] += op.dur
            by_op[f"{base}:{scope}/{label}"] += op.dur
            intervals.append((op.start, op.start + op.dur))
        merged = _union(intervals)
        if merged:
            spans.append((merged[0][0], merged[-1][1]))
        busy_total += sum(e - s for s, e in merged)
        for (s0, e0), (s1, _) in zip(merged, merged[1:]):
            gaps_all.append((s1 - e0, e0, s1))
    n_dev = len(devices)
    if window is None:
        window = (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else (0.0, 0.0)
    idle = collections.Counter()
    mids = sorted(((s + e) / 2, length) for length, s, e in gaps_all)
    for (_, length), name in zip(mids, _host_activity(host, [m for m, _ in mids])):
        idle[name] += length / n_dev
    return Summary(
        window_s=window[1] - window[0], busy_s=busy_total / n_dev,
        by_module={k: v / n_dev for k, v in by_module.items()},
        by_scope={k: v / n_dev for k, v in by_scope.items()},
        by_label={k: v / n_dev for k, v in by_label.items()},
        by_op={k: v / n_dev for k, v in by_op.items()},
        idle_gaps=idle.most_common(), module_runs=dict(runs))


def _label(op_text):
    m = _INSTR.match(op_text)
    return _opcode(m.group(2)) if m else "op"


def _containing(modules, starts, t):
    import bisect

    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i].start + modules[i].dur:
        return modules[i]
    return None


def _host_events(planes):
    """Events of the host's Python thread, the one that drives the steps."""
    cpu = planes.get("/host:CPU", {})
    line = next((v for k, v in cpu.items() if k.startswith("python")), [])
    return sorted(line, key=lambda e: e.start)


def _host_activity(host, times):
    """Innermost host event covering each of ``times`` (sorted)."""
    out, active, i = [], [], 0
    for t in times:
        while i < len(host) and host[i].start <= t:
            active.append(host[i])
            i += 1
        active = [e for e in active if e.start + e.dur >= t]
        best = min(active, key=lambda e: e.dur, default=None)
        out.append(best.name if best else "no host event")
    return out


def merge(parts, window_s):
    """One summary of traces taken back to back (the window's phases);
    ``window_s`` is the traced time they cover together."""
    total = {f: collections.Counter() for f in
             ("by_module", "by_scope", "by_label", "by_op", "module_runs")}
    idle = collections.Counter()
    for p in parts:
        for f, c in total.items():
            c.update(getattr(p, f))
        idle.update(dict(p.idle_gaps))
    return Summary(window_s=window_s, busy_s=sum(p.busy_s for p in parts),
                   idle_gaps=idle.most_common(),
                   **{f: dict(c) for f, c in total.items()})

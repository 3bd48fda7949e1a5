"""Per-layer metrics of a traced run: each is read by its own file under
``metrics/``, from the trace summary and the shapes of the cell."""
from __future__ import annotations

import dataclasses
import os

from harness.common import PERFBENCH, load_json

EXT_MODULE = "jit_extended_train_step"


@dataclasses.dataclass
class Reading:
    """What a metric reader may read."""
    summary: object          # harness.trace.Summary of the traced window
    steps: int               # extended steps in the traced window
    step_s: float            # their wall time (host clock)
    config: dict
    traffic: dict
    layers: list             # reference.layers(config, traffic)
    step_flops: float        # reference.step_flops(config, traffic)
    exact_columns: int       # reference.exact_columns(config, traffic)
    peak_flops: float        # chip peak, bf16 FLOP/s
    peak_bw: float           # chip peak, HBM bytes/s

    def scope_s(self, *scopes):
        """Device seconds per extended step under the named scopes."""
        return sum(self.summary.by_scope.get((EXT_MODULE, s), 0.0)
                   for s in scopes) / self.steps

    def kernel_s(self, kernel):
        """Device seconds per extended step in one Pallas kernel."""
        return self.summary.by_label.get((EXT_MODULE, "pallas:" + kernel),
                                         0.0) / self.steps

    def act_bytes(self):
        return 2 if self.config.get("dtype") == "bfloat16" else 4

    def least_s(self, flops, nbytes):
        return max(flops / self.peak_flops, nbytes / self.peak_bw)


def peaks(device_kind):
    table = load_json(os.path.join(PERFBENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def read_metrics(cell, summary, steps, step_s, device_kind):
    """``{name: {"value", "unit"}}`` for the cell's per-layer metrics that
    found something to read."""
    ref = cell.module("reference")
    pk = peaks(device_kind)
    r = Reading(summary=summary, steps=steps, step_s=step_s,
                config=cell.config, traffic=cell.traffic,
                layers=ref.layers(cell.config, cell.traffic),
                step_flops=ref.step_flops(cell.config, cell.traffic),
                exact_columns=ref.exact_columns(cell.config, cell.traffic),
                peak_flops=pk["bf16_flops_per_s"], peak_bw=pk["hbm_bytes_per_s"])
    units = {m["name"]: m["unit"] for m in cell.per_layer()}
    out = {}
    for name, reader in cell.metric_readers().items():
        value = reader.read(r)
        if value is not None:
            out[name] = {"value": value, "unit": units[name]}
    return out

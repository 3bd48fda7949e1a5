"""The trace reduction on a small trace recorded on one TPU v5e: three
runs of a program holding a ``cross_dot`` and a ``fused_first_order``
kernel under the ``first_order_sweep`` scope (``data/probe.*``)."""
import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def summary():
    planes = trace.load(os.path.join(DATA, "probe.xplane.pb"))
    with open(os.path.join(DATA, "probe.hlo.txt")) as f:
        hlo = f.read()
    return trace.summarize(planes, {"jit_f": hlo})


def test_programs_and_kernels_by_name(summary):
    assert summary.module_runs == {"jit_f": 3}
    # the three cross_dot events last 3.066, 3.246 and 3.114 us
    assert summary.by_label[("jit_f", "pallas:cross_dot")] == \
        pytest.approx(9.426e-6, rel=1e-6)
    # the three fused_first_order events: 1.622, 1.626 and 1.841 us
    assert summary.by_label[("jit_f", "pallas:fused_first_order")] == \
        pytest.approx(5.089e-6, rel=1e-6)


def test_scopes_and_busy_time(summary):
    scoped = summary.by_scope[("jit_f", "first_order_sweep")]
    other = summary.by_scope[("jit_f", "other")]
    ops = sum(summary.by_op.values())
    assert ops == pytest.approx(scoped + other, rel=1e-9)
    # ops run one after another; the three asynchronous copies (6.736,
    # 6.924 and 7.000 us in flight) overlap them in part
    assert ops < summary.busy_s <= ops + 20.66e-6
    # the convolution fusion (19.04 + 19.031 + 19.068 us) is in the scope
    assert summary.by_op["jit_f:first_order_sweep/fusion"] >= 57.139e-6
    assert 0 < summary.busy_s < summary.window_s
    gaps = dict(summary.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_merge_adds_phases(summary):
    both = trace.merge([summary, summary], 2 * summary.window_s)
    assert both.busy_s == pytest.approx(2 * summary.busy_s)
    assert both.module_runs == {"jit_f": 6}
    assert both.by_label[("jit_f", "pallas:cross_dot")] == \
        pytest.approx(2 * 9.426e-6, rel=1e-6)


def test_kernel_names_come_from_the_mosaic_body():
    with open(os.path.join(DATA, "probe.hlo.txt")) as f:
        index = trace.hlo_index(f.read())
    assert index["_unknown_.3"] == ("first_order_sweep", "pallas:cross_dot")
    assert index["_unknown_.2"] == ("first_order_sweep",
                                    "pallas:fused_first_order")
    assert index["copy.1"][1] == "copy"

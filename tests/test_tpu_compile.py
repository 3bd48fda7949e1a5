"""Compile guard: the main path's Pallas kernels, Mosaic-compiled for a
described TPU v5e at real widths.

The TPU compiler installed with JAX compiles for a chip it is only told
about (``jax.experimental.topologies``), so these tests need no chip: each
lowers one ``kernels/ops.py`` registry wrapper with ``interpret=False`` on
one device of a described ``v5e:2x2`` and asserts the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  That is where tiles off
the (8, 128) layout, more scoped VMEM than a kernel may use, and
unsupported in-kernel layouts are refused — none of which interpret mode
sees.  Shapes are the ones ``chip_smoke.py`` dispatches: 3C3D's conv
layers at the paper's batch N=128 (CIFAR-10, 32x32, unfold widths
75/576/864) and its dense layers, and stablelm-1.6b's projections and
100,352-wide head at 8 x 256 tokens.

The last two tests compile the whole tiny 3C3D extended step of the chip
benchmark (``perfbench/tests/tiny.py``, kernels on) and read the
program's own ``rule.*`` and ``kernel.*`` scopes in its HLO, beside the
scope and label the benchmark's trace reduction
(``perfbench/harness/trace.py``) gives each instruction.

The topology is described inside a module fixture, never at import or
collection time: only one process may hold the TPU library at a time.
"""
from __future__ import annotations

import collections
import contextlib
import os
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 128  # 3C3D at the paper's batch
# 3C3D unfold factors (R = output positions, a = C_in·k·k, b = C_out).
CONV1, CONV2, CONV3 = (1024, 75, 64), (256, 576, 96), (64, 864, 128)
C = 10  # CIFAR-10 classes: the exact GGN factor axis
# stablelm-1.6b: 8 sequences x 256 tokens, d_model 2048, d_ff 5632,
# vocabulary 100,352.
LN, LT, D, FF, V = 8, 256, 2048, 5632, 100352


def _conv_first(r, a, b):
    return [(1, N, r, a), (1, N, r, b)]


def _conv_second(r, a, b, c):
    return [(N, r, a), (c, N, r, b)]


CASES = {
    # first-order sweep: l2 + moment, and BatchDot's pairwise dots
    "ffo-3c3d-conv1": ("fused_first_order", _conv_first(*CONV1),
                       dict(want_l2=True, want_moment=True)),
    "ffo-3c3d-conv2": ("fused_first_order", _conv_first(*CONV2),
                       dict(want_l2=True, want_moment=True)),
    "ffo-3c3d-conv3": ("fused_first_order", _conv_first(*CONV3),
                       dict(want_l2=True, want_moment=True)),
    "dot-3c3d-conv1": ("cross_dot", _conv_first(*CONV1) * 2, {}),
    "dot-3c3d-conv3": ("cross_dot", _conv_first(*CONV3) * 2, {}),
    # exact (C classes) and MC (one sample) second-order sweeps
    "fso-exact-3c3d-conv1": ("fused_second_order",
                             _conv_second(*CONV1, C),
                             dict(want_diag=True, want_kron=True,
                                  want_trace=True)),
    "fso-exact-3c3d-conv2": ("fused_second_order",
                             _conv_second(*CONV2, C),
                             dict(want_diag=True, want_kron=True,
                                  want_trace=True)),
    "fso-mc-3c3d-conv3": ("fused_second_order", _conv_second(*CONV3, 1),
                          dict(want_diag=True, want_kron=True)),
    # rank-1 dense layers: (A∘A)ᵀ(B∘B) over the C·N factor rows
    "sq-3c3d-dense1": ("sq_matmul", [(C * N, 2048), (C * N, 512)], {}),
    "sq-3c3d-dense3": ("sq_matmul", [(C * N, 256), (C * N, 10)], {}),
    # NTK / GGN-Gram cross blocks and the Laplace predictive
    "cross-3c3d-conv2": ("cross_dot", [(C, N, 256, 576), (C, N, 256, 96)] * 2,
                         {}),
    "predvar-3c3d-conv1": ("predictive_var",
                           _conv_second(*CONV1, C) + [(75, 64)],
                           dict(want_sigma=True)),
    # stablelm-1.6b: Variance (moment) and DiagGGNMC (diag) per projection
    "ffo-lm-up": ("fused_first_order", [(1, LN, LT, D), (1, LN, LT, FF)],
                  dict(want_l2=False, want_moment=True)),
    "fso-lm-down": ("fused_second_order", [(LN, LT, FF), (1, LN, LT, D)],
                    dict(want_diag=True)),
    "ffo-lm-head": ("fused_first_order", [(1, LN, LT, D), (1, LN, LT, V)],
                    dict(want_l2=False, want_moment=True)),
    "fso-lm-head": ("fused_second_order", [(LN, LT, D), (1, LN, LT, V)],
                    dict(want_diag=True)),
    "cross-lm-head": ("cross_dot", [(1, LN, LT, D), (1, LN, LT, V)] * 2, {}),
    "predvar-lm-head": ("predictive_var", [(LN, LT, D), (1, LN, LT, V)],
                        dict(want_sigma=False)),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    kernel, shapes, static = CASES[case]
    wrapper = ops.get_spec(kernel).wrapper
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(partial(wrapper, interpret=False, **static)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the tiny 3C3D extended step of the chip benchmark, whole ------------------

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*op_name=\"([^\"]*)\"")
_TAG = re.compile(r"\b(?:rule|kernel)\.[A-Za-z0-9_]+")


def _scope_tags(hlo_text):
    """``{instruction: (tag, ...)}``: the innermost ``rule.*`` and the
    innermost ``kernel.*`` component of each instruction's ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        found = _TAG.findall(m.group(2))
        tags = tuple(t for t in (
            next((t for t in reversed(found) if t.startswith(k)), None)
            for k in ("rule.", "kernel.")) if t)
        if tags:
            out[m.group(1)] = tags
    return out


@pytest.fixture(scope="module")
def tiny_step_hlo(one_chip, no_persistent_cache, tmp_path_factory):
    """Compiled HLO text of the tiny extended step, kernels on, with the
    program's rule/kernel scopes (``"tagged"``) and without (``"bare"``)."""
    import numpy as np

    for d in (os.path.join(PERFBENCH, "tests"), PERFBENCH):
        if d not in sys.path:
            sys.path.insert(0, d)
    import tiny
    from harness import discovery

    root = tiny.make_root(tmp_path_factory.mktemp("tags"), ["c3d3.small"])
    cell = discovery.find_cell(root, "c3d3.small")
    job, ref = cell.job(), cell.module("reference")
    key = jax.random.PRNGKey(0)
    p0 = ref.init_params(cell.config, key)
    batch = ref.make_batches(cell.config, cell.traffic, key, 1)[0]
    real_scope, interpret = jax.named_scope, ops._interpret

    def bare_scope(name):
        return (contextlib.nullcontext()
                if name.startswith(("rule.", "kernel.")) else real_scope(name))

    def compile_step(scope):
        jax.named_scope = scope
        try:
            model = cell.module("build").build(cell.config)
            step, _, opt, _ = job.program_steps(cell, model)
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                               sharding=one_chip),
                (p0, opt.init(p0), batch, np.int32(0), key))
            return step.lower(*args).compile().as_text()
        finally:
            jax.named_scope = real_scope

    ops._interpret = lambda: False
    try:
        yield {"tagged": compile_step(real_scope),
               "bare": compile_step(bare_scope)}
    finally:
        ops._interpret = interpret


def test_every_pallas_op_lies_under_its_own_kernel_scope(tiny_step_hlo):
    from harness import trace

    index = trace.hlo_index(tiny_step_hlo["tagged"])
    tags = _scope_tags(tiny_step_hlo["tagged"])
    kernels = collections.Counter()
    for name, (_, label) in index.items():
        if label.startswith("pallas:"):
            kernel = label.split(":", 1)[1]
            assert "kernel." + kernel in tags.get(name, ()), (name, label)
            kernels[kernel] += 1
    assert {"cross_dot", "fused_first_order",
            "fused_second_order"} <= set(kernels)
    rules = {t for ts in tags.values() for t in ts if t.startswith("rule.")}
    assert {"rule.Conv2d", "rule.Dense"} <= rules
    assert _scope_tags(tiny_step_hlo["bare"]) == {}


def test_scopes_leave_each_instruction_scope_and_label(tiny_step_hlo):
    from harness import trace

    tagged = trace.hlo_index(tiny_step_hlo["tagged"])
    assert tagged == trace.hlo_index(tiny_step_hlo["bare"])
    assert any(scope == "first_order_sweep" for scope, _ in tagged.values())

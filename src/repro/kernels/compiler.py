"""Shared Mosaic compiler-parameter plumbing for the reduction kernels.

Every ``pl.pallas_call`` in :mod:`repro.kernels` routes its TPU compiler
options through :func:`mosaic_params` so there is exactly one code path —
the ``pltpu.CompilerParams`` dataclass.

Under the interpreter (the CPU correctness path) no params are built at
all: Mosaic never runs, and ``pallas_call`` accepts ``None``.
"""
from __future__ import annotations


def mosaic_params(*dimension_semantics: str, interpret: bool = False):
    """Build ``CompilerParams(dimension_semantics=...)`` or ``None``.

    ``dimension_semantics`` is one ``"parallel"``/``"arbitrary"`` entry per
    grid axis; grid axes that accumulate into a revisited output block must
    be ``"arbitrary"`` (sequential) so the accumulator tile stays resident.
    """
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))

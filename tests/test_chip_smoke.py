"""CPU rehearsal of ``chip_smoke.py``: its phases at tiny sizes, kernels in
interpret mode, and its refusal to report a result without a TPU."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


def test_paper_path_kernels_match_jnp():
    """Phase 1 on 3C3D at N=8, 8x8 images: every output of the kernel
    route within tolerance of the jnp route (interpret mode, so no Mosaic
    calls in the program)."""
    assert chip_smoke.paper_path(n=8, img=8) == 0


def test_trainer_reduced_lm():
    """Phase 2 on the reduced stablelm-1.6b: two finite-loss steps."""
    losses = chip_smoke.trainer(chip_smoke.lm_config().reduced(), batch=2,
                                seq=8, steps=2)
    assert len(losses) == 2 and all(np.isfinite(losses))


def test_compare_flags_mismatch_and_nonfinite():
    want = {"a": jnp.ones((3,)), "b": jnp.zeros((2,))}
    assert chip_smoke.compare("same", want, want) == 0.0
    with pytest.raises(AssertionError, match="error/allowance"):
        chip_smoke.compare("off", {"a": jnp.ones((3,)) * 1.01,
                                   "b": jnp.zeros((2,))}, want)
    with pytest.raises(AssertionError, match="not finite"):
        chip_smoke.compare("nan", {"a": jnp.full((3,), jnp.nan),
                                   "b": jnp.zeros((2,))}, want)


def test_main_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "off")
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out

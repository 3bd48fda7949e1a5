"""The benchmark's own arithmetic, against counts worked out by hand."""
import importlib.util
import math
import os

import numpy as np
import pytest

from harness import check, layers
from tiny import PERFBENCH


def _module(*parts):
    path = os.path.join(PERFBENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "pb_" + "_".join(parts).replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LAYER = {"n": 4, "r": 3, "a": 5, "b": 2}


def test_cross_dot_counts_one_layer():
    # N² = 16 dots over a·b = 10 entries: 2·16·10 = 320 operations;
    # inputs 4·3·(5+2) = 84 float32 = 336 bytes, output 16 float32 = 64.
    flops, nbytes = _module("metrics", "cross_dot_roofline.py").required(
        LAYER, 4)
    assert (flops, nbytes) == (320, 400)


@pytest.mark.parametrize("l2,moment,want", [
    (True, True, (160, 336 + 4 * (4 + 10))),
    (True, False, (80, 336 + 16)),
    (False, True, (80, 336 + 40)),
])
def test_fused_first_order_counts_one_layer(l2, moment, want):
    # each output: 2·N·a·b = 80 operations over the per-sample gradients
    m = _module("metrics", "fused_first_order_roofline.py")
    assert m.required(LAYER, 4, l2, moment) == want


def test_fused_second_order_counts_one_layer():
    # C = 10 columns: diag and trace 2·10·4·10 = 800 each; B factor
    # 2·10·4·3·2² = 960.  Inputs 4·3·5 + 10·4·3·2 = 300 float32;
    # outputs diag 10, B 4, trace 4 float32.
    m = _module("metrics", "fused_second_order_roofline.py")
    flops, nbytes = m.required(LAYER, 4, 10, {"diag", "kron", "trace"})
    assert flops == 800 + 800 + 960
    assert nbytes == 4 * 300 + 4 * (10 + 4 + 4)
    assert m.sweeps(["diag_ggn", "kflr", "diag_ggn_mc", "batch_l2"], 10, 1) \
        == [(10, {"diag", "kron"}), (1, {"diag"})]


def test_bf16_activations_halve_the_input_bytes():
    m = _module("metrics", "cross_dot_roofline.py")
    assert m.required(LAYER, 2) == (320, 2 * 84 + 64)


def test_c3d3_step_flops_by_hand():
    ref = _module("configs", "c3d3", "reference.py")
    cfg = {"img": 32, "in_channels": 3, "n_classes": 10,
           "conv_channels": [64, 96, 128], "conv_kernels": [5, 3, 3],
           "conv_padding": ["VALID", "VALID", "SAME"], "pool_window": 2,
           "pool_stride": 2, "dense": [512, 256]}
    shapes = [(L["r"], L["a"], L["b"]) for L in ref.layers(cfg, {"batch": 128})]
    # sides 32 -> 28 (5x5 VALID) -> 14 -> 12 (3x3 VALID) -> 6 -> 6 (SAME)
    # -> 3: DeepOBS's feature maps and its flatten width 3·3·128 = 1152
    assert shapes == [(784, 75, 64), (144, 576, 96), (36, 864, 128),
                      (1, 1152, 512), (1, 512, 256), (1, 256, 10)]
    # 784·75·64 + 144·576·96 + 36·864·128 + 1152·512 + 512·256 + 256·10
    per_sample = 3763200 + 7962624 + 3981312 + 589824 + 131072 + 2560
    assert ref.step_flops(cfg, {"batch": 128}) == 6 * 128 * per_sample


def test_step_mfu_and_roofline_reading():
    class Summary:
        by_scope = {(layers.EXT_MODULE, "first_order_sweep"): 0.03}
        by_label = {(layers.EXT_MODULE, "pallas:cross_dot"): 0.002}

    r = layers.Reading(summary=Summary(), steps=2, step_s=0.5,
                       config={"dtype": "float32"},
                       traffic={"extensions": ["batch_dot"]},
                       layers=[dict(LAYER, name="x")], step_flops=1e12,
                       exact_columns=10, peak_flops=2e14, peak_bw=1e12)
    # 1e12 operations in 0.25 s a step against 2e14/s: 2%
    assert _module("metrics", "step_mfu.py").read(r) == pytest.approx(2.0)
    assert _module("metrics", "first_order_sweep_ms.py").read(r) == \
        pytest.approx(15.0)
    assert _module("metrics", "second_order_sweep_ms.py").read(r) is None
    # least time max(320/2e14, 400/1e12) = 4e-10 s over 1e-3 s a step
    assert _module("metrics", "cross_dot_roofline.py").read(r) == \
        pytest.approx(100 * 4e-10 / 1e-3)
    assert _module("metrics", "fused_second_order_roofline.py").read(r) is None


def test_leaf_gap_uses_the_larger_of_leaf_and_median_norm():
    ref = {"a": 1.0, "b": 2.0, "c": 0.01, "key_bias": 1e-9}
    grad = {"a": 1.0, "b": 1.0, "c": 1.0, "key_bias": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 0.05, "key_bias": 1.0}
    # median of kept leaves is 1.0: a reads 0.1, c reads 0.04/1.0;
    # key_bias is left out (its gradient is under 1e-3 of the median)
    assert check.leaf_gap(prog, ref, grad) == pytest.approx(0.1)


def test_rel_gap_and_judge():
    assert check.rel_gap([1.0, 2.2], [1.0, 2.0]) == pytest.approx(0.1)
    assert check.rel_gap([0.1], [0.0], scales=[2.0]) == pytest.approx(0.05)
    ok, failed, checks = check.judge({"x": 0.1, "y": 0.5},
                                     {"x": 0.2, "y": 0.4})
    assert not ok and failed == ["y"]
    assert checks["x"] == {"value": 0.1, "limit": 0.2}
    ok, failed, _ = check.judge({"x": 0.1}, {"x": 0.2, "z": 1.0})
    assert not ok and failed == ["z"]
    ok, failed, _ = check.judge({"x": math.nan}, {"x": 0.2})
    assert failed == ["x"]


def test_numbers_cover_loss_updates_and_signatures():
    ref = {"ext": {"loss": [2.0, 2.0, 2.0],
                   "sig": {"batch_l2": {"w": np.array([1.0, 2.0, 4.0])}},
                   "norms": {"batch_l2": {"w": np.zeros(())}},
                   "update1": {"w": 1.0}, "change3": {"w": 3.0},
                   "grad1": {"w": 1.0}}}
    prog = {"ext": {"loss": [2.0, 2.2, 2.0],
                    "sig": {"batch_l2": {"w": np.array([1.3, 2.0, 4.0])}},
                    "update1": {"w": 1.0}, "change3": {"w": 0.0}}}
    n = check.numbers(prog, ref)
    # sample 0 reads 1.3 against 1.0, floored at the median sample's 2.0;
    # of three samples the 95th percentile is the worst
    assert n == pytest.approx({"ext.loss": 0.1, "ext.batch_l2": 0.15,
                               "ext.update1": 0.0, "ext.change3": 1.0})


@pytest.mark.parametrize("q,prog,ref,norms,want", [
    # diagonal 4, 9, 16 floored at its median 9; pair (1, 2) off by 0.45
    # against sqrt(9·16) = 12, and so are (2, 1)'s rows: rows 1 and 2 read
    # 0.0375, row 0 reads 0; the 95th percentile of three rows is the worst
    ("batch_dot", [[4.0, 1.0, 0.0], [1.0, 9.0, 2.45], [0.0, 2.45, 16.0]],
     [[4.0, 1.0, 0.0], [1.0, 9.0, 2.0], [0.0, 2.0, 16.0]],
     np.zeros(()), 0.0375),
    # sample 1's projection off by 0.5; its norm 5 (the median of 1 and 5
    # is 3, so sample 0 would be floored at 3)
    ("batch_grad", [[1.0, 0.0], [2.5, 1.0]], [[1.0, 0.0], [2.0, 1.0]],
     np.array([1.0, 5.0]), 0.1),
    # a projected tensor: gap 0.2 against its Frobenius norm 4
    ("variance", [1.2, 0.0], [1.0, 0.0], np.array(4.0), 0.05),
])
def test_structure_gap_scales_by_hand(q, prog, ref, norms, want):
    got = check.structure_gap(q, {"w": np.array(prog)}, {"w": np.array(ref)},
                              {"w": norms})
    assert got == pytest.approx(want)


def test_per_sample_reading_passes_over_a_few_samples_only():
    # 128 samples with reference value 1: six read 2 (gap 1), the rest
    # exact -> the 95th percentile (the 7th worst) is exact; a seventh
    # sample off shows
    ref = {"w": np.ones(128)}
    prog = np.ones(128)
    prog[:6] = 2.0
    norms = {"w": np.zeros(())}
    assert check.structure_gap("batch_l2", {"w": prog}, ref, norms) == 0.0
    prog[6] = 2.0
    assert check.structure_gap("batch_l2", {"w": prog}, ref, norms) == 1.0
    # pairs: one sample's row and column off leaves every other row's
    # 95th percentile exact, and it is one row of 128
    dot = np.eye(128)
    bad = dot.copy()
    bad[3, :] += 0.5
    bad[:, 3] += 0.5
    assert check.structure_gap("batch_dot", {"w": bad}, {"w": dot},
                               norms) == 0.0
    bad[:8, :] += 0.5            # a tile of 8 rows off
    assert check.structure_gap("batch_dot", {"w": bad}, {"w": dot},
                               norms) > 0.4


def test_structure_gap_refuses_a_missing_or_reshaped_leaf():
    ref = {"w": np.ones(4)}
    norms = {"w": np.zeros(())}
    assert check.structure_gap("batch_l2", {"w": np.ones(2)}, ref,
                               norms) == math.inf
    assert check.structure_gap("batch_l2", {}, ref, norms) == math.inf
    assert check.structure_gap("batch_l2", None, ref, norms) == math.inf


def test_signature_sees_a_permutation_that_the_mean_hides():
    import jax.numpy as jnp

    from harness import signatures

    g = jnp.arange(24.0).reshape(4, 3, 2) ** 0.5
    swapped = g[jnp.array([1, 0, 2, 3])]
    assert float(jnp.mean(g)) == pytest.approx(float(jnp.mean(swapped)))
    a = np.asarray(signatures.sign("batch_grad", g))
    b = np.asarray(signatures.sign("batch_grad", swapped))
    assert a.shape == (4, signatures.K)
    np.testing.assert_allclose(a[[1, 0, 2, 3]], b, rtol=1e-6)
    assert np.max(np.abs(a - b)) > 0.1 * np.max(np.abs(a))
    # the same directions in every call: a projection is reproducible
    np.testing.assert_array_equal(a, np.asarray(
        signatures.sign("batch_grad", g)))


def test_peak_table_refuses_an_unknown_device():
    assert layers.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        layers.peaks("cpu")

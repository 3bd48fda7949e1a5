"""First-order extensions vs autodiff oracles (paper §2.2 / App. A.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Activation,
    BatchGrad,
    BatchL2,
    CrossEntropyLoss,
    Dense,
    MSELoss,
    SecondMoment,
    Sequential,
    Variance,
    oracle,
    run,
)

N, D, H, C = 6, 5, 7, 4


@pytest.fixture(scope="module")
def setup():
    model = Sequential([Dense(D, H), Activation("sigmoid"), Dense(H, C)])
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    y = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, C)
    loss = CrossEntropyLoss()
    res = run(model, params, x, y, loss,
              extensions=(BatchGrad, BatchL2, SecondMoment, Variance))
    psg = oracle.per_sample_grads(model, loss, params, x, y)
    og = oracle.grad(model, loss, params, x, y)
    return model, params, x, y, loss, res, psg, og


def test_loss_and_grads(setup):
    model, params, x, y, loss, res, psg, og = setup
    np.testing.assert_allclose(
        res.loss, oracle.loss_fn(model, loss, params, x, y), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(res.grads), jax.tree.leaves(og)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_batch_grad(setup):
    *_, res, psg, og = setup
    for a, b in zip(jax.tree.leaves(res["batch_grad"]), jax.tree.leaves(psg)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_batch_grad_sums_to_grad(setup):
    *_, res, psg, og = setup
    for a, b in zip(jax.tree.leaves(res["batch_grad"]), jax.tree.leaves(og)):
        np.testing.assert_allclose(jnp.sum(a, 0), b, rtol=1e-4, atol=1e-6)


def test_batch_l2(setup):
    *_, res, psg, og = setup
    for a, g in zip(jax.tree.leaves(res["batch_l2"]), jax.tree.leaves(psg)):
        np.testing.assert_allclose(
            a, jnp.sum(g.reshape(N, -1) ** 2, -1), rtol=1e-4, atol=1e-9)


def test_second_moment_and_variance(setup):
    *_, res, psg, og = setup
    sm = jax.tree.map(lambda g: N * jnp.sum(g ** 2, 0), psg)
    for a, b in zip(jax.tree.leaves(res["second_moment"]), jax.tree.leaves(sm)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-8)
    var = jax.tree.map(lambda s, g: s - g ** 2, sm, og)
    for a, b in zip(jax.tree.leaves(res["variance"]), jax.tree.leaves(var)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_mse_loss_path():
    model = Sequential([Dense(D, C)])
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    y = jax.random.normal(jax.random.PRNGKey(2), (N, C))
    loss = MSELoss()
    res = run(model, params, x, y, loss, extensions=(BatchGrad,))
    psg = oracle.per_sample_grads(model, loss, params, x, y)
    for a, b in zip(jax.tree.leaves(res["batch_grad"]), jax.tree.leaves(psg)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_padding_mask_tokens_excluded():
    """y = -1 positions must not contribute to loss or stats."""
    model = Sequential([Dense(D, C)])
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (N, 3, D))
    y = jax.random.randint(jax.random.PRNGKey(2), (N, 3), 0, C)
    y_mask = y.at[:, -1].set(-1)
    loss = CrossEntropyLoss()
    r1 = run(model, params, x, y_mask, loss, extensions=(BatchGrad,))
    # oracle: zero-out masked positions by slicing
    r2 = run(model, params, x[:, :2], y[:, :2], loss, extensions=(BatchGrad,))
    for a, b in zip(jax.tree.leaves(r1.grads), jax.tree.leaves(r2.grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_batch_dot_matches_oracle(setup):
    from repro.core import BatchDot

    model, params, x, y, loss, _, psg, _ = setup
    res = run(model, params, x, y, loss, extensions=(BatchDot, BatchL2))
    for d, g, l2 in zip(jax.tree.leaves(res["batch_dot"]),
                        jax.tree.leaves(psg),
                        jax.tree.leaves(res["batch_l2"])):
        gf = np.asarray(g, np.float32).reshape(N, -1)
        np.testing.assert_allclose(np.asarray(d), gf @ gf.T,
                                   rtol=2e-4, atol=1e-8)
        # diagonal of the pairwise dots == batch_l2
        np.testing.assert_allclose(np.asarray(jnp.diagonal(d)),
                                   np.asarray(l2), rtol=2e-4, atol=1e-8)


# -- BatchDot's weight leaf from the per-sample gradients BatchGrad formed ----

ROUTES = "rule.batch_dot."


def _routes(fn):
    """``(fn(), {route: count})`` of the ``rule.batch_dot.*`` counters."""
    from repro import obs

    reg = obs.ObsRegistry()
    with obs.use(reg):
        out = fn()
    return out, {k[len(ROUTES):]: v for k, v in reg.counters.items()
                 if k.startswith(ROUTES)}


@pytest.fixture(scope="module")
def conv_setup():
    """2C2D on 8x8 images (both convolutions at R > 1), its per-sample
    gradients, and each Conv2d's unfolded input A [N, R, a] with a random
    output cotangent B [N, R, b] (already / N) for the rule called
    directly."""
    from repro.configs.papernets import c2d2
    from repro.nn.layers import Conv2d

    model = c2d2(n_classes=C, in_ch=1, img=8)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (N, 8, 8, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, C)
    loss = CrossEntropyLoss()
    psg = oracle.per_sample_grads(model, loss, params, x, y)
    _, tapes = model.forward_tape(params, x)
    factors = []
    for i, (m, t) in enumerate(zip(model.mods, tapes)):
        if isinstance(m, Conv2d):
            pat, (hh, ww) = m._unfold(t)
            B = jax.random.normal(jax.random.PRNGKey(10 + i),
                                  (N, hh * ww, m.c_out)) / N
            factors.append((pat, B))
    return model, params, x, y, loss, psg, factors


def _gram(g, cs=None):
    g = np.asarray(g, np.float64).reshape(g.shape[0], -1)
    return g @ g.T if cs is None else g[:cs] @ g[cs:].T


@pytest.mark.parametrize("split", [None, 2], ids=["whole", "cross_split"])
@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["jnp", "kernels"])
def test_batch_dot_reads_batch_grad(conv_setup, use_kernels, split):
    """With BatchGrad requested, BatchDot's weight leaf at R > 1 is G·Gᵀ of
    the returned per-sample gradients (the ``[:cs] × [cs:]`` block under
    ``cross_split``), once per convolution, on either ``use_kernels``
    route; without BatchGrad the factor route (``cross_dot`` kernel or
    einsum) still serves it and agrees."""
    from repro.core import BatchDot, ExtensionConfig
    from repro.core.module import dense_first_order_stats
    from repro.nn.layers import Conv2d

    model, params, x, y, loss, psg, factors = conv_setup
    n_conv, n_dense = len(factors), 2
    factor_route = "cross_dot" if use_kernels else "einsum"
    if split is None:
        cfg = ExtensionConfig(use_kernels=use_kernels)
        both, seen = _routes(lambda: run(model, params, x, y, loss,
                                         extensions=(BatchGrad, BatchDot),
                                         cfg=cfg))
        alone, seen_alone = _routes(lambda: run(model, params, x, y, loss,
                                                extensions=(BatchDot,),
                                                cfg=cfg))
        assert seen == {"from_batch_grad": n_conv, "rank1": n_dense}
        assert seen_alone == {factor_route: n_conv, "rank1": n_dense}
        pairs = [(both["batch_grad"][i]["w"], both["batch_dot"][i]["w"],
                  alone["batch_dot"][i]["w"], psg[i]["w"])
                 for i, m in enumerate(model.mods) if isinstance(m, Conv2d)]
    else:
        cfg = ExtensionConfig(use_kernels=use_kernels, cross_split=split)
        pairs = []
        for A, B in factors:
            both, seen = _routes(lambda: dense_first_order_stats(
                A, B, (BatchGrad, BatchDot), cfg, bias=True))
            alone, seen_alone = _routes(lambda: dense_first_order_stats(
                A, B, (BatchDot,), cfg, bias=True))
            assert seen == {"from_batch_grad": 1}
            assert seen_alone == {factor_route: 1}
            want = np.einsum("nra,nrb->nab", np.asarray(A, np.float64),
                             np.asarray(B, np.float64))
            pairs.append((both["batch_grad"]["w"], both["batch_dot"]["w"],
                          alone["batch_dot"]["w"], want))
    assert len(pairs) == n_conv
    for g, dot, dot_alone, want in pairs:
        assert dot.shape == ((N, N) if split is None else (split, N - split))
        np.testing.assert_allclose(np.asarray(dot), _gram(g, split),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dot), _gram(want, split),
                                   rtol=2e-4, atol=1e-8)
        np.testing.assert_allclose(np.asarray(dot_alone), np.asarray(dot),
                                   rtol=2e-4, atol=1e-8)


def test_batch_dot_routes_on_3c3d():
    """The chip benchmark's 3C3D with its ``dot_mc`` traffic, traced at the
    published 32x32: BatchDot reads BatchGrad's per-sample gradients at
    the three convolutions and takes the rank-1 closed form at the three
    dense layers; no layer runs ``cross_dot``."""
    import importlib.util
    import json
    import os

    from repro.core import ExtensionConfig, by_name

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    with open(os.path.join(bench, "configs", "c3d3", "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "dot_mc.n128.json")) as f:
        traffic = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "c3d3_build", os.path.join(bench, "configs", "c3d3", "build.py"))
    build = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(build)
    model = build.build(config)
    side, n = config["img"], 4
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((n, side, side, config["in_channels"]),
                             jnp.float32)
    y = jax.ShapeDtypeStruct((n,), jnp.int32)
    exts = tuple(by_name(e) for e in traffic["extensions"])
    cfg = ExtensionConfig(**traffic["ext_config"])
    _, seen = _routes(lambda: jax.eval_shape(
        lambda p, x, y, k: run(model, p, x, y, CrossEntropyLoss(), exts,
                               cfg, rng=k).ext,
        params, x, y, jax.random.PRNGKey(1)))
    assert seen == {"from_batch_grad": 3, "rank1": 3}

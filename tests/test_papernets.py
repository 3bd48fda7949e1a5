"""Conv substrate (the paper's own benchmark nets) vs oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs.papernets import c2d2, c3d3, logreg, mlp
from repro.core import (
    BatchGrad,
    BatchL2,
    CrossEntropyLoss,
    DiagGGN,
    KFAC,
    KFLR,
    ExtensionConfig,
    SecondMoment,
    Variance,
    oracle,
    run,
)

LOSS = CrossEntropyLoss()


@pytest.fixture(scope="module")
def conv_setup():
    model = c2d2(n_classes=4, in_ch=1, img=8)
    # shrink for oracle feasibility
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (3,), 0, 4)
    return model, params, x, y


def test_conv_grads_and_batch_stats(conv_setup):
    model, params, x, y = conv_setup
    res = run(model, params, x, y, LOSS,
              extensions=(BatchGrad, BatchL2, SecondMoment, Variance))
    og = oracle.grad(model, LOSS, params, x, y)
    for a, b in zip(jax.tree.leaves(res.grads), jax.tree.leaves(og)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)
    psg = oracle.per_sample_grads(model, LOSS, params, x, y)
    for a, b in zip(jax.tree.leaves(res["batch_grad"]), jax.tree.leaves(psg)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)
    sm = jax.tree.map(lambda g: 3 * jnp.sum(g ** 2, 0), psg)
    for a, b in zip(jax.tree.leaves(res["second_moment"]), jax.tree.leaves(sm)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-8)


def test_conv_diag_ggn_small():
    # tiny conv chain: the explicit-GGN oracle materializes [P, P]
    from repro.core import Activation, Dense, Sequential
    from repro.nn.layers import Conv2d, Flatten, MaxPool2d

    model = Sequential([
        Conv2d(1, 4, kernel=3), Activation("relu"), MaxPool2d(2),
        Conv2d(4, 6, kernel=3), Activation("relu"), MaxPool2d(2),
        Flatten(), Dense(6, 3),
    ])
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 4, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (2,), 0, 3)
    res = run(model, params, x, y, LOSS, extensions=(DiagGGN,))
    want = oracle.ggn_diag(model, LOSS, params, x, y)
    got, _ = ravel_pytree(res["diag_ggn"])
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)


def test_logreg_and_mlp_train():
    from repro.optim import curvature_optimizer
    from repro.optim.optimizers import apply_updates
    from repro.core.engine import run as erun

    model = mlp(n_classes=4, in_dim=10, hidden=(16,))
    params = model.init(jax.random.PRNGKey(0))
    opt = curvature_optimizer(1.0, damping=1e-1, curvature="kfac")
    opt_state = opt.init(params)
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (32, 10))
    y = (x[:, 0] > 0).astype(jnp.int32) + 2 * (x[:, 1] > 0).astype(jnp.int32)
    losses = []
    for i in range(20):
        res = erun(model, params, x, y, LOSS, extensions=(KFAC,),
                   cfg=ExtensionConfig(), rng=jax.random.fold_in(k, i))
        ups, opt_state = opt.update(res.grads, opt_state, params,
                                    curv=res.ext["kfac"])
        params = apply_updates(params, ups)
        losses.append(float(res.loss))
    assert losses[-1] < 0.5 * losses[0], losses


def test_kflr_kfac_factor_shapes(conv_setup):
    model, params, x, y = conv_setup
    res = run(model, params, x, y, LOSS, extensions=(KFLR, KFAC),
              rng=jax.random.PRNGKey(5))
    f = res["kflr"][0]  # first conv layer
    a_dim = 5 * 5 * 1
    assert f["w"]["A"].shape == (a_dim, a_dim)
    assert f["w"]["B"].shape == (32, 32)
    f2 = res["kfac"][0]
    assert f2["w"]["B"].shape == (32, 32)


@pytest.mark.parametrize("hw,size,stride", [((8, 8), 2, None),
                                            ((9, 7), 2, None),
                                            ((9, 9), 3, None),
                                            ((8, 8), 3, 2)])
def test_maxpool_jac_t_mat_matches_vjp(hw, size, stride):
    """MaxPool2d's one-hot transpose equals the generic vmap(vjp) one,
    ties (first max in row-major window order) and ragged edges included;
    overlapping windows fall back to the generic form."""
    from repro.core.module import Module
    from repro.nn.layers import MaxPool2d

    mp = MaxPool2d(size, stride)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.nn.relu(jax.random.normal(k1, (3,) + hw + (5,)))
    x = x.at[0, :size, :size, 0].set(1.5)  # a tie inside one window
    M = jax.random.normal(k2, (4,) + mp.apply(None, x).shape)
    got = mp.jac_t_mat(None, x, M)
    want = Module.jac_t_mat(mp, None, x, M)
    assert got.shape == want.shape == (4,) + x.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

"""Fused first-order kernel: parity, masks, registry, engine routing."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BatchDot,
    BatchGrad,
    BatchL2,
    CrossEntropyLoss,
    ExtensionConfig,
    SecondMoment,
    Variance,
    first_order_mask,
    plan_sweeps,
    run,
)
from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16]
TOL = {jnp.float32: dict(rtol=3e-5, atol=3e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}
LOSS = CrossEntropyLoss()


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _pair(e, n, r, a, b, dtype=jnp.float32, seed=0):
    k = jax.random.PRNGKey(seed)
    return (_rand(k, (e, n, r, a), dtype),
            _rand(jax.random.fold_in(k, 1), (e, n, r, b), dtype))


def _dots(A, B):
    """Pairwise dots of the materialized per-sample gradients [E, N, N]."""
    g = jnp.einsum("enra,enrb->enab", A.astype(jnp.float32),
                   B.astype(jnp.float32))
    return jnp.einsum("enab,emab->enm", g, g)


# --- kernel vs oracle parity -------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("e,n,r,a,b", [
    (1, 3, 5, 17, 9),       # nothing block-aligned
    (1, 6, 1, 33, 65),      # R=1 rank-1 case, odd features
    (2, 4, 7, 130, 24),     # grouped
    (3, 1, 2, 8, 300),      # single sample, wide output
])
def test_fused_parity_all_outputs(e, n, r, a, b, dtype):
    A, B = _pair(e, n, r, a, b, dtype, seed=e * n + a)
    got = ops.fused_first_order(A, B, want_l2=True, want_moment=True)
    want = ref.fused_first_order(A, B, want_l2=True, want_moment=True)
    got["dot"], want["dot"] = ops.cross_dot(A, B, A, B), _dots(A, B)
    for key in ("l2", "moment", "dot"):
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]), **TOL[dtype])


@pytest.mark.parametrize("block_a,block_b", [(8, 8), (16, 32), (32, 16)])
def test_fused_parity_multi_tile(block_a, block_b):
    """Force feature tiling so the cross-tile l2/dot accumulation
    (zero-init at grid step (0,0) + `+=` across (i, j)) is exercised —
    the auto block policy would otherwise make every test single-tile."""
    A, B = _pair(2, 5, 3, 50, 41, seed=7)
    got = ops.fused_first_order(A, B, want_l2=True, want_moment=True,
                                block_a=block_a, block_b=block_b)
    want = ref.fused_first_order(A, B, want_l2=True, want_moment=True)
    got["dot"] = ops.cross_dot(A, B, A, B, block_a=block_a, block_b=block_b)
    want["dot"] = _dots(A, B)
    for key in ("l2", "moment", "dot"):
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]),
                                   rtol=3e-5, atol=3e-5, err_msg=key)


def test_fused_all_mask_combinations():
    """Every 2^2 mask: requested keys present and correct, others absent."""
    A, B = _pair(1, 5, 3, 19, 11)
    for wl, wm in itertools.product([False, True], repeat=2):
        if not (wl or wm):
            with pytest.raises(ValueError):
                ops.fused_first_order(A, B, want_l2=False, want_moment=False)
            continue
        got = ops.fused_first_order(A, B, want_l2=wl, want_moment=wm)
        want = ref.fused_first_order(A, B, want_l2=wl, want_moment=wm)
        assert set(got) == set(want)
        for key in got:
            np.testing.assert_allclose(np.asarray(got[key]),
                                       np.asarray(want[key]),
                                       rtol=3e-5, atol=3e-5)


def test_fused_internal_consistency():
    """diag(cross_dot) == l2, and moment == Σ_n of the per-sample outer
    squares."""
    A, B = _pair(1, 7, 4, 23, 13)
    got = ops.fused_first_order(A, B, want_l2=True, want_moment=True)
    dot = ops.cross_dot(A, B, A, B)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(dot[0])),
                               np.asarray(got["l2"][0]), rtol=3e-5, atol=3e-5)
    g = jnp.einsum("nra,nrb->nab", A[0], B[0])
    np.testing.assert_allclose(np.asarray(got["moment"][0]),
                               np.asarray(jnp.sum(g * g, 0)),
                               rtol=3e-5, atol=3e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 8), r=st.integers(1, 6), a=st.integers(1, 33),
       b=st.integers(1, 33), seed=st.integers(0, 2 ** 16))
def test_fused_hypothesis_parity(n, r, a, b, seed):
    A, B = _pair(1, n, r, a, b, seed=seed)
    got = ops.fused_first_order(A, B, want_l2=True, want_moment=True)
    want = ref.fused_first_order(A, B, want_l2=True, want_moment=True)
    got["dot"], want["dot"] = ops.cross_dot(A, B, A, B), _dots(A, B)
    for key in got:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]),
                                   rtol=5e-5, atol=5e-5)
    assert (np.asarray(got["l2"]) >= -1e-6).all()


# --- dispatch registry -------------------------------------------------------

def test_registry_contents_and_specs():
    names = ops.registered()
    for expected in ("sq_matmul", "per_sample_moment", "batch_l2",
                     "ggn_diag", "fused_first_order"):
        assert expected in names
        spec = ops.get_spec(expected)
        assert spec.ref is not None and spec.description
    with pytest.raises(KeyError):
        ops.dispatch("no_such_kernel", jnp.zeros((2, 2)))


def test_registry_jit_cache_is_config_keyed():
    ops.clear_cache()
    A, B = _pair(1, 4, 2, 16, 8)
    ops.fused_first_order(A, B, want_l2=True)
    n0 = ops.cache_stats()["total"]
    ops.fused_first_order(A, B, want_l2=True)          # same config: cached
    assert ops.cache_stats()["total"] == n0
    A2, B2 = _pair(1, 4, 2, 24, 8)
    got = ops.fused_first_order(A2, B2, want_l2=True)  # new shape: same entry
    assert ops.cache_stats()["total"] == n0            # (jax.jit retraces)
    np.testing.assert_allclose(
        np.asarray(got["l2"]),
        np.asarray(ref.fused_first_order(A2, B2, want_l2=True)["l2"]),
        rtol=3e-5, atol=3e-5)
    ops.fused_first_order(A, B, want_l2=True, want_moment=True)  # new opts
    stats = ops.cache_stats()
    assert stats["total"] == n0 + 1
    assert stats["fused_first_order"] >= 2


# --- engine routing ----------------------------------------------------------

ALL_FIRST = (BatchGrad, BatchL2, SecondMoment, Variance, BatchDot)


def test_sweep_plan_fused_mask():
    plan = plan_sweeps(ALL_FIRST)
    assert plan.fused_mask.l2 and plan.fused_mask.moment
    assert not plan.fused_active  # default config: jnp path
    assert "fused_first_order=None" in plan.describe()
    active = plan_sweeps(ALL_FIRST, ExtensionConfig(use_kernels=True))
    assert active.fused_active
    assert "fused_first_order=['l2', 'moment']" in active.describe()
    legacy = plan_sweeps(ALL_FIRST, ExtensionConfig(use_kernels=True,
                                                    use_fused=False))
    assert not legacy.fused_active
    plan = plan_sweeps((BatchGrad,))
    assert not plan.fused_mask.any()
    mask = first_order_mask({"variance"})
    assert mask.moment and not mask.l2
    assert mask.wants() == dict(want_l2=False, want_moment=True)


def _paper_nets():
    from repro.configs.papernets import c2d2, logreg, mlp

    k = jax.random.PRNGKey(3)
    x_img = jax.random.normal(k, (4, 8, 8, 1))
    x_flat = jax.random.normal(k, (4, 12))
    return [
        ("logreg", logreg(n_classes=5, in_dim=12), x_flat),
        ("mlp", mlp(n_classes=5, in_dim=12, hidden=(9,)), x_flat),
        ("2c2d", c2d2(n_classes=5, in_ch=1, img=8), x_img),
    ]


@pytest.mark.parametrize("name,model,x", _paper_nets(),
                         ids=[n for n, _, _ in _paper_nets()])
def test_engine_fused_matches_jnp_on_papernets(name, model, x):
    """use_kernels=True (fused) ≡ pure-jnp path to 1e-5, all first-order
    extensions, on the paper's benchmark architectures."""
    params = model.init(jax.random.PRNGKey(0))
    y = jax.random.randint(jax.random.PRNGKey(1), (x.shape[0],), 0, 5)
    res_jnp = run(model, params, x, y, LOSS, extensions=ALL_FIRST,
                  cfg=ExtensionConfig(use_kernels=False))
    res_fused = run(model, params, x, y, LOSS, extensions=ALL_FIRST,
                    cfg=ExtensionConfig(use_kernels=True))
    for ext in ("batch_grad", "batch_l2", "second_moment", "variance",
                "batch_dot"):
        ja, fu = (jax.tree.leaves(res_jnp.ext[ext]),
                  jax.tree.leaves(res_fused.ext[ext]))
        assert len(ja) == len(fu) and ja
        for a, b in zip(ja, fu):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5, err_msg=ext)


def test_engine_legacy_kernel_path_still_matches():
    """use_fused=False keeps the one-kernel-per-extension baseline correct."""
    from repro.configs.papernets import mlp

    model = mlp(n_classes=4, in_dim=10, hidden=(8,))
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 10))
    y = jax.random.randint(jax.random.PRNGKey(2), (5,), 0, 4)
    res_jnp = run(model, params, x, y, LOSS, extensions=ALL_FIRST,
                  cfg=ExtensionConfig(use_kernels=False))
    res_leg = run(model, params, x, y, LOSS, extensions=ALL_FIRST,
                  cfg=ExtensionConfig(use_kernels=True, use_fused=False))
    for ext in ("batch_l2", "second_moment", "variance", "batch_dot"):
        for a, b in zip(jax.tree.leaves(res_jnp.ext[ext]),
                        jax.tree.leaves(res_leg.ext[ext])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5, err_msg=ext)


def test_batched_dense_expert_moment_fused():
    """MoE experts: fused kernel (expert group axis) ≡ the einsum formula."""
    from repro.core.extensions import SecondMoment as SM
    from repro.nn.layers import BatchedDense

    mod = BatchedDense(3, 7, 5)
    params = mod.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 7))
    y, tape = mod.forward_tape(params, x)
    g = jax.random.normal(jax.random.PRNGKey(2), y.shape)
    _, _, st_jnp = mod.backward(params, tape, g, (SM,),
                                ExtensionConfig(use_kernels=False))
    _, _, st_ker = mod.backward(params, tape, g, (SM,),
                                ExtensionConfig(use_kernels=True))
    np.testing.assert_allclose(np.asarray(st_ker["_sum_grad2"]["w"]),
                               np.asarray(st_jnp["_sum_grad2"]["w"]),
                               rtol=1e-5, atol=1e-5)
    # use_fused=False must fall back to the einsum baseline for experts too
    _, _, st_leg = mod.backward(params, tape, g, (SM,),
                                ExtensionConfig(use_kernels=True,
                                                use_fused=False))
    np.testing.assert_allclose(np.asarray(st_leg["_sum_grad2"]["w"]),
                               np.asarray(st_jnp["_sum_grad2"]["w"]),
                               rtol=1e-6, atol=1e-6)


# --- variance invariants (property) -----------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 8), d=st.integers(2, 9), c=st.integers(2, 5),
       seed=st.integers(0, 2 ** 16))
def test_fused_variance_nonneg_and_identity(n, d, c, seed):
    """Fused-path variance ≥ 0 and equals N·Σ g² − (Σ g)² = smom − N²·mean²."""
    from repro.configs.papernets import mlp

    model = mlp(n_classes=c, in_dim=d, hidden=(d + 1,))
    params = model.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, d))
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (n,), 0, c)
    res = run(model, params, x, y, LOSS,
              extensions=(BatchGrad, SecondMoment, Variance),
              cfg=ExtensionConfig(use_kernels=True))
    for v in jax.tree.leaves(res["variance"]):
        assert float(jnp.min(v)) >= -1e-5
    # variance == second_moment − N² · mean² with mean = (Σ_n g_n)/N
    for var, sm, bg in zip(jax.tree.leaves(res["variance"]),
                           jax.tree.leaves(res["second_moment"]),
                           jax.tree.leaves(res["batch_grad"])):
        mean = jnp.sum(bg, 0) / n
        np.testing.assert_allclose(
            np.asarray(var), np.asarray(sm - (n * mean) ** 2 / 1.0),
            rtol=2e-4, atol=2e-5)

"""Batch-sharded sweep lane: reducer properties + sharded-vs-single parity.

In-process tests build the mesh from however many devices the process owns
— 1 in the default lanes (the shard_map path, the scale-corrected loss and
every reducer still execute), 8 in the ``tests-multidevice`` CI lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exported before
jax initializes).  A subprocess test (marked ``sharding``) guarantees
genuine multi-device exactness even when the running process owns a single
device; it skips itself where the in-process tests are already
multi-device.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ALL_EXTENSIONS,
    Activation,
    CrossEntropyLoss,
    Dense,
    DiagGGNMC,
    ExtensionConfig,
    Sequential,
    by_name,
    plan_sweeps,
    reduce_spec,
    run,
)
from repro.core.engine import _chan_merge, local_loss_and_grad
from repro.launch.mesh import make_data_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

N, D_IN, H, C = 16, 6, 7, 4


@pytest.fixture(scope="module")
def setup():
    model = Sequential([Dense(D_IN, H), Activation("sigmoid"), Dense(H, C)])
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D_IN))
    y = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, C)
    return model, params, x, y


# ---------------------------------------------------------------------------
# reducer declarations
# ---------------------------------------------------------------------------


def test_reduce_spec_table():
    from repro.core import Reducer

    spec = reduce_spec(ALL_EXTENSIONS)
    assert all(isinstance(r, Reducer) for r in spec.values())
    assert {nm: r.name for nm, r in spec.items()} == {
        "batch_grad": "concat",
        "batch_l2": "concat",
        "batch_dot": "gram",
        "second_moment": "psum",
        "variance": "moment_merge",
        "diag_ggn": "psum",
        "diag_ggn_mc": "psum",
        "kflr": "kron",
        "kfac": "kron",
        "kfra": "pmean",
        "diag_hessian": "psum",
        "ggn_trace": "concat",
        "ggn_gram": "gram_pair",
        "ntk": "gram",
        "ntk_classwise": "gram",
    }


def test_describe_reports_placement(setup):
    model, params, x, y = setup
    mesh = make_data_mesh()
    exts = (by_name("batch_l2"), by_name("variance"), by_name("kfac"))
    desc = plan_sweeps(exts, ExtensionConfig()).shard(mesh, "data").describe()
    assert "shard_axes=['data']" in desc
    assert f"shards={jax.device_count()}" in desc
    assert "batch_l2:concat->sharded(axis0)" in desc
    assert "variance:moment_merge->replicated" in desc
    assert "kfac:kron->replicated" in desc
    assert "grads:psum->replicated" in desc


# ---------------------------------------------------------------------------
# pairwise moment merge (the 'moment_merge' reducer's arithmetic)
# ---------------------------------------------------------------------------


@given(n_shards=st.integers(min_value=1, max_value=8),
       per_shard=st.integers(min_value=1, max_value=6),
       offset=st.floats(min_value=-100.0, max_value=100.0),
       seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_moment_merge_property(n_shards, per_shard, offset, seed):
    """A binary tree of Chan merges over per-shard (count, mean, M2)
    triples reproduces the global n·M2 == n·Σg² − (Σg)² exactly."""
    rng = np.random.default_rng(seed)
    g = rng.normal(size=(n_shards * per_shard, 3)) * 2.0 + offset
    parts = []
    for s in range(n_shards):
        loc = g[s * per_shard:(s + 1) * per_shard]
        nl = float(per_shard)
        mean = loc.sum(0) / nl
        m2 = (loc ** 2).sum(0) - loc.sum(0) ** 2 / nl
        parts.append((nl, mean, m2))
    while len(parts) > 1:
        merged = [_chan_merge(parts[i], parts[i + 1])
                  for i in range(0, len(parts) - 1, 2)]
        if len(parts) % 2:
            merged.append(parts[-1])
        parts = merged
    n, _, m2 = parts[0]
    direct = (g.shape[0] * (g ** 2).sum(0) - g.sum(0) ** 2)
    np.testing.assert_allclose(n * m2, direct, rtol=1e-9, atol=1e-7)


def test_moment_merge_beats_naive_cancellation():
    """The merge path never forms the catastrophically cancelling global
    Σg² − (Σg)²/n between large intermediates: with a large common offset
    in float32 it stays near the float64 truth where the naive single-pass
    formula has lost most of its bits."""
    rng = np.random.default_rng(0)
    g64 = rng.normal(size=(64,)) * 1e-2 + 1e4
    g = g64.astype(np.float32)
    truth = float(len(g64) * (((g64 - g64.mean()) ** 2).sum()))
    parts = []
    for s in range(8):
        loc = g[s * 8:(s + 1) * 8].astype(np.float32)
        nl = np.float32(8.0)
        mean = loc.sum() / nl
        m2 = ((loc - mean) ** 2).sum()
        parts.append((nl, mean, m2))
    while len(parts) > 1:
        parts = [_chan_merge(parts[i], parts[i + 1])
                 for i in range(0, len(parts), 2)]
    merged = float(parts[0][0] * parts[0][2])
    naive = float(
        np.float32(len(g)) * np.float32((g ** 2).sum())
        - np.float32(g.sum()) ** 2)
    assert abs(merged - truth) <= abs(naive - truth)
    np.testing.assert_allclose(merged, truth, rtol=5e-2)


# ---------------------------------------------------------------------------
# sharded lane behavior
# ---------------------------------------------------------------------------


def test_sharded_loss_logits_grads(setup):
    model, params, x, y = setup
    loss = CrossEntropyLoss()
    mesh = make_data_mesh()
    ref = run(model, params, x, y, loss)
    plan = plan_sweeps((), ExtensionConfig())
    res = plan.shard(mesh, "data").run(model, params, x, y, loss)
    np.testing.assert_allclose(np.asarray(res.loss), np.asarray(ref.loss),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res.logits),
                               np.asarray(ref.logits), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(ref.grads), jax.tree.leaves(res.grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_sharded_masked_loss_scaling(setup):
    """Uneven padding masks across shards: the psum'd unit count keeps the
    global 1/M normalization exact (a pmean of local means would not)."""
    model, params, x, _ = setup
    loss = CrossEntropyLoss()
    # first half of the batch almost fully masked — shard unit counts differ
    y = jax.random.randint(jax.random.PRNGKey(5), (N,), 0, C)
    y = y.at[: N // 2].set(-1)
    y = y.at[0].set(1)  # keep at least one valid unit in the first shards
    mesh = make_data_mesh()
    ref = run(model, params, x, y, loss, extensions=(by_name("batch_l2"),))
    res = plan_sweeps((by_name("batch_l2"),), ExtensionConfig()).shard(
        mesh, "data").run(model, params, x, y, loss)
    np.testing.assert_allclose(np.asarray(res.loss), np.asarray(ref.loss),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res.ext["batch_l2"][0]["w"]),
                               np.asarray(ref.ext["batch_l2"][0]["w"]),
                               rtol=3e-5, atol=3e-6)


def test_local_loss_and_grad_is_unreduced_seam(setup):
    """psum(local contributions) == the engine's global gradient — the
    compressed-DP step's compression seam."""
    from jax.sharding import PartitionSpec as P

    model, params, x, y = setup
    loss = CrossEntropyLoss()
    mesh = make_data_mesh()

    def body(p, xx, yy):
        lv, g = local_loss_and_grad(model, p, xx, yy, loss, ("data",))
        return lv, jax.tree.map(lambda a: jax.lax.psum(a, ("data",)), g)

    lv, g = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(), P(("data",)), P(("data",))),
                          out_specs=(P(), P()), check_vma=False)(params, x, y)
    ref = run(model, params, x, y, loss)
    np.testing.assert_allclose(np.asarray(lv), np.asarray(ref.loss),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref.grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_sharded_mc_needs_seed_or_rng(setup):
    model, params, x, y = setup
    sp = plan_sweeps((DiagGGNMC,), ExtensionConfig()).shard(
        make_data_mesh(), "data")
    with pytest.raises(ValueError, match="rng"):
        sp.run(model, params, x, y, CrossEntropyLoss())


@pytest.mark.skipif(jax.device_count() < 2
                    and not os.environ.get("REPRO_REQUIRE_MULTIDEVICE"),
                    reason="needs a multi-device process: divisibility is "
                           "trivially satisfied at 1 device, so the check "
                           "only bites on a real mesh; the tests-multidevice "
                           "CI lane (8 virtual devices) runs it with "
                           "REPRO_REQUIRE_MULTIDEVICE=1")
def test_sharded_batch_divisibility_error(setup):
    # under the require flag a 1-device process is a lane misconfiguration,
    # not a reason to skip
    assert jax.device_count() >= 2, (
        "REPRO_REQUIRE_MULTIDEVICE is set but the process owns "
        f"{jax.device_count()} device(s) — the multidevice lane must set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax "
        "initializes")
    model, params, x, y = setup
    sp = plan_sweeps((), ExtensionConfig()).shard(make_data_mesh(), "data")
    with pytest.raises(ValueError, match="divisible"):
        sp.run(model, params, x[:jax.device_count() + 1],
               y[:jax.device_count() + 1], CrossEntropyLoss())


def test_dist_kfac_step_matches_single_device(setup):
    """The end-to-end consumer: one sharded sweep → Kronecker factors →
    preconditioned update equals the single-device extended step (factor
    compression off for exact comparison).  Runs on the process's devices
    — 1 in the default lanes, 8 in tests-multidevice."""
    from repro.distributed import make_dist_kfac_step
    from repro.optim import curvature_optimizer
    from repro.train.step import make_extended_train_step

    model, params, x, y = setup
    loss = CrossEntropyLoss()
    batch = {"inputs": x, "labels": y}
    opt = curvature_optimizer(1e-2, curvature="kfac")
    state = opt.init(params)
    cfg = ExtensionConfig(mc_seed=0)
    rng = jax.random.PRNGKey(3)
    dist = make_dist_kfac_step(model, loss, opt, (by_name("kfac"),),
                               make_data_mesh(), cfg=cfg, compress=False)
    p1, _, m1 = dist(params, state, batch, jnp.int32(0), rng)
    single = make_extended_train_step(model, loss, opt, (by_name("kfac"),),
                                      cfg)
    p2, _, m2 = single(params, state, batch, jnp.int32(0), rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_dist_kfac_step_rejects_dataless_mesh(setup):
    from repro.distributed import make_dist_kfac_step
    from repro.launch.mesh import make_mesh
    from repro.optim import curvature_optimizer

    model, *_ = setup
    opt = curvature_optimizer(1e-2, curvature="kflr")
    with pytest.raises(ValueError, match="data-parallel axis"):
        make_dist_kfac_step(model, CrossEntropyLoss(), opt,
                            (by_name("kflr"),), make_mesh((1,), ("model",)))
    with pytest.raises(ValueError, match="curvature extension"):
        make_dist_kfac_step(model, CrossEntropyLoss(), opt, (),
                            make_data_mesh())


# ---------------------------------------------------------------------------
# genuine multi-device exactness from a single-device session (subprocess)
# ---------------------------------------------------------------------------

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import itertools, json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import (ALL_EXTENSIONS, Activation, CrossEntropyLoss,
                            Dense, ExtensionConfig, Sequential, run,
                            plan_sweeps)
    from repro.launch.mesh import make_mesh

    model = Sequential([Dense(6, 7), Activation("sigmoid"), Dense(7, 4)])
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 4)
    loss = CrossEntropyLoss()
    exts = tuple(ALL_EXTENSIONS)
    rng = jax.random.PRNGKey(42)
    checked = 0
    for nd in (2, 8):
        mesh = make_mesh((nd,), ("data",))
        for uk in (False, True):
            cfg = ExtensionConfig(use_kernels=uk)
            ref = run(model, params, x, y, loss, extensions=exts, cfg=cfg,
                      rng=rng)
            res = plan_sweeps(exts, cfg).shard(mesh, "data").run(
                model, params, x, y, loss, cfg=cfg, rng=rng)
            np.testing.assert_allclose(np.asarray(res.loss),
                                       np.asarray(ref.loss), rtol=1e-6)
            for name in ref.ext:
                ra = jax.tree.leaves(ref.ext[name])
                rb = jax.tree.leaves(res.ext[name])
                assert len(ra) == len(rb) and ra, name
                for a, b in zip(ra, rb):
                    assert a.shape == b.shape, (name, nd, uk)
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5,
                        err_msg=f"{{name}} nd={{nd}} uk={{uk}}")
                    checked += 1
    print(json.dumps({{"ok": True, "checked": checked}}))
""")


@pytest.mark.slow
@pytest.mark.sharding
def test_sharded_exactness_8dev_subprocess():
    if jax.device_count() >= 2:
        pytest.skip("in-process sharded tests already run multi-device")
    code = _SUBPROC.format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["checked"] > 0

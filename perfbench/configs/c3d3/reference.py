"""Plain reference for ``c3d3``: 3C3D and the quantities the extended step
produces, written from their definitions with ``jax.numpy`` and autodiff.

It imports nothing of the program.  It makes the weights and batches from
the seed (the program is handed the same arrays), and it runs the training
steps in a stated precision:

* ``"float32"``: float32 storage, every matmul and convolution at
  ``highest`` precision -- the reference the comparison trusts;
* ``"high"``: float32 storage, matmuls and convolutions at ``high``
  precision (three bfloat16 passes on a TPU) -- the control, the step
  below the configuration's float32 at ``highest``;
* ``"bfloat16"``: weights, data, activations, gradients and statistics in
  bfloat16 -- the step below that, which a CPU (where matmul precision
  has no effect) can show.

Definitions, for the mean cross-entropy ``L = (1/N) Σ_n ℓ_n`` over N
images, per-sample gradients ``g_n = ∇ℓ_n / N`` and per-sample
per-column vectors ``G_cn = J_nᵀ s_cn`` (``s`` a column of a symmetric
factor of the loss Hessian at the logits, carrying ``1/√N``), per leaf:

    batch_grad     g_n                    [N, *param]
    batch_l2       ‖g_n‖²                 [N]
    batch_dot      g_n · g_m              [N, N]
    second_moment  N Σ_n g_n²             [*param]
    variance       N Σ_n g_n² − (Σ_n g_n)²
    diag_ggn       Σ_c Σ_n G_cn²,  s_c = √p_c (e_c − p) / √N   (exact)
    diag_ggn_mc    Σ_n G_n²,       s = (p − e_ŷ) / √N, ŷ ~ Cat(softmax z_n)
                   drawn with ``categorical(fold_in(rng, n), z_n)``
    ggn_trace      Σ_c ‖G_cn‖²            [N]
    kflr           per layer A = Σ_nr x xᵀ / (N R),  B = R Σ_cnr y yᵀ
                   (x: the layer's input patch at position r, in
                   (channel, row, column) order; y: the column s_c
                   back-propagated to the layer's output there); the
                   weight's leaf holds {A, B}, the bias's {B}

Each quantity is a tree shaped like the parameters (an empty tuple for a
module without them), as the program's engine returns it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# precision name -> (storage dtype, matmul precision)
PRECISIONS = {"float32": (jnp.float32, "highest"),
              "high": (jnp.float32, "high"),
              "bfloat16": (jnp.bfloat16, "default")}
HIGHEST = jax.lax.Precision.HIGHEST


# -- architecture -------------------------------------------------------------


def layout(config):
    """Module list in the order the repository's Sequential holds it:
    ``("conv", k, c_in, c_out, padding)``, ``("dense", d_in, d_out)`` or
    a parameter-free ``("relu",)``, ``("pool",)``, ``("flatten",)``."""
    mods, c, side = [], config["in_channels"], config["img"]
    win, stride = config["pool_window"], config["pool_stride"]
    for k, c_out, pad in zip(config["conv_kernels"], config["conv_channels"],
                             config["conv_padding"]):
        mods += [("conv", k, c, c_out, pad), ("relu",), ("pool",)]
        side = side - k + 1 if pad == "VALID" else side
        c, side = c_out, (side - win) // stride + 1
    mods.append(("flatten",))
    d = side * side * c
    widths = list(config["dense"]) + [config["n_classes"]]
    for i, w in enumerate(widths):
        mods.append(("dense", d, w))
        if i + 1 < len(widths):
            mods.append(("relu",))
        d = w
    return mods


_JITTED = {}


def _once(name, config, build):
    """One jitted function per (use, configuration), built on first use."""
    k = (name, repr(sorted(config.items())))
    if k not in _JITTED:
        _JITTED[k] = build()
    return _JITTED[k]


def init_params(config, key):
    """normal(0, 1/fan_in) weights, zero biases, float32 (one jitted call)."""
    return _once("init", config, lambda: jax.jit(
        functools.partial(_init, layout(config))))(key)


def _init(mods, key):
    keys = jax.random.split(key, len(mods))
    out = []
    for m, k in zip(mods, keys):
        if m[0] == "conv":
            fan_in = m[1] * m[1] * m[2]
            out.append({"b": jnp.zeros((m[3],), jnp.float32),
                        "w": jax.random.normal(k, (fan_in, m[3]))
                        * fan_in ** -0.5})
        elif m[0] == "dense":
            out.append({"b": jnp.zeros((m[2],), jnp.float32),
                        "w": jax.random.normal(k, (m[1], m[2]))
                        * m[1] ** -0.5})
        else:
            out.append(())
    return tuple(out)


def make_batches(config, traffic, key, count):
    """``count`` CIFAR-shaped batches: normal pixels, uniform labels."""
    n, side = traffic["batch"], config["img"]

    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (count, n, side, side,
                                   config["in_channels"]), jnp.float32)
        y = jax.random.randint(ky, (count, n), 0, config["n_classes"],
                               jnp.int32)
        return x, y

    x, y = _once(f"batches{n}x{count}", config, lambda: jax.jit(make))(key)
    return [{"inputs": x[i], "labels": y[i]} for i in range(count)]


def _conv(x, w, b, k, pad):
    """Convolution; ``w`` is [c_in·k·k, c_out] in (c_in, kh, kw) order,
    the layout the repository stores."""
    c_out = w.shape[1]
    kern = w.reshape(-1, k, k, c_out).transpose(1, 2, 0, 3)     # HWIO
    y = jax.lax.conv_general_dilated(
        x, kern, (1, 1), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _patches(x, k, pad):
    """[N·R, c_in·k·k] input patches in (c_in, kh, kw) order."""
    pat = jax.lax.conv_general_dilated_patches(
        jnp.moveaxis(x, -1, 1), (k, k), (1, 1), pad,
        precision=HIGHEST)                                  # [N, C·k·k, H', W']
    return jnp.moveaxis(pat.reshape(pat.shape[0], pat.shape[1], -1),
                        1, 2).reshape(-1, pat.shape[1])


def forward(config, params, x, probes=None, inputs=None, outputs=None):
    """Logits.  ``probes`` (zeros shaped like each weighted layer's output)
    are added to those outputs, so that a vjp against them gives the
    back-propagated columns there; ``inputs`` and ``outputs`` collect each
    weighted layer's input and output."""
    mods, h, j = layout(config), x, 0
    win, stride = config["pool_window"], config["pool_stride"]
    for m, p in zip(mods, params):
        if m[0] in ("conv", "dense"):
            if inputs is not None:
                inputs.append(h)
            y = (_conv(h, p["w"], p["b"], m[1], m[4]) if m[0] == "conv"
                 else h @ p["w"] + p["b"])
            if probes is not None:
                y = y + probes[j]
            if outputs is not None:
                outputs.append(y)
            j += 1
            h = y
        elif m[0] == "relu":
            h = jax.nn.relu(h)
        elif m[0] == "pool":
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, win, win, 1), (1, stride, stride, 1),
                                      "VALID")
        else:
            h = h.reshape(h.shape[0], -1)
    return h


def _per_sample(config, params, x, cot):
    """Per-sample parameter vectors ``J_nᵀ cot_n`` ([N, *param] leaves)."""
    def one(xn, cn):
        return jax.grad(lambda q: jnp.sum(
            forward(config, q, xn[None])[0] * cn))(params)

    return jax.vmap(one)(x, cot)


def _columns_at_outputs(config, params, x, cot):
    """``cot`` back-propagated to each weighted layer's output."""
    def outputs():
        out = []
        forward(config, params, x, outputs=out)
        return out

    probes = [jnp.zeros(s.shape, s.dtype) for s in jax.eval_shape(outputs)]
    _, vjp = jax.vjp(lambda pr: forward(config, params, x, probes=pr), probes)
    return vjp(cot)[0]


def _per_layer(params, values):
    """Place one value per weighted layer into a parameter-shaped tuple."""
    it = iter(values)
    return tuple(next(it) if p != () else () for p in params)


def _kron_a(config, inputs):
    """Each weighted layer's input factor A = Σ_nr x xᵀ / (N R)."""
    out, mods = [], [m for m in layout(config) if m[0] in ("conv", "dense")]
    for m, h in zip(mods, inputs):
        x = _patches(h, m[1], m[4]) if m[0] == "conv" else h
        out.append(jnp.matmul(x.T, x, precision=HIGHEST) / x.shape[0])
    return out


def _kron_b(y):
    """R Σ_nr y yᵀ over one column's outputs at one layer."""
    b = y.shape[-1]
    r = y.size // (y.shape[0] * b)
    y2 = y.reshape(-1, b)
    return r * jnp.matmul(y2.T, y2, precision=HIGHEST)


def make_steps(config, traffic, precision="float32"):
    """Cached :func:`build_steps`."""
    return _once(f"steps-{precision}-{sorted(traffic.items())!r}", config,
                 lambda: build_steps(config, traffic, precision))


def build_steps(config, traffic, precision):
    """``(ext_step, plain_step)`` with the trainer's signatures, computed
    at ``precision``.

    ``ext_step(params, opt_state, batch, step_idx, rng)`` and
    ``plain_step(params, opt_state, batch, step_idx)`` return
    ``(params, opt_state, metrics)``; the extended metrics hold the loss,
    under ``"_ext"`` every quantity the traffic requests, and under
    ``"_aux"`` each leaf's gradient norm.  The optimizer is SGD, float32
    parameters.
    """
    dt, matmul = PRECISIONS[precision]
    lr = traffic["optimizer"]["lr"]
    assert traffic["optimizer"]["name"] == "sgd", traffic["optimizer"]
    wanted = set(traffic["extensions"])
    n_classes = config["n_classes"]

    def cast(t):
        return jax.tree.map(lambda a: a.astype(dt), t)

    def loss_terms(p, x, y):
        z = forward(config, p, x)
        logp = jax.nn.log_softmax(z, axis=-1)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
        return loss, z

    def sgd(params, grad):
        return jax.tree.map(
            lambda w, g: (w.astype(dt) - lr * g.astype(dt)).astype(w.dtype),
            params, grad)

    def sum_sq(tree):
        return jax.tree.map(lambda a: jnp.sum(a * a, 0), tree)

    def per_sample_sq(tree):
        return jax.tree.map(
            lambda a: jnp.sum((a * a).reshape(a.shape[0], -1), 1), tree)

    def ext(params, opt_state, batch, step_idx, rng):
        p = cast(params)
        x, y = batch["inputs"].astype(dt), batch["labels"]
        n = x.shape[0]
        loss, z = loss_terms(p, x, y)
        prob = jax.nn.softmax(z, axis=-1)
        onehot = jax.nn.one_hot(y, n_classes, dtype=dt)
        g_n = _per_sample(config, p, x, (prob - onehot) / n)
        grad = jax.tree.map(lambda a: jnp.sum(a, 0), g_n)
        sq = sum_sq(g_n)
        out = {
            "batch_grad": g_n,
            "batch_l2": per_sample_sq(g_n),
            "batch_dot": jax.tree.map(
                lambda a: jnp.matmul(a.reshape(n, -1), a.reshape(n, -1).T,
                                     precision=HIGHEST), g_n),
            "second_moment": jax.tree.map(lambda s: n * s, sq),
            "variance": jax.tree.map(lambda s, g: n * s - g * g, sq, grad)}
        if {"diag_ggn", "ggn_trace", "kflr"} & wanted:
            sp = jnp.sqrt(prob)

            def column(c):
                s_c = sp[:, c][:, None] * (jax.nn.one_hot(c, n_classes, dtype=dt)
                                        - prob) / jnp.sqrt(jnp.asarray(n, dt))
                G = _per_sample(config, p, x, s_c)
                ys = _columns_at_outputs(config, p, x, s_c)
                return sum_sq(G), per_sample_sq(G), [_kron_b(v) for v in ys]

            diags, traces, bs = jax.lax.map(column, jnp.arange(n_classes))
            out["diag_ggn"] = jax.tree.map(lambda a: jnp.sum(a, 0), diags)
            out["ggn_trace"] = jax.tree.map(lambda a: jnp.sum(a, 0), traces)
            if "kflr" in wanted:
                inputs = []
                forward(config, p, x, inputs=inputs)
                a_f = _kron_a(config, inputs)
                b_f = [jnp.sum(b, 0) for b in bs]
                out["kflr"] = _per_layer(p, [
                    {"b": {"B": b}, "w": {"A": a, "B": b}}
                    for a, b in zip(a_f, b_f)])
        if "diag_ggn_mc" in wanted:
            keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                rng, jnp.arange(n))
            yhat = jax.vmap(lambda k, zn: jax.random.categorical(
                k, zn, axis=-1, shape=(1,)))(keys, z.astype(jnp.float32))[:, 0]
            s = (prob - jax.nn.one_hot(yhat, n_classes, dtype=dt)) \
                / jnp.sqrt(jnp.asarray(n, dt))
            out["diag_ggn_mc"] = sum_sq(_per_sample(config, p, x, s))
        metrics = {"loss": loss.astype(jnp.float32), "step": step_idx + 1,
                   "_ext": {q: out[q] for q in sorted(wanted)},
                   "_aux": {"grad_norms": jax.tree.map(
                       lambda g: jnp.linalg.norm(g.astype(jnp.float32)),
                       grad)}}
        return sgd(params, grad), opt_state, metrics

    def plain(params, opt_state, batch, step_idx):
        p = cast(params)
        x, y = batch["inputs"].astype(dt), batch["labels"]
        (loss, _), grad = jax.value_and_grad(loss_terms, has_aux=True)(p, x, y)
        return sgd(params, grad), opt_state, {
            "loss": loss.astype(jnp.float32), "step": step_idx + 1}

    def at_precision(fn):
        jitted = jax.jit(fn)

        @functools.wraps(fn)
        def run(*args):
            with jax.default_matmul_precision(matmul):
                return jitted(*args)
        return run

    return at_precision(ext), at_precision(plain)


# -- shapes for the per-layer metrics ------------------------------------------


def layers(config, traffic):
    """Each weighted layer as the statistics see it: ``n`` samples, ``r``
    positions per sample, ``a`` inputs and ``b`` outputs per position
    (a convolution's input is its unfolded patch)."""
    out, side = [], config["img"]
    n = traffic["batch"]
    win, stride = config["pool_window"], config["pool_stride"]
    for m in layout(config):
        if m[0] == "conv":
            side = side - m[1] + 1 if m[4] == "VALID" else side
            out.append({"name": f"conv{len(out)}", "n": n, "r": side * side,
                        "a": m[1] * m[1] * m[2], "b": m[3], "bias": True})
        elif m[0] == "pool":
            side = (side - win) // stride + 1
        elif m[0] == "dense":
            out.append({"name": f"dense{len(out)}", "n": n, "r": 1,
                        "a": m[1], "b": m[2], "bias": True})
    return out


def step_flops(config, traffic):
    """Operations of one plain forward and backward pass over the batch:
    2·n·r·a·b per layer forward, twice that backward."""
    return sum(6 * L["n"] * L["r"] * L["a"] * L["b"]
               for L in layers(config, traffic))


def exact_columns(config, traffic):
    """Columns of the exact loss-Hessian factor per sample (classes)."""
    return config["n_classes"]

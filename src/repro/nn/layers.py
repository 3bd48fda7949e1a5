"""Extra parameter-holding modules.

* ``Conv2d`` — the paper benchmarks conv nets (3C3D, All-CNN-C, 2C2D); we
  implement convolution via im2col/unfold so every BackPACK formula reduces
  to the sequence-Dense case: per-sample gradients are sums of rank-1 terms
  over patch positions, exactly like tokens (Grosse & Martens 2016).
* ``BatchedDense`` — per-expert weights ``[E, a, b]`` for MoE; statistics are
  *token-level* (each routed token is a sample unit — per-sequence moments
  are undefined once tokens of one sequence route to different experts).
* ``Param`` — a raw learnable tensor (RWKV bonus ``u``, token-shift mixers).
  Gradients flow through Wired taps; per-sample stats are not extracted
  (documented: ≲0.01% of parameters).
* ``Buffer`` — non-trainable per-layer scalar (sliding-window sizes); kept
  in the params tree so ``lax.scan`` can vary it per layer, masked out of
  optimizer updates by the ``*_buf`` name convention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.module import (
    Axes,
    Module,
    _f32,
    dense_curv_stats,
    dense_first_order_stats,
)


class Param(Module):
    """Raw learnable tensor; ``apply`` ignores x and returns the tensor."""

    def __init__(self, shape, init=0.0, dtype=jnp.float32, axes=None):
        self.shape = tuple(shape)
        self.init_val = init
        self.dtype = dtype
        self.axes = axes or Axes((None,) * len(self.shape))

    def init(self, key):
        if callable(self.init_val):
            return {"v": self.init_val(key, self.shape).astype(self.dtype)}
        return {"v": jnp.full(self.shape, self.init_val, self.dtype)}

    def param_axes(self):
        return {"v": self.axes}

    def apply(self, params, x):
        return params["v"]

    def backward(self, params, tape, g, exts, cfg):
        return None, {"v": g.astype(params["v"].dtype)}, {}

    def jac_t_mat(self, params, tape, M):
        return None

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        return None, {}


class Buffer(Module):
    """Non-trainable scalar/array riding in the params tree (name it *_buf)."""

    def __init__(self, value, dtype=jnp.int32):
        self.value = value
        self.dtype = dtype

    def init(self, key):
        return {"v": jnp.asarray(self.value, self.dtype)}

    def param_axes(self):
        return {"v": Axes(())}

    def apply(self, params, x):
        return params["v"]

    def backward(self, params, tape, g, exts, cfg):
        return None, {"v": jnp.zeros_like(params["v"])}, {}

    def jac_t_mat(self, params, tape, M):
        return None

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        return None, {}


class BatchedDense(Module):
    """Per-expert weights: x [E, cap, a] → [E, cap, b] via W [E, a, b]."""

    def __init__(self, n_experts, d_in, d_out, dtype=jnp.float32,
                 axes=("expert", "embed", "mlp"), init_scale=None):
        self.E, self.d_in, self.d_out = n_experts, d_in, d_out
        self.dtype = dtype
        self.axes = axes
        self.init_scale = init_scale if init_scale is not None else d_in ** -0.5

    def init(self, key):
        w = jax.random.normal(key, (self.E, self.d_in, self.d_out), jnp.float32)
        return {"w": (w * self.init_scale).astype(self.dtype)}

    def param_axes(self):
        return {"w": Axes(tuple(self.axes))}

    def apply(self, params, x):
        return jnp.einsum("eca,eab->ecb", x, params["w"])

    def backward(self, params, tape, g, exts, cfg):
        x = tape
        Af, Bf = _f32(x), _f32(g)
        gw = jnp.einsum("eca,ecb->eab", Af, Bf).astype(params["w"].dtype)
        g_in = jnp.einsum("ecb,eab->eca", g, params["w"])
        stats = {}
        names = {e.name for e in exts}
        if "second_moment" in names or "variance" in names:
            # token-level (capacity slots are the sample units for experts)
            if cfg.use_kernels and cfg.use_fused:
                # Fused kernel with experts as the group axis ([E, cap, 1, d]):
                # unlike the einsum below, the squares happen in-register on
                # the way out of the MXU — A², B² are never materialized in
                # HBM — and all E experts ride one launch.  (Deliberate even
                # though the synthetic R=1 axis means no multi-stat fusion:
                # there is no batched sq_matmul kernel.)
                from repro.kernels import ops as kops

                stats["_sum_grad2"] = {"w": kops.fused_first_order(
                    Af[:, :, None, :], Bf[:, :, None, :],
                    want_l2=False, want_moment=True)["moment"]}
            else:
                stats["_sum_grad2"] = {
                    "w": jnp.einsum("eca,ecb->eab", Af ** 2, Bf ** 2)}
        if "kfac" in names or "kflr" in names:
            cap = x.shape[1]
            stats["_kron_a"] = {
                "w": jnp.einsum("eca,ecd->ead", Af, Af) / float(cap)
            }
        return g_in, {"w": gw}, stats

    def jac_t_mat(self, params, tape, M):
        return jnp.einsum("xecb,eab->xeca", M, params["w"])

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        x = tape
        names = {e.name for e in exts}
        stats = {}
        Sf = _f32(S)
        diag_name = "diag_ggn_mc" if ext_prefix == "mc" else "diag_ggn"
        kron_name = "kfac" if ext_prefix == "mc" else "kflr"
        if diag_name in names:
            stats[diag_name] = {
                "w": jnp.einsum("eca,xecb->eab", _f32(x) ** 2, Sf ** 2)
            }
        if kron_name in names:
            b_fac = jnp.einsum("xeci,xecj->eij", Sf, Sf)
            stats[kron_name] = {"w": {"B": b_fac}}
        return self.jac_t_mat(params, tape, S), stats


class Conv2d(Module):
    """NHWC conv via unfold → Dense-shaped BackPACK formulas.

    x: [N, H, W, C_in] → [N, H', W', C_out].
    """

    def __init__(self, c_in, c_out, kernel=3, stride=1, padding="SAME",
                 use_bias=True, dtype=jnp.float32):
        self.c_in, self.c_out = c_in, c_out
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else kernel
        self.stride = (stride, stride) if isinstance(stride, int) else stride
        self.padding = padding
        self.use_bias = use_bias
        self.dtype = dtype

    def init(self, key):
        kh, kw = self.kernel
        fan_in = kh * kw * self.c_in
        w = jax.random.normal(key, (fan_in, self.c_out), jnp.float32) * fan_in ** -0.5
        p = {"w": w.astype(self.dtype)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.c_out,), self.dtype)
        return p

    def param_axes(self):
        p = {"w": Axes((None, None))}
        if self.use_bias:
            p["b"] = Axes((None,))
        return p

    def _unfold(self, x):
        # lax patches util expects NCHW; returns [N, C*kh*kw, H', W']
        xt = jnp.moveaxis(x, -1, 1)
        pat = jax.lax.conv_general_dilated_patches(
            xt, self.kernel, self.stride, self.padding
        )
        n, k, hh, ww = pat.shape
        pat = pat.reshape(n, k, hh * ww)
        return jnp.moveaxis(pat, 1, 2), (hh, ww)  # [N, P, K]

    def apply(self, params, x):
        pat, (hh, ww) = self._unfold(x)
        y = pat @ params["w"]
        if self.use_bias:
            y = y + params["b"]
        return y.reshape(x.shape[0], hh, ww, self.c_out)

    def forward_tape(self, params, x):
        return self.apply(params, x), x

    def backward(self, params, tape, g, exts, cfg):
        x = tape
        pat, (hh, ww) = self._unfold(x)
        B = g.reshape(g.shape[0], hh * ww, self.c_out)
        gw = jnp.einsum("npk,npc->kc", _f32(pat), _f32(B)).astype(params["w"].dtype)
        grads = {"w": gw}
        if self.use_bias:
            grads["b"] = jnp.sum(_f32(B), axis=(0, 1)).astype(params["w"].dtype)
        # input cotangent via vjp of unfold+matmul (XLA fuses to conv-transpose)
        _, vjp = jax.vjp(lambda xx: self.apply(params, xx), x)
        g_in = vjp(g)[0]
        stats = dense_first_order_stats(pat, B, exts, cfg, self.use_bias) if exts else {}
        return g_in, grads, stats

    def jac_t_mat(self, params, tape, M):
        x = tape
        _, vjp = jax.vjp(lambda xx: self.apply(params, xx), x)
        return jax.vmap(lambda m: vjp(m)[0])(M)

    def curv_backward(self, params, tape, S, exts, cfg, ext_prefix):
        x = tape
        pat, (hh, ww) = self._unfold(x)
        c = S.shape[0]
        Sr = S.reshape(c, S.shape[1], hh * ww, self.c_out)
        stats = dense_curv_stats(pat, Sr, exts, cfg, self.use_bias, ext_prefix)
        return self.jac_t_mat(params, tape, S), stats


class MaxPool2d(Module):
    def __init__(self, size=2, stride=None):
        self.size = size
        self.stride = stride or size

    def apply(self, params, x):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            (1, self.size, self.size, 1), (1, self.stride, self.stride, 1),
            "VALID",
        )

    def jac_t_mat(self, params, tape, M):
        """Route each column of M to the first maximum of its window.

        Non-overlapping windows (stride == size) take a one-hot mask of
        each window's argmax, built once from x and broadcast over the
        leading column axis.  The generic ``vmap(vjp)`` form lowers to a
        batched ``select_and_scatter`` over a column-wide copy of x, which
        XLA on the TPU v5e returned as zeros for 3C3D's first pool when
        the exact and MC GGN sweeps shared one program (see
        ``tools/maxpool_repro.py``).  Ties go to the first element in
        row-major window order, as ``select_and_scatter`` resolves them.
        """
        if self.stride != self.size:
            return super().jac_t_mat(params, tape, M)
        x = tape
        s = self.size
        n, h, w, ch = x.shape
        ho, wo = h // s, w // s
        win = x[:, :ho * s, :wo * s].reshape(n, ho, s, wo, s, ch)
        win = jnp.moveaxis(win, 3, 2).reshape(n, ho, wo, s * s, ch)
        first = jax.nn.one_hot(jnp.argmax(win, axis=3), s * s, axis=3,
                               dtype=M.dtype)
        out = first[None] * M[:, :, :, :, None, :]
        out = out.reshape(M.shape[:4] + (s, s, ch))
        out = jnp.moveaxis(out, 4, 3).reshape(M.shape[:2] + (ho * s, wo * s, ch))
        return jnp.pad(out, ((0, 0), (0, 0), (0, h - ho * s), (0, w - wo * s),
                             (0, 0)))


class Flatten(Module):
    def apply(self, params, x):
        return x.reshape(x.shape[0], -1)

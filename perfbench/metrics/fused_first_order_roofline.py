"""Share of its roofline that ``fused_first_order`` (per-sample squared
norms for BatchL2, the summed squared gradient for SecondMoment and
Variance) reaches.

Required work per step, for each layer whose statistics take the kernel
(more than one position per sample): each wanted output over the N
per-sample gradients of a·b entries, 2·N·a·b operations apiece; its
inputs read once, N·R·(a+b) activations, and its outputs written once.
"""


def wants(extensions):
    l2 = "batch_l2" in extensions
    moment = bool({"second_moment", "variance"} & set(extensions))
    return l2, moment


def required(layer, act_bytes, l2, moment):
    n, r, a, b = layer["n"], layer["r"], layer["a"], layer["b"]
    flops = 2 * n * a * b * (l2 + moment)
    nbytes = act_bytes * n * r * (a + b) + 4 * (n * l2 + a * b * moment)
    return flops, nbytes


def read(r):
    l2, moment = wants(r.traffic["extensions"])
    if not (l2 or moment):
        return None
    t = r.kernel_s("fused_first_order")
    least = sum(r.least_s(*required(L, r.act_bytes(), l2, moment))
                for L in r.layers if L["r"] > 1)
    return 100.0 * least / t if t > 0 and least > 0 else None

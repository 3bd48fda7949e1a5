"""Share of its roofline that ``cross_dot`` (BatchDot's pairwise
per-sample gradient dots) reaches.

Required work per step, from the shapes of each layer whose statistics
take the kernel (more than one position per sample): the N² dots over
the per-sample gradients, 2·N²·a·b operations, and its inputs read once
and its output written once, N·R·(a+b) activations and N² float32.  The
least time is the larger of operations over peak FLOP/s and bytes over
peak bandwidth; the share is that over the kernel's device time.
"""


def required(layer, act_bytes):
    n, r, a, b = layer["n"], layer["r"], layer["a"], layer["b"]
    flops = 2 * n * n * a * b
    nbytes = act_bytes * n * r * (a + b) + 4 * n * n
    return flops, nbytes


def read(r):
    if "batch_dot" not in r.traffic["extensions"]:
        return None
    t = r.kernel_s("cross_dot")
    least = sum(r.least_s(*required(L, r.act_bytes()))
                for L in r.layers if L["r"] > 1)
    return 100.0 * least / t if t > 0 and least > 0 else None

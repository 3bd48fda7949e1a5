"""Share of its roofline that ``fused_second_order`` (GGN diagonal,
Kronecker B factor and GGN trace from the back-propagated factor S)
reaches.

Required work per step, for each curvature sweep and each layer whose
statistics take the kernel (more than one position per sample), with C
columns per sample (the classes in the exact sweep, ``mc_samples`` in
the Monte-Carlo one): the diagonal and the trace over the C·N
per-sample-per-column gradients of a·b entries, 2·C·N·a·b operations
apiece; the B factor SᵀS, 2·C·N·R·b² operations; the inputs read once,
N·R·a + C·N·R·b activations, and the outputs written once.
"""

EXACT = {"diag_ggn": "diag", "kflr": "kron", "ggn_trace": "trace"}
MC = {"diag_ggn_mc": "diag", "kfac": "kron"}


def sweeps(extensions, exact_columns, mc_samples):
    """``[(C, {"diag", "kron", "trace"} wanted)]`` per curvature sweep."""
    out = []
    for table, c in ((EXACT, exact_columns), (MC, mc_samples)):
        wanted = {table[e] for e in extensions if e in table}
        if wanted:
            out.append((c, wanted))
    return out


def required(layer, act_bytes, c, wanted):
    n, r, a, b = layer["n"], layer["r"], layer["a"], layer["b"]
    per_col = ("diag" in wanted) + ("trace" in wanted)
    flops = 2 * c * n * a * b * per_col
    flops += 2 * c * n * r * b * b * ("kron" in wanted)
    nbytes = act_bytes * (n * r * a + c * n * r * b)
    nbytes += 4 * (a * b * ("diag" in wanted) + b * b * ("kron" in wanted)
                   + n * ("trace" in wanted))
    return flops, nbytes


def read(r):
    mc = r.traffic.get("ext_config", {}).get("mc_samples", 1)
    todo = sweeps(r.traffic["extensions"], r.exact_columns, mc)
    if not todo:
        return None
    t = r.kernel_s("fused_second_order")
    least = sum(r.least_s(*required(L, r.act_bytes(), c, wanted))
                for c, wanted in todo for L in r.layers if L["r"] > 1)
    return 100.0 * least / t if t > 0 and least > 0 else None

"""Device time per extended step under ``ggn_exact_sweep`` and
``ggn_mc_sweep`` together (the curvature sweeps)."""


def read(r):
    s = r.scope_s("ggn_exact_sweep", "ggn_mc_sweep")
    return 1e3 * s if s > 0 else None

"""Device time per extended step under the engine's ``first_order_sweep``
named scope (the gradient sweep and every first-order statistic)."""


def read(r):
    s = r.scope_s("first_order_sweep")
    return 1e3 * s if s > 0 else None

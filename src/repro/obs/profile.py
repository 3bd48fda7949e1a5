"""``jax.profiler`` integration.

``obs.profile(dir)`` wraps ``jax.profiler.start_trace`` / ``stop_trace``
as a context manager (no-op when ``dir`` is falsy), so a device trace can
be captured around any region.  Device ops carry the program's
``jax.named_scope`` names (the sweeps, ``rule.<Module>``,
``kernel.<name>``); recording obs spans are profiler TraceMes, so they
land on the Python thread's line of the same trace, on the same clock.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Iterator, Optional

__all__ = ["profile"]


@contextmanager
def profile(trace_dir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` device trace into ``trace_dir``.

    A falsy ``trace_dir`` makes this a no-op, so call sites can pass a CLI
    flag straight through.  The host-side span is recorded too, so the obs
    trace shows exactly which wall-clock window the device trace covers.
    """
    if not trace_dir:
        yield
        return
    import jax.profiler  # deferred: keep repro.obs import-light

    from repro.obs import registry as _reg

    jax.profiler.start_trace(trace_dir)
    try:
        with _reg.span("obs/profile", dir=str(trace_dir)):
            yield
    finally:
        jax.profiler.stop_trace()

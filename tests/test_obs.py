"""Observability layer: span nesting, JSONL round-trip, disabled no-op
identity, compile events, the cache hit/miss stats, padding-waste
accounting, the trainer's step spans on the profiler's clock, and the
streaming-sweep trace acceptance check (per-slice span count matches the
described schedule; the offline renderer agrees with the in-memory
report)."""
import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (
    Activation,
    CrossEntropyLoss,
    Dense,
    ExtensionConfig,
    Sequential,
    by_name,
    plan_sweeps,
)
from repro.kernels import ops
from repro.obs import NullRegistry, ObsRegistry
from repro.obs.reporting import load_jsonl, render

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends with the disabled module registry."""
    obs.disable()
    yield
    obs.disable()


def _tiny_problem(n=8, d=4, h=6, c=3, seed=0):
    model = Sequential([Dense(d, h), Activation("sigmoid"), Dense(h, c)])
    params = model.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, d))
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (n,), 0, c)
    return model, params, x, y, CrossEntropyLoss()


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------


def test_span_nesting_paths_and_attrs():
    reg = ObsRegistry()
    with obs.use(reg):
        with obs.span("outer", n=2):
            with obs.span("inner", bytes=128) as sp:
                sp.set(rows=7)
            with obs.span("inner"):
                pass
    spans = [e for e in reg.events if e["kind"] == "span"]
    assert [tuple(e["path"]) for e in spans] == [
        ("outer", "inner"), ("outer", "inner"), ("outer",)]
    assert spans[0]["attrs"] == {"bytes": 128, "rows": 7}
    assert spans[2]["attrs"] == {"n": 2}
    assert all(e["dur_s"] >= 0.0 for e in spans)
    # children accounted inside the parent's duration
    assert spans[2]["dur_s"] >= spans[0]["dur_s"] + spans[1]["dur_s"]


def test_counters_and_gauges():
    reg = ObsRegistry()
    with obs.use(reg):
        obs.count("steps")
        obs.count("steps", 4)
        obs.gauge("cursor", 3)
        obs.gauge("cursor", 9)
    assert reg.counters == {"steps": 5}
    assert reg.gauges == {"cursor": 9}


def test_use_restores_previous_registry_on_error():
    before = obs.get()
    with pytest.raises(RuntimeError):
        with obs.use(ObsRegistry()):
            assert obs.enabled()
            raise RuntimeError("boom")
    assert obs.get() is before
    assert not obs.enabled()


def test_enable_disable_module_registry():
    assert not obs.enabled()
    obs.enable()
    assert obs.enabled()
    obs.count("x")
    assert obs.get().counters == {"x": 1}
    obs.disable()
    assert isinstance(obs.get(), NullRegistry)


def test_jsonl_round_trip(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    reg = ObsRegistry(trace_jsonl=trace)
    with obs.use(reg):
        with obs.span("work", n=np.int64(3), frac=np.float32(0.5),
                      tag="slice"):
            obs.count("calls", 2)
        obs.gauge("cursor", 1)
    reg.close()
    events = load_jsonl(trace)
    assert events == list(reg.events)
    # every attr value landed as a JSON primitive, not a numpy repr
    for line in open(trace):
        for v in json.loads(line).get("attrs", {}).values():
            assert isinstance(v, (int, float, str, bool))


def test_load_jsonl_tolerates_torn_tail(tmp_path):
    trace = tmp_path / "trace.jsonl"
    trace.write_text(
        json.dumps({"kind": "count", "name": "a", "value": 1}) + "\n"
        + '{"kind": "span", "name": "tru')
    events = load_jsonl(str(trace))
    assert len(events) == 1 and events[0]["name"] == "a"


# ---------------------------------------------------------------------------
# disabled path: a no-op, and numerically invisible
# ---------------------------------------------------------------------------


def test_null_registry_is_shared_singleton_noop():
    s1 = obs.span("a", n=1)
    s2 = obs.span("b")
    assert s1 is s2  # one preallocated null span, no per-call allocation
    with s1 as sp:
        sp.set(bytes=4)
    assert obs.get().events == ()
    assert obs.get().counters == {}
    assert "disabled" in obs.report()


def test_disabled_and_enabled_sweeps_agree():
    model, params, x, y, loss = _tiny_problem()
    exts = tuple(by_name(nm) for nm in ("batch_l2", "variance", "diag_ggn"))
    cfg = ExtensionConfig(use_kernels=True)
    plan = plan_sweeps(exts, cfg)
    off = plan.run(model, params, x, y, loss, cfg=cfg)
    reg = ObsRegistry()
    with obs.use(reg):
        on = plan.run(model, params, x, y, loss, cfg=cfg)
    assert len(reg.events) > 0  # instrumentation did record
    np.testing.assert_allclose(off.loss, on.loss)
    jax.tree.map(np.testing.assert_array_equal, off.ext, on.ext)


# ---------------------------------------------------------------------------
# kernel dispatch telemetry
# ---------------------------------------------------------------------------


def _compiles(reg):
    return [e for e in reg.events if e["kind"] == "compile"]


def test_cache_hit_miss_counters():
    """The dispatch cache's hits and misses live in ``cache_stats()``; the
    registry sees the one compile the miss causes, and none on the hit."""
    ops.clear_cache()
    A = jnp.ones((4, 5, 3))
    B = jnp.ones((4, 5, 2))
    reg = ObsRegistry()
    with obs.use(reg):
        ops.batch_l2(A, B)
        first = len(_compiles(reg))
        ops.batch_l2(A, B)
    stats = ops.cache_stats()
    assert stats["misses"]["batch_l2"] == 1
    assert stats["hits"]["batch_l2"] == 1
    assert isinstance(stats["total"], int)  # legacy shape preserved
    assert first >= 1 and len(_compiles(reg)) == first
    assert reg.counters["jax.compiles"] == first
    assert not any(k.startswith(("kernel.calls", "kernel.cache_"))
                   for k in reg.counters)


def test_padding_waste_matches_hand_computed_bytes():
    ops.clear_cache()
    # batch_l2 pads axis 1 (R) of both operands up to block_r: R=5 with
    # block_r=8 zero-fills 3 rows of [a]/[b] float32 per sample
    N, R, a, b = 4, 5, 3, 2
    A = jnp.ones((N, R, a), jnp.float32)
    B = jnp.ones((N, R, b), jnp.float32)
    pad = (-R) % 8
    expected = pad * N * a * 4 + pad * N * b * 4
    reg = ObsRegistry()
    with obs.use(reg):
        ops.batch_l2(A, B, block_r=8)
        ops.batch_l2(A, B, block_r=8)  # cached shapes: waste replayed
    assert reg.counters["kernel.padding_waste_bytes.batch_l2"] == 2 * expected


def test_dispatch_records_at_trace_time_not_per_eval():
    """Inside jit, dispatch runs once at trace time and the program compiles
    once: steady-state calls of the jitted wrapper fire no compile event."""
    ops.clear_cache()
    A = jnp.ones((4, 5, 3))
    B = jnp.ones((4, 5, 2))
    fn = jax.jit(lambda A, B: ops.batch_l2(A, B))
    reg = ObsRegistry()
    with obs.use(reg):
        jax.block_until_ready(fn(A, B))
        traced = len(_compiles(reg))
        for _ in range(2):
            jax.block_until_ready(fn(A, B))
    assert traced >= 1 and len(_compiles(reg)) == traced  # cached calls
    assert ops.cache_stats()["misses"]["batch_l2"] == 1
    with obs.use(reg):
        ops.batch_l2(A, B)  # eager: the kernel's own jit compiles once
        eager = len(_compiles(reg))
        ops.batch_l2(A, B)
    assert eager > traced and len(_compiles(reg)) == eager


# ---------------------------------------------------------------------------
# acceptance: streaming sweep trace matches the described schedule
# ---------------------------------------------------------------------------


def test_stream_trace_matches_schedule_and_renders(tmp_path):
    model, params, x, y, loss = _tiny_problem(n=8)
    exts = tuple(by_name(nm) for nm in ("batch_l2", "variance"))
    cfg = ExtensionConfig(use_kernels=True)
    stream = plan_sweeps(exts, cfg).accumulate(4).stream(
        model, params, x, y, loss, cfg=cfg)
    trace = str(tmp_path / "trace.jsonl")
    reg = ObsRegistry(trace_jsonl=trace)
    with obs.use(reg):
        while not stream.done:
            stream.step()
        res = stream.result()
    reg.close()
    assert np.isfinite(float(res.loss))
    assert f"stream: {stream.n_slices} slice" in stream.describe()

    events = load_jsonl(trace)
    slices = [e for e in events
              if e["kind"] == "span" and e["name"] == "engine/stream/slice"]
    assert len(slices) == stream.n_slices == len(stream.units)
    assert [e["attrs"]["t"] for e in slices] == list(range(stream.n_slices))
    # finalize spans: one per reducer-carried extension (variance); the
    # row-concat extension (batch_l2) has no finalize step by design
    finals = [e for e in events
              if e["kind"] == "span" and e["name"] == "engine/finalize"]
    assert sorted(e["attrs"]["ext"] for e in finals) == \
        sorted(stream.carry_names)
    assert reg.gauges["engine.stream.cursor"] == len(stream.units)

    # offline renderer and the in-memory report agree on the same trace
    report = render(events)
    assert "engine/stream/slice" in report
    assert f"{stream.n_slices:>6d}" in report
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"), trace],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == report.strip()


def test_report_renders_tree_counters_gauges():
    events = [
        {"kind": "span", "name": "a", "path": ["a"], "dur_s": 0.25,
         "attrs": {"bytes": 100, "step": 7}},
        {"kind": "span", "name": "b", "path": ["a", "b"], "dur_s": 0.1,
         "attrs": {"bytes": 40}},
        {"kind": "span", "name": "b", "path": ["a", "b"], "dur_s": 0.1,
         "attrs": {"bytes": 2}},
        {"kind": "count", "name": "calls", "value": 3},
        {"kind": "gauge", "name": "cursor", "value": 5},
    ]
    out = render(events)
    lines = out.splitlines()
    (a_line,) = [ln for ln in lines if ln.startswith("a ")]
    (b_line,) = [ln for ln in lines if ln.lstrip().startswith("b ")]
    assert lines.index(b_line) > lines.index(a_line)  # child under parent
    assert "bytes=42" in b_line
    assert "step=" not in a_line  # identifiers are not summed
    assert "calls = 3" in out and "cursor = 5" in out
    assert render([]) == "no events recorded"


# ---------------------------------------------------------------------------
# compile events and the trainer's step spans
# ---------------------------------------------------------------------------


def test_compile_events_count_backend_compiles_under_span():
    """A jitted function called under a span compiles once: ``jax.compiles``
    rises by the backend compiles of the first call and by 0 on the
    second, and each ``compile`` event carries the span's path."""
    fn = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.arange(7.0)
    reg = ObsRegistry()
    with obs.use(reg):
        with obs.span("outer"), obs.span("call", k=1):
            jax.block_until_ready(fn(x))
        first = reg.counters.get("jax.compiles", 0)
        with obs.span("outer"), obs.span("call", k=2):
            jax.block_until_ready(fn(x))
    events = _compiles(reg)
    assert first >= 1 and reg.counters["jax.compiles"] == first
    assert len(events) == first
    assert all(e["path"] == ["outer", "call"] for e in events)
    assert any("<lambda>" in e["name"] for e in events)
    assert reg.counters["jax.compile_s"] == pytest.approx(
        sum(e["dur_s"] for e in events), rel=1e-6)
    report = render(reg.events)
    assert "compiles (under span path)" in report
    assert "outer/call: " in report


def test_compile_listener_is_silent_when_disabled(monkeypatch):
    """Compiles after the switch back to NullRegistry reach no registry:
    the recording registry's count stays where it was, and no registry's
    ``compiled`` is called."""
    reg = ObsRegistry()  # registers the process-wide listener
    with obs.use(reg):
        jax.block_until_ready(jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(3)))
    before = reg.counters["jax.compiles"]
    assert before >= 1 and isinstance(obs.get(), NullRegistry)
    calls = []
    monkeypatch.setattr(ObsRegistry, "compiled",
                        lambda self, *a: calls.append(a))
    jax.block_until_ready(jax.jit(lambda x: x * 7.0 + 4.0)(jnp.ones(5)))
    assert calls == []
    assert reg.counters["jax.compiles"] == before


def test_run_step_syncs_reads_metrics_and_counts_outputs():
    """``run_step`` times dispatch and sync and reads the metrics as host
    floats; ``fit`` sets the output-buffer gauge once, on its first step."""
    from repro.configs import ARCHS, SHAPES
    from repro.nn.models import build_model
    from repro.optim import adamw
    from repro.train.loop import LoopConfig, fit, run_step

    def step(p, s, x):
        return p + x, {"n": s["n"] + 1}, {"loss": jnp.sum(x), "acc": x[0]}

    reg = ObsRegistry()
    with obs.use(reg):
        p, s, metrics = run_step(jax.jit(step), jnp.ones(3), {"n": 0},
                                 jnp.arange(3.0))
    assert metrics == {"loss": 3.0, "acc": 0.0}
    assert all(type(v) is float for v in metrics.values())
    np.testing.assert_array_equal(p, [1.0, 2.0, 3.0])
    assert int(s["n"]) == 1
    assert [e["name"] for e in reg.events if e["kind"] == "span"] == \
        ["train/dispatch", "train/sync"]
    assert reg.gauges == {}

    cfg = ARCHS["stablelm-1.6b"].reduced()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=8,
                                global_batch=2)
    reg = ObsRegistry()
    with obs.use(reg):
        params, opt_state, _, _ = fit(build_model(cfg), cfg, shape,
                                      adamw(1e-3),
                                      LoopConfig(steps=2, log_every=1000))
    n_out = len(jax.tree.leaves((params, opt_state))) + 2  # loss, step
    assert reg.gauges["train.step_outputs"] == n_out
    assert [e["name"] for e in reg.events if e["kind"] == "gauge"] == \
        ["train.step_outputs"]


def _fit_python_line(tmp_path, enable):
    """Two ``fit`` steps of a small LM under ``obs.profile``; the events
    of the xplane's Python-thread line as ``(name, start, end)``."""
    from jax.profiler import ProfileData

    from repro.configs import ARCHS, SHAPES
    from repro.nn.models import build_model
    from repro.optim import adamw
    from repro.train.loop import LoopConfig, fit

    cfg = ARCHS["stablelm-1.6b"].reduced()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=8,
                                global_batch=2)
    if enable:
        obs.enable()
    with obs.profile(str(tmp_path)):
        fit(build_model(cfg), cfg, shape, adamw(1e-3),
            LoopConfig(steps=2, log_every=1000))
    obs.disable()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    line = next(ln for ln in host.lines if ln.name.startswith("python"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def test_fit_step_spans_on_the_profiler_clock(tmp_path):
    events = _fit_python_line(tmp_path, enable=True)
    steps = sorted(e for e in events if e[0] == "train/step")
    assert len(steps) == 2
    for name in ("train/data", "train/dispatch", "train/sync"):
        inner = sorted(e for e in events if e[0] == name)
        assert len(inner) == 2, name
        for (_, s, t), (_, s0, t0) in zip(inner, steps):
            assert s0 <= s <= t <= t0, name


def test_fit_step_spans_absent_when_disabled(tmp_path):
    names = {e[0] for e in _fit_python_line(tmp_path, enable=False)}
    assert not names & {"train/step", "train/data", "train/dispatch",
                        "train/sync"}

"""The reduction from a trace to the per-layer metrics, on a synthetic
trace and on one recorded on the CPU."""
import shutil
import tempfile

import pytest

from bench import harness, profile
from bench.tests.conftest import CPU_DEVICE


def _synthetic():
    ops = [[(100.0, 200.0, "%a"), (150.0, 300.0, "%b"),
            (600.0, 700.0, "%a")]]
    modules = [[(100.0, 300.0, "jit_sweep(1)"), (600.0, 700.0, "jit_other")]]
    spans = [(0.0, 1000.0, "bench.window"),
             (0.0, 40.0, "bench.inputs"), (50.0, 400.0, "bench.call"),
             (60.0, 390.0, "bench.surface"),
             (400.0, 540.0, "bench.inputs"), (550.0, 800.0, "bench.call")]
    return profile.from_intervals(ops, modules, spans)


def _run(trace, kind="TPU v5 lite", name="video.fast"):
    cell = harness.load_cell(name)
    return harness.Run(cell=cell, device=dict(CPU_DEVICE, kind=kind),
                       setup_s=1.0, calls=[], trace=trace)


def test_busy_idle_and_host_time():
    trace = _synthetic()
    assert profile.busy_ns(trace) == 300.0           # [100,300] + [600,700]
    assert profile.busy_ns(trace, 150.0, 650.0) == 200.0
    run = _run(trace)
    assert harness.reader("device_idle_share")(run) == pytest.approx(70.0)
    # calls: 350 ns with 200 busy, 250 ns with 100 busy
    assert harness.reader("host_ms")(run) == pytest.approx(150.0 * 1e-6)
    # one sweep run of 200 ns over two calls
    assert harness.reader("sweep_device_ms")(run) == pytest.approx(1e-4)


def test_breakdown_names_ops_and_gaps():
    out = profile.breakdown(_synthetic())
    assert [n for n, _ in out["device_ops"]] == ["%a", "%b"]
    assert [s for _, s in out["device_ops"]] == pytest.approx([200e-9,
                                                               150e-9])
    gaps = out["idle_gaps"]
    # gaps: [0,100] (around 50: inside the call), [300,600] (around
    # 450: drawing inputs), [700,1000] (around 850: between spans)
    assert sorted(g[1] for g in gaps) == pytest.approx([100e-9, 300e-9,
                                                        300e-9])
    names = {round(g[1] * 1e9): g[0] for g in gaps}
    assert names[100] == "bench.call"
    assert {g[0] for g in gaps if round(g[1] * 1e9) == 300} == {
        "bench.inputs", "bench.window"}


def test_sweep_roofline_counts_what_the_result_needs():
    run = _run(_synthetic())
    mix, config = run.cell.mix, run.cell.config
    c, n = mix["candidates"]["count"], mix["arrivals"]["count"]
    v, e = len(config["functions"]), len(config["edges"])
    roof = harness.reader("sweep_roofline")
    least = 8 * (n + c * v + e + c * n) / 819e9
    assert roof(run) == pytest.approx(least / 200e-9 * 100.0)
    # video.fast: 4,096 arrivals, 8 x 6 runtimes, 7 edges and 8 x 4,096
    # finishes, at 8 bytes, in one second of sweep
    one_second = profile.from_intervals(
        [[(0.0, 1e9, "%while")]], [[(0.0, 1e9, "jit_sweep(7)")]],
        [(0.0, 2e9, "bench.window"), (0.0, 1.5e9, "bench.call")])
    assert roof(_run(one_second)) == pytest.approx(295_352 / 819e9 * 100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.reader("sweep_roofline")(_run(_synthetic(), kind="TPU v99"))


def test_nothing_to_read_gives_no_metric():
    trace = profile.from_intervals([], [], [(0.0, 10.0, "bench.window")])
    run = _run(trace)
    assert harness.reader("sweep_device_ms")(run) is None
    assert harness.reader("sweep_roofline")(run) is None
    assert harness.reader("host_ms")(run) is None
    assert harness.reader("device_idle_share")(run) == 100.0
    assert harness.reader("host_ms")(_run(None)) is None


def test_reads_a_recorded_cpu_trace():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(profile.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
        jax.profiler.stop_trace()
        trace = profile.read(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    calls = profile.spans_named(trace, "bench.call")
    assert len(calls) == 3
    lo, hi = trace.window
    assert all(lo <= a <= b <= hi for a, b, _ in calls)
    assert trace.ops == []            # the CPU has no device plane
    assert profile.busy_ns(trace) == 0.0

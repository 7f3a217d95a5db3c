"""The ``montage`` configuration and its cell ``montage.fast``: the
workflow's shape against the source's, the file against its generator,
the program against the reference at the workflow's full width with a
tiny cut of the traffic, the precision control, and the sweep's blocks
at this fan-in (CPU)."""
import importlib.util

import numpy as np

from bench import control, generate, harness, reference
from bench.tests.conftest import CPU_DEVICE, ROOT, tiny

CELL = "montage.fast"


def _generator():
    path = ROOT / "scripts" / "make_montage_config.py"
    spec = importlib.util.spec_from_file_location("make_montage_config",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ranks(graph: reference.Graph):
    rank = {}
    for v in graph.topo:
        rank[v] = 1 + max((rank[u] for u in graph.preds[v]), default=-1)
    return rank


def test_shape_is_the_sources():
    """Per band 256 projections, 930 overlap fits (a cell and its 8
    neighbours), one global fit, one background model, 256 corrections,
    one table, one co-add and one shrink; three bands into one JPEG."""
    config = harness.load_cell(CELL).config
    graph = reference.Graph.of(config)
    assert len(graph.names) == 3 * (256 + 930 + 1 + 1 + 256 + 3) + 1 == 4342
    assert len(config["edges"]) == 3 * (2 * 930 + 930 + 1 + 2 * 256 + 256
                                        + 2) + 3 == 10686
    indegree = [len(p) for p in graph.preds]
    assert max(indegree) == 930
    assert sorted(set(indegree)) == [0, 1, 2, 3, 256, 930]
    assert len(graph.sources) == 3 * 256
    assert [graph.names[v] for v in range(len(graph.names))
            if not graph.succs[v]] == ["mJPEG"]
    assert max(_ranks(graph).values()) + 1 == 9
    assert config["reduced"] == [] and config["precision"] == "float64"


def test_file_is_the_generators_output():
    assert (ROOT / "bench" / "configs" / "montage.json").read_text() \
        == _generator().render()


def test_incumbent_meets_the_slo_and_challengers_can_miss_it():
    """The incumbent sits within 5% under the SLO. A challenger the
    traffic can draw, one band's ``mAdd`` (a serial task on the critical
    path) 1 vCPU smaller, misses it; the drawn challengers of a call,
    which mostly move short parallel tasks, meet it."""
    cell = harness.load_cell(CELL)
    graph = reference.Graph.of(cell.config)
    inputs = generate.draw_call(cell.mix, cell.config, 2**40 + 11, 1)
    inputs.arrivals = inputs.arrivals[:8]
    slower = inputs.cpu[0].copy()
    slower[graph.names.index("mAdd_H")] -= cell.mix["candidates"]["cpu_delta"]
    inputs.cpu = np.vstack([inputs.cpu, slower])
    inputs.mem = np.vstack([inputs.mem, inputs.mem[0]])
    answers = harness.want(cell, graph, inputs)
    lat = answers[0].latency
    slo = cell.config["slo_s"]
    assert answers[0].hits == lat.size
    assert 0.95 * slo <= lat.max() <= slo
    assert answers[-1].hits == 0
    assert sum(a.hits == lat.size for a in answers[1:-1]) >= 5


def test_program_agrees_with_reference():
    cell = tiny(CELL)
    system = harness.System(cell)
    assert system.plane == ("fast", [])
    graph = reference.Graph.of(cell.config)
    for call in (1, 2):
        inputs = generate.draw_call(cell.mix, cell.config, 2**40 + 3, call)
        got = harness.answers(
            system.call(system.configs(inputs), inputs.arrivals), system.slo)
        numbers = harness.compare(got, harness.want(cell, graph, inputs))
        assert numbers["time_rel"] <= 1e-12, numbers
        assert numbers["cost_rel"] == 0.0 and numbers["mismatch"] == 0


def test_float32_control_is_not_correct():
    cell = tiny(CELL, instances=64)
    system = harness.System(cell)
    calls, sampled, failed = harness.window(
        cell, system, 2**34 + 9, 0.3, np.random.default_rng(0))
    assert calls and not failed
    limits = cell.config["check_limits"]
    assert all(v <= limits[k]
               for k, v in harness.check(cell, sampled).items())
    assert any(v > limits[k]
               for k, v in control.control_reading(cell, sampled).items())


def test_result_line_of_the_cell():
    out = harness.run_cell(tiny(CELL, instances=16), 2**35 + 7, 0.3, False,
                           dict(CPU_DEVICE), t_start=0.0)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"replay_rate", "setup_s",
                                   "validate_p95_ms"}


def test_sweep_gathers_fan_in_930_in_blocks_of_edges():
    """Nine steps, one per rank; the 2,790 overlap fits and the fits of
    in-degree 930 are cut into blocks of at most 1,024 slots, and every
    slot is an edge. The finishes are the numpy sweep's, bit for bit,
    on the CPU."""
    from repro.core import telemetry
    from repro.core.engine import FleetEngine

    cell = tiny(CELL)
    system = harness.System(cell)
    inputs = generate.draw_call(cell.mix, cell.config, 2**36 + 1, 1)
    configs = system.configs(inputs)
    before = telemetry.counters()
    got = system.call(configs, inputs.arrivals)
    after = telemetry.counters()
    # blocks: overlap fits 6, global fits 3, background 2, and one for
    # each of the five narrow ranks
    assert {k: after[k] - before.get(k, 0) for k in (
        "fleet.sweep.steps", "fleet.sweep.blocks", "fleet.sweep.slots",
        "fleet.sweep.edges")} == {
        "fleet.sweep.steps": 9, "fleet.sweep.blocks": 6 + 3 + 2 + 5,
        "fleet.sweep.slots": 10686, "fleet.sweep.edges": 10686}
    numpy_plane = FleetEngine(system.backend, pricing=system.engine.pricing)
    want = numpy_plane.run_many(system.template, configs, [inputs.arrivals])
    for g, w in zip(got, want):
        assert g.finishes.tobytes() == w.finishes.tobytes()
        assert g.latencies.tobytes() == w.latencies.tobytes()


def test_sweep_ops_counts_device_ops_per_sweep_run():
    """Ops on the first device that start inside a ``jit_sweep`` run in
    the window, over the runs: 3 and 1 here, so 2.0; ops of another
    program, of another device, and runs outside the window are not
    counted. A trace with no sweep run gives no metric."""
    from bench import profile

    ops = [[(100.0, 120.0, "%gather"), (120.0, 150.0, "%reduce"),
            (150.0, 200.0, "%dus"), (600.0, 700.0, "%gather"),
            (800.0, 850.0, "%other"), (1200.0, 1300.0, "%gather")],
           [(100.0, 200.0, "%gather"), (110.0, 190.0, "%reduce")]]
    modules = [[(100.0, 200.0, "jit_sweep(3)"), (600.0, 700.0, "jit_sweep(3)"),
                (800.0, 850.0, "jit_other"), (1200.0, 1300.0, "jit_sweep(3)")],
               [(100.0, 200.0, "jit_sweep(3)")]]
    spans = [(0.0, 1000.0, "bench.window"), (50.0, 400.0, "bench.call"),
             (550.0, 900.0, "bench.call")]
    read = harness.reader("sweep_ops")

    def run(trace):
        return harness.Run(cell=harness.load_cell(CELL),
                           device=dict(CPU_DEVICE, kind="TPU v5 lite"),
                           setup_s=1.0, calls=[], trace=trace)

    assert read(run(profile.from_intervals(ops, modules, spans))) == 2.0
    assert read(run(profile.from_intervals(
        [ops[0]], [[modules[0][2]]], spans))) is None
    assert read(run(profile.from_intervals([], [], spans))) is None
    assert read(run(None)) is None

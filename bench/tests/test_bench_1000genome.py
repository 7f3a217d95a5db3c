"""The ``1000genome`` configuration and its cell ``genome.fast``: the
workflow's shape against the source's, the file against its generator,
the program against the reference at a tiny size of the traffic, and
the sweep and busy ledger at the workflow's width (CPU)."""
import importlib.util

import numpy as np
import pytest

from bench import control, generate, harness, reference
from bench.tests.conftest import CPU_DEVICE, ROOT, tiny

CELL = "genome.fast"


def _generator():
    path = ROOT / "scripts" / "make_1000genome_config.py"
    spec = importlib.util.spec_from_file_location("make_1000genome_config",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _components(graph: reference.Graph) -> int:
    parent = list(range(len(graph.names)))

    def root(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for v, preds in enumerate(graph.preds):
        for u in preds:
            parent[root(u)] = root(v)
    return len({root(v) for v in range(len(parent))})


def test_shape_is_the_sources():
    """22 autosomes of 10 individuals shards, a merge, sifting, and a
    mutation_overlap and a frequency for each of 7 populations."""
    config = harness.load_cell(CELL).config
    graph = reference.Graph.of(config)
    assert len(graph.names) == 22 * 26 == 572
    assert len(config["edges"]) == 22 * 38 == 836
    assert len(graph.sources) == 242
    assert sum(not s for s in graph.succs) == 308
    indegree = [len(p) for p in graph.preds]
    assert sorted(set(indegree)) == [0, 2, 10]
    assert all(indegree[graph.names.index(f"individuals_merge_chr{c}")]
               == 10 for c in range(1, 23))
    assert _components(graph) == 22
    assert config["reduced"] == [] and config["precision"] == "float64"


def test_file_is_the_generators_output():
    assert (ROOT / "bench" / "configs" / "1000genome.json").read_text() \
        == _generator().render()


def test_incumbent_meets_the_slo_and_challengers_can_miss_it():
    """The incumbent sits within 5% under the SLO; at the traffic's own
    sizes some challengers of a few calls miss it, so ``mismatch``
    compares answers that differ."""
    cell = harness.load_cell(CELL)
    graph = reference.Graph.of(cell.config)
    met, missed = 0, 0
    for call in (1, 2, 3):
        inputs = generate.draw_call(cell.mix, cell.config, 2**40 + 11, call)
        inputs.arrivals = inputs.arrivals[:16]
        answers = harness.want(cell, graph, inputs)
        lat = answers[0].latency
        slo = cell.config["slo_s"]
        assert answers[0].hits == lat.size
        assert 0.95 * slo <= lat.max() <= slo
        for a in answers[1:]:
            met += a.hits == lat.size
            missed += a.hits == 0
    assert met and missed and met + missed == 3 * 7


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


def test_sweep_is_three_ranks_that_gather_only_edges():
    """The jitted sweep takes one step per rank (the shards and sifting,
    the merges, the population tasks) and gathers 10 slots for each
    merge and 2 for each population task: every slot an edge. Its
    finishes are the numpy sweep's, bit for bit, on the CPU."""
    from repro.core import telemetry
    from repro.core.engine import FleetEngine

    cell = tiny(CELL)
    system = harness.System(cell)
    inputs = generate.draw_call(cell.mix, cell.config, 2**36 + 1, 1)
    configs = system.configs(inputs)
    before = telemetry.counters()
    got = system.call(configs, inputs.arrivals)
    after = telemetry.counters()
    assert {k: after[k] - before.get(k, 0) for k in (
        "fleet.sweep.steps", "fleet.sweep.slots", "fleet.sweep.edges")} \
        == {"fleet.sweep.steps": 3, "fleet.sweep.slots": 836,
            "fleet.sweep.edges": 836}
    numpy_plane = FleetEngine(system.backend, pricing=system.engine.pricing)
    want = numpy_plane.run_many(system.template, configs, [inputs.arrivals])
    for g, w in zip(got, want):
        assert g.finishes.tobytes() == w.finishes.tobytes()
        assert g.latencies.tobytes() == w.latencies.tobytes()


def test_busy_ledger_is_each_runtime_added_once_per_instance():
    """Each function's busy time is its runtime added m times from 0.0,
    left to right, as the scalar event loop adds it, at this width."""
    cell = tiny(CELL, instances=40)
    system = harness.System(cell)
    inputs = generate.draw_call(cell.mix, cell.config, 2**37 + 5, 1)
    configs = system.configs(inputs)
    reports = system.call(configs, inputs.arrivals)
    nodes, _, cpu, mem = system.engine._candidate_arrays(system.template,
                                                         configs)
    runtime, _ = system.backend.invoke_config_batch(nodes, cpu, mem)
    m = inputs.arrivals.size
    for c, report in enumerate(reports):
        want = {}
        for v, node in enumerate(nodes):
            acc = 0.0
            for _ in range(m):
                acc += float(runtime[c, v])
            want[f"{system.template.identity}/{node.name}"] = acc
        assert {k: x.hex() for k, x in report.busy_by_function.items()} \
            == {k: x.hex() for k, x in want.items()}

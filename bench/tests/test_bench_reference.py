"""The plain reference against hand-worked cases, and against the
program at a tiny size of each cell's traffic."""
import numpy as np
import pytest

from bench import generate, harness, reference
from bench.tests.conftest import cell_of, tiny

#: the benchmark's cell and the capacity mix kept for a later one
CELLS = ["video.fast", "video.capacity"]


def _chain(n_fn=2):
    """A chain of ``n_fn`` functions, each 10 s of serial work at 1 vCPU
    and no I/O, above its memory knee."""
    fns = [{"name": f"f{i}", "cpu_work": 10.0, "parallel_frac": 0.0,
            "mem_floor": 100.0, "mem_knee": 200.0, "mem_penalty": 1.0,
            "io_time": 0.0, "scale_mem": True} for i in range(n_fn)]
    return {"functions": fns,
            "edges": [[f"f{i}", f"f{i + 1}"] for i in range(n_fn - 1)],
            "input_scale": 1.0, "slo_s": 35.0,
            "pricing": {"mu0_per_vcpu_s": 1.0, "mu1_per_mb_s": 0.0,
                        "mu2_per_invocation": 0.5}}


def test_surface_by_hand():
    config = harness.load_cell("video.fast").config
    cpu = np.full((1, 6), 8.0)
    mem = np.array([[5120.0, 5120.0, 5120.0, 5120.0, 5120.0, 4000.0]])
    rt, failed = reference.surface(config, cpu, mem, np.float64)
    # split_video: 5 + 90 * (0.4 + 0.6 / 8), at its knee
    assert rt[0, 0] == pytest.approx(5.0 + 90.0 * 0.475)
    # extract_a: 4608 MB knee, so no paging penalty at 5120 MB
    assert rt[0, 1] == pytest.approx(2.0 + 700.0 * (0.08 + 0.92 / 8))
    assert not failed[0, :5].any()
    # aggregate above its knee at 4000 MB; nothing fails there
    assert not failed[0, 5]
    mem[0, 0] = 4000.0          # below split_video's 4096 MB floor
    rt2, failed2 = reference.surface(config, cpu, mem, np.float64)
    assert failed2[0, 0]
    assert rt2[0, 0] == pytest.approx(5.0 + 90.0 * 0.475 * 6.0)


def test_cluster_queue_by_hand():
    """Two instances of a two-function chain on a cluster that holds one
    invocation at a time: the second instance waits for the first."""
    config = _chain()
    graph = reference.Graph.of(config)
    cpu = np.ones((1, 2))
    mem = np.full((1, 2), 256.0)
    out = reference.validate(config, graph, cpu, mem, np.array([0.0, 1.0]),
                             {"total_cpu": 1.0, "total_mem_mb": 1e9}, None)
    (a,) = out
    # instance 0's f0 runs 0-10; instance 1's f0, queued since 1, runs
    # 10-20 ahead of instance 0's f1 (FIFO), which runs 20-30; instance
    # 1's f1 runs 30-40
    assert a.finish.tolist() == [30.0, 40.0]
    assert a.queue.tolist() == [10.0, 19.0]
    assert a.cost.tolist() == [21.0, 21.0]
    assert a.hits == 1 and a.total_cost == 42.0


def test_cold_start_by_hand():
    """A warm container is reused within its keep-alive, not after."""
    config = _chain(1)
    graph = reference.Graph.of(config)
    cold = {"delay_s": 2.0, "keep_alive_s": 5.0}
    out = reference.validate(config, graph, np.ones((1, 1)),
                             np.full((1, 1), 256.0),
                             np.array([0.0, 13.0, 40.0]),
                             {"total_cpu": 10.0, "total_mem_mb": 1e9}, cold)
    (a,) = out
    assert a.cold.tolist() == [2.0, 0.0, 2.0]
    assert a.finish.tolist() == [12.0, 23.0, 52.0]


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_reference(name):
    """At a tiny size of each cell's traffic, what ``run_many`` answers
    is what the reference answers."""
    cell = tiny(name)
    system = harness.System(cell)
    graph = reference.Graph.of(cell.config)
    for call in (1, 2):
        inputs = generate.draw_call(cell.mix, cell.config, 2**40 + 3, call)
        got = harness.answers(
            system.call(system.configs(inputs), inputs.arrivals), system.slo)
        numbers = harness.compare(got, harness.want(cell, graph, inputs))
        limits = cell.config["check_limits"]
        assert all(numbers[k] <= limits[k] for k in limits), numbers


def test_lattice_values_survive_the_programs_quantization():
    """The traffic hands the program lattice points, which its
    ResourceConfig keeps unchanged, so the reference sees the same
    sizes."""
    for name in CELLS:
        cell = cell_of(name)
        system = harness.System(cell)
        inputs = generate.draw_call(cell.mix, cell.config, 7, 1)
        configs = system.configs(inputs)
        got_cpu = [[c[n].cpu for n in system.names] for c in configs]
        got_mem = [[c[n].mem for n in system.names] for c in configs]
        assert np.array_equal(got_cpu, inputs.cpu)
        assert np.array_equal(got_mem, inputs.mem)


def test_same_seed_same_inputs_and_large_seeds():
    cell = cell_of("video.capacity")
    a = generate.draw_call(cell.mix, cell.config, 2**33 + 5, 4)
    b = generate.draw_call(cell.mix, cell.config, 2**33 + 5, 4)
    c = generate.draw_call(cell.mix, cell.config, 2**33 + 6, 4)
    assert np.array_equal(a.cpu, b.cpu) and np.array_equal(a.arrivals,
                                                           b.arrivals)
    assert not np.array_equal(a.arrivals, c.arrivals)
    # the incumbent leads, each challenger moves two functions
    assert (a.cpu[0] == 8.0).all() and (a.mem[0] == 5120.0).all()
    moved = ((a.cpu[1:] != 8.0) | (a.mem[1:] != 5120.0)).sum(axis=1)
    assert (moved <= 2).all()

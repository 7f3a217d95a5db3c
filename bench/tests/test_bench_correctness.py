"""``correct`` has to come out false for the precision control and for
each fault a replay validation can have, with the rest of a run driven
as the benchmark drives it (CPU, tiny sizes)."""
import numpy as np
import pytest

from bench import control, harness
from bench.tests.conftest import CPU_DEVICE, tiny

#: the benchmark's cell and the capacity mix kept for a later one
CELLS = ["video.fast", "video.capacity"]


def _sampled(cell, seconds=0.3):
    system = harness.System(cell)
    rng = np.random.default_rng(0)
    calls, sampled, failed = harness.window(cell, system, 2**34 + 9, seconds,
                                            rng)
    assert calls and not failed
    return sampled


def _fails(numbers, limits):
    return any(numbers[k] > limits[k] for k in limits)


@pytest.mark.parametrize("name", CELLS)
def test_float32_control_is_not_correct(name):
    """The reference computed in float32, one precision below the
    configuration's float64, put in the program's place."""
    cell = tiny(name, instances=64)
    sampled = _sampled(cell)
    limits = cell.config["check_limits"]
    assert not _fails(harness.check(cell, sampled), limits)
    assert _fails(control.control_reading(cell, sampled), limits)


def _break(monkeypatch, fault):
    """Plant ``fault`` under every ``run_many`` the timed path calls."""
    from repro.core.engine import FleetEngine

    real = FleetEngine.run_many
    last = {}

    def broken(self, template, configs, arrivals, **kw):
        reports = real(self, template, configs, arrivals, **kw)
        out = fault(reports, last.get(id(self)))
        last[id(self)] = reports
        return out

    monkeypatch.setattr(FleetEngine, "run_many", broken)


def _altered(reports, _):
    """An answer altered where it is produced: one instance's finish
    1 ms late."""
    reports[0].finishes[0] += 1e-3
    return reports


def _half(reports, _):
    """Half the batch left out: each report keeps its first half of the
    instances."""
    from repro.core.engine import FleetReport

    out = []
    for r in reports:
        h = len(r) // 2
        out.append(FleetReport.from_arrays(
            arrival=r.arrivals[:h], finish=r.finishes[:h],
            e2e=r.latencies[:h], queue_delay=r.queue_delays[:h],
            cold_delay=r.cold_delays[:h], cost=r.costs[:h],
            failed=r.failed_mask[:h], makespan=r.makespan,
            cpu_utilization=r.cpu_utilization,
            mem_utilization=r.mem_utilization,
            queue_delay_by_function=r.queue_delay_by_function))
    return out


def _unchanged(reports, previous):
    """A step that returns its state unchanged: the previous call's
    reports again."""
    return previous if previous is not None else reports


@pytest.mark.parametrize("fault", [_altered, _half, _unchanged])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    cell = tiny(name, instances=32)
    _break(monkeypatch, fault)
    out = harness.run_cell(cell, 2**36 + 2, 0.5, False, dict(CPU_DEVICE),
                           t_start=0.0)
    assert out["attempted"] >= 2
    assert out["correct"] is False

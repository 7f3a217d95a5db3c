"""The harness end to end on the CPU: a cell defined only by new files,
a run with no TPU, and the result line's shape."""
import json
import shutil

import pytest

from bench import harness
from bench.tests.conftest import CPU_DEVICE, ROOT, cell_of, tiny

#: a deployment, a mix and a metric that the benchmark does not have
NEW_CONFIG = {
    "name": "chain-3", "workflow": "chain-3", "backend": "analytic",
    "precision": "float64", "input_scale": 1.0, "slo_s": 60.0,
    "pricing": {"mu0_per_vcpu_s": 0.512, "mu1_per_mb_s": 0.001,
                "mu2_per_invocation": 0.0},
    "lattice": {"cpu_min": 0.1, "cpu_max": 10.0, "cpu_step": 0.1,
                "mem_min_mb": 128.0, "mem_max_mb": 10240.0,
                "mem_step_mb": 64.0},
    "check_limits": {"time_rel": 1e-10, "cost_rel": 1e-10, "mismatch": 0},
    "incumbent": {"cpu": 2.0, "mem_mb": 1024.0},
    "assumed": [], "reduced": [],
    "functions": [
        {"name": f"f{i}", "cpu_work": 20.0 + i, "parallel_frac": 0.7,
         "mem_floor": 512.0, "mem_knee": 1024.0, "mem_penalty": 2.0,
         "io_time": 1.0, "scale_mem": True} for i in range(3)],
    "edges": [["f0", "f1"], ["f1", "f2"]],
}
NEW_MIX = {
    "candidates": {"count": 3, "draw": "incumbent_moves", "moved": 2,
                   "cpu_delta": 1.0, "mem_delta_mb": 512},
    "arrivals": {"process": "poisson", "rate": 0.5, "count": 30},
    "cluster": {"total_cpu": 6.0, "total_mem_mb": 8192.0},
    "cold_start": {"delay_s": 0.5, "keep_alive_s": 30.0},
    "check_calls": 2,
}
NEW_METRIC = '''"""calls_done: calls made in the window."""


def read(run):
    return float(len(run.calls))
'''


@pytest.fixture
def new_cell_root(tmp_path):
    """A checkout's benchmark files plus one cell added by new files and
    new entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "chain-3.json").write_text(
        json.dumps(NEW_CONFIG))
    (tmp_path / "bench" / "traffic" / "chain.capacity.json").write_text(
        json.dumps(NEW_MIX))
    (tmp_path / "bench" / "metrics" / "calls_done.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "chain-3", "source": "a test",
                            "file": "bench/configs/chain-3.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "chain3.capacity", "config": "chain-3",
                              "traffic": "chain.capacity", "chips": 1,
                              "why": "a test"})
    spec["end_to_end"].append({"name": "calls_done", "unit": "calls",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["chain3.capacity"]})
    for m in spec["per_layer"]:
        m["workloads"].append("chain3.capacity")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


@pytest.mark.parametrize("trace", [False, True])
def test_a_cell_of_new_files_is_found_and_runs(new_cell_root, trace):
    cell = harness.load_cell("chain3.capacity", new_cell_root)
    assert cell.config["name"] == "chain-3" and cell.mix == NEW_MIX
    out = harness.run_cell(cell, 2**35 + 1, 0.3, trace, dict(CPU_DEVICE),
                           t_start=0.0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    metrics = out["metrics"]
    if trace:
        assert metrics["device_idle_share"]["value"] == 100.0
        assert "host_ms" in metrics and "surface_ms" in metrics
        assert "sweep_device_ms" not in metrics     # no device plane
        assert out["device"]["window_s"] > 0.0
    else:
        assert metrics["calls_done"] == {"value": float(out["attempted"]),
                                         "unit": "calls"}
        assert set(metrics) == {"replay_rate", "setup_s", "calls_done"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"time_rel", "cost_rel", "mismatch"}


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        harness.main(["--workload", "video.fast", "--seed", "1",
                      "--seconds", "1"])
    assert exc.value.code == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_result_line_of_a_cell(capsys):
    """A short run of a real cell at a tiny size: end-to-end metrics,
    the device named, and each compared number beside its limit on the
    last lines of standard error."""
    cell = tiny("video.fast", instances=40)
    out = harness.run_cell(cell, 99, 0.3, False, dict(CPU_DEVICE),
                           t_start=0.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"replay_rate", "validate_p95_ms",
                                   "setup_s"}
    assert out["device"]["kind"] == "cpu"
    err = capsys.readouterr().err.strip().splitlines()
    assert [line.split()[1] for line in err[-3:]] == ["time_rel",
                                                       "cost_rel",
                                                       "mismatch"]
    assert all(line.endswith("device=cpu") for line in err[-3:])


@pytest.mark.parametrize("name,plane", [("video.fast", "fast"),
                                        ("video.capacity", "constrained")])
def test_a_call_is_one_replay_on_its_mixs_plane(name, plane):
    """One ``run_many`` per call: the fast plane on an infinite cluster,
    the constrained plane where the mix names a cluster and cold
    starts."""
    system = harness.System(tiny(name))
    assert system.plane[0] == plane
    assert system.engine.cluster.total_cpu == (
        float("inf") if plane == "fast" else 544.0)


def test_replay_rate_spans_the_window():
    """Instances over the seconds from the first call's start to the last
    call's end, the time between calls included."""
    calls = [harness.CallRecord(10.0, 11.0, 300, 0.0),
             harness.CallRecord(11.5, 12.0, 300, 0.0),
             harness.CallRecord(13.0, 14.0, 400, 0.0)]
    run = harness.Run(cell=cell_of("video.fast"), device=dict(CPU_DEVICE),
                      setup_s=1.0, calls=calls)
    assert harness.reader("replay_rate")(run) == pytest.approx(1000 / 4.0)
    run.calls = []
    assert harness.reader("replay_rate")(run) is None

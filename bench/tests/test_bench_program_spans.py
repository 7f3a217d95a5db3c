"""The reduction of the program's own spans (``bench/program_spans.py``)
on a synthetic trace, and on a traced run of a cell on the CPU."""
import pytest

from bench import profile, program_spans
from bench.tests.conftest import CPU_DEVICE, tiny

OPS = [[(100.0, 200.0, "%a"), (150.0, 300.0, "%b"), (600.0, 700.0, "%a")]]
MODULES = [[(100.0, 300.0, "jit_sweep(1)"), (600.0, 700.0, "jit_sweep(1)")]]
SPANS = [(0.0, 1000.0, "bench.window"),
         (0.0, 40.0, "bench.inputs"), (50.0, 400.0, "bench.call"),
         (60.0, 390.0, "bench.surface"),
         (400.0, 540.0, "bench.inputs"), (550.0, 800.0, "bench.call")]
#: the program's spans in the two calls, [50, 400] and [550, 800]; a
#: collection after the window lies outside it
PROGRAM = [
    (55.0, 395.0, "fleet.run_many", {"call": 1}),
    (60.0, 70.0, "fleet.surface", {"call": 1}),
    (70.0, 80.0, "fleet.price", {"call": 1}),
    (80.0, 320.0, "fleet.sweep", {"call": 1, "cells": 8}),
    (250.0, 320.0, "fleet.fetch", {"call": 1}),
    (320.0, 390.0, "fleet.assemble", {"call": 1}),
    (330.0, 350.0, "fleet.ledger", {"call": 1, "cand": 0}),
    (350.0, 370.0, "fleet.ledger", {"call": 1, "cand": 1}),
    (355.0, 365.0, "py.gc", {"generation": 0}),
    (555.0, 795.0, "fleet.run_many", {"call": 2}),
    (560.0, 570.0, "fleet.surface", {"call": 2}),
    (570.0, 580.0, "fleet.price", {"call": 2}),
    (580.0, 610.0, "fleet.sweep", {"call": 2, "cells": 8}),
    (610.0, 780.0, "fleet.assemble", {"call": 2}),
    (620.0, 640.0, "fleet.ledger", {"call": 2, "cand": 0}),
    (700.0, 720.0, "fleet.cell", {"call": 2, "plane": "fast"}),
    (785.0, 790.0, "py.gc", {"generation": 0}),
    (1005.0, 1010.0, "py.gc", {"generation": 2}),
]


def _trace():
    return profile.from_intervals(OPS, MODULES, SPANS)


@pytest.mark.parametrize("key,ns", [
    ("surface_ms", (10.0 + 10.0) / 2),
    ("pricing_ms", (10.0 + 10.0) / 2),
    # call 1: 240 ns, 200 busy; call 2: 30 ns, 10 busy
    ("sweep_host_ms", (40.0 + 20.0) / 2),
    # 70 ns, 50 of them busy
    ("fetch_ms", 20.0 / 2),
    # call 1: 70 ns, none busy; call 2: 170 ns, 90 of them busy
    ("assembly_ms", (70.0 + 80.0) / 2),
    ("ledger_ms", (20.0 + 20.0 + 20.0) / 2),
    ("cell_ms", 20.0 / 2),
    ("gc_ms", (10.0 + 5.0) / 2),
    # call 1: [50, 60] and [390, 400]; call 2: [550, 560], [780, 785]
    # and [790, 800]
    ("call_other_ms", (20.0 + 25.0) / 2),
    # calls of 350 and 250 ns, 200 and 100 of them busy
    ("host_ms", (150.0 + 150.0) / 2),
])
def test_split_of_a_call(key, ns):
    out = program_spans.split(_trace(), PROGRAM)
    assert out[key] == pytest.approx(ns * 1e-6)


def test_counts_of_calls_collections_and_covered_sweeps():
    out = program_spans.split(_trace(), PROGRAM)
    assert out["calls"] == 2 and out["collections"] == 2
    # the second sweep run, [600, 700], lies outside call 2's
    # fleet.sweep span, [580, 610]
    assert out["sweep_inside_share"] == 0.5


@pytest.mark.parametrize("key", ["surface_ms", "pricing_ms", "sweep_host_ms",
                                 "assembly_ms", "ledger_ms", "gc_ms",
                                 "call_other_ms"])
def test_a_program_without_spans_gives_nothing(key):
    assert program_spans.split(_trace(), [])[key] is None


def test_gaps_are_named_by_the_innermost_program_span():
    ops = [[(0.0, 10.0, "%a"), (90.0, 100.0, "%a"), (190.0, 200.0, "%a")]]
    spans = [(0.0, 200.0, "bench.window"), (0.0, 200.0, "bench.call")]
    program = [(0.0, 200.0, "fleet.run_many", {"call": 1}),
               (10.0, 90.0, "fleet.assemble", {"call": 1}),
               (20.0, 80.0, "fleet.ledger", {"call": 1, "cand": 0}),
               (140.0, 160.0, "py.gc", {"generation": 0})]
    trace = profile.from_intervals(ops, [], spans)
    gaps = program_spans.breakdown(trace, program)["idle_gaps"]
    # [10, 90] around 50: the ledger; [100, 190] around 145: a collection
    assert [g[0] for g in gaps] == ["py.gc", "fleet.ledger"]
    assert [g[0] for g in profile.breakdown(trace)["idle_gaps"]] == [
        "bench.call"] * 2


def test_a_traced_run_of_a_cell_yields_the_program_spans():
    cell = tiny("video.fast")
    read_bench = profile.read
    result, out = program_spans.traced_run(cell, 2**33 + 5, 0.3,
                                           dict(CPU_DEVICE), 0.0)
    assert profile.read is read_bench
    assert result["correct"] is True
    calls = out["calls"]
    assert calls == result["attempted"] >= 1
    c = cell.mix["candidates"]["count"]
    assert out["counters"]["fleet.calls.fast"] == calls + 1  # + warm-up
    assert out["counters"]["fleet.cells.swept"] == (calls + 1) * c
    assert out["counters"]["fleet.instances"] == (
        (calls + 1) * c * cell.mix["arrivals"]["count"])
    for key in ("surface_ms", "pricing_ms", "sweep_host_ms", "fetch_ms",
                "assembly_ms", "ledger_ms", "gc_ms", "call_other_ms"):
        assert out[key] >= 0.0, key
    assert out["assembly_ms"] >= out["ledger_ms"] > 0.0
    assert out["call_other_ms"] < out["host_ms"]
    # what the harness's readers read is the benchmark's own trace
    assert result["metrics"]["host_ms"]["value"] == out["host_ms"]

"""The benchmark's one traffic generator.

A traffic mix is a JSON file under ``bench/traffic/``; this module reads
its parameters and draws, for one validation call, the candidate
config-maps and the arrival stream from ``(seed, call index)``. It uses
no code of the program, so a change to the program cannot move what the
benchmark offers it.

Mix parameters (all required unless marked optional):

* ``candidates``: ``count`` (C) and the ``draw`` ``incumbent_moves``:
  candidate 0 is the configuration's incumbent; each other candidate
  moves ``moved`` functions (drawn without replacement) by up to
  ``cpu_delta`` vCPU and ``mem_delta_mb`` MB, uniformly on the lattice,
  clamped to its range.
* ``arrivals``: ``process`` ``poisson`` with ``rate`` (instances/s) and
  ``count`` (N), starting at 0.
* ``cluster`` (optional): ``total_cpu`` and ``total_mem_mb``; absent means
  an infinite cluster.
* ``cold_start`` (optional): ``delay_s`` and ``keep_alive_s``.
* ``check_calls``: how many of the window's calls the correctness check
  compares with the reference (a sample drawn from the seed).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class Lattice:
    """The configuration lattice of the deployment (integer steps)."""

    cpu_min: float
    cpu_max: float
    cpu_step: float
    mem_min_mb: float
    mem_max_mb: float
    mem_step_mb: float

    def cpu(self, steps: np.ndarray) -> np.ndarray:
        """vCPU values of integer lattice steps, clamped to the range."""
        lo = round(self.cpu_min / self.cpu_step)
        hi = round(self.cpu_max / self.cpu_step)
        return np.clip(steps, lo, hi) * self.cpu_step

    def mem(self, steps: np.ndarray) -> np.ndarray:
        lo = round(self.mem_min_mb / self.mem_step_mb)
        hi = round(self.mem_max_mb / self.mem_step_mb)
        return np.clip(steps, lo, hi) * self.mem_step_mb


@dataclasses.dataclass
class CallInputs:
    """What one validation call is given: (C, V) vCPU and MB per
    candidate and function (functions in the configuration's order),
    and N arrival times."""

    cpu: np.ndarray
    mem: np.ndarray
    arrivals: np.ndarray


def lattice_of(config: Dict) -> Lattice:
    return Lattice(**config["lattice"])


def call_rng(seed: int, call: int) -> np.random.Generator:
    """The stream of one call: the same (seed, call) gives the same
    inputs. Any whole seed is accepted; it is folded to 64 bits."""
    return np.random.default_rng([seed % 2**64, call])


def draw_candidates(mix: Dict, config: Dict,
                    rng: np.random.Generator) -> tuple:
    spec = mix["candidates"]
    lat = lattice_of(config)
    n_fn = len(config["functions"])
    count = int(spec["count"])
    if spec["draw"] != "incumbent_moves":
        raise ValueError(f"unknown candidate draw {spec['draw']!r}")
    inc = config["incumbent"]
    cpu_k = np.full((count, n_fn), round(inc["cpu"] / lat.cpu_step))
    mem_k = np.full((count, n_fn), round(inc["mem_mb"] / lat.mem_step_mb))
    d_cpu = round(spec["cpu_delta"] / lat.cpu_step)
    d_mem = round(spec["mem_delta_mb"] / lat.mem_step_mb)
    for c in range(1, count):
        moved = rng.choice(n_fn, size=int(spec["moved"]), replace=False)
        cpu_k[c, moved] += rng.integers(-d_cpu, d_cpu + 1, size=moved.size)
        mem_k[c, moved] += rng.integers(-d_mem, d_mem + 1, size=moved.size)
    return lat.cpu(cpu_k), lat.mem(mem_k)


def draw_arrivals(mix: Dict, rng: np.random.Generator) -> np.ndarray:
    spec = mix["arrivals"]
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    gaps = rng.exponential(1.0 / float(spec["rate"]), size=int(spec["count"]))
    return np.cumsum(gaps)


def draw_call(mix: Dict, config: Dict, seed: int, call: int) -> CallInputs:
    rng = call_rng(seed, call)
    cpu, mem = draw_candidates(mix, config, rng)
    return CallInputs(cpu=cpu, mem=mem, arrivals=draw_arrivals(mix, rng))

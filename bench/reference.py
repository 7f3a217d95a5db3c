"""Plain reference of replay validation, independent of the program.

It imports nothing of the program and reads only the configuration file
and a call's inputs. The semantics it implements:

* Response surface (AARC section II-A): a function of work ``w``,
  parallel fraction ``p``, working-set floor and knee, paging penalty
  and I/O time runs ``io + w * ((1 - p) + p / cpu) * f(mem)``, with
  ``f = 1`` at or above the knee, rising linearly to ``1 + penalty`` at
  the floor; below the floor the invocation is OOM-killed after
  thrashing at ``1 + penalty`` and the instance is marked failed.
* Pricing (section IV-A d): ``runtime * (mu0 * cpu + mu1 * mem) + mu2``
  per invocation; an instance costs the sum over its functions.
* Replay: an instance arrives, its source functions become ready, a
  function becomes ready when all its predecessors have finished, and
  the instance finishes with its last function.
  - With an infinite cluster and no cold starts every function starts
    when it is ready.
  - With a finite cluster, ready invocations wait in one FIFO queue and
    start, at each event time, from the head for as long as the head
    fits the free vCPU and MB (no overtaking). An invocation holds its
    size from start to finish. Events at one time are handled in the
    order they were scheduled (arrivals first, by instance), finishes
    before admission.
  - With cold starts a function that finds no warm container of its own
    (one deposited by an earlier finish, not expired) pays the
    provisioning delay; a finish that was not OOM-killed leaves one warm
    container that lives ``keep_alive_s``. Containers are claimed in the
    order they were deposited.

Everything is computed in one dtype: float64, as the configuration
states, or a lower one for the precision control.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import math
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Graph:
    """The workflow's topology in the configuration's function order."""

    names: List[str]
    preds: List[List[int]]
    succs: List[List[int]]
    sources: List[int]
    topo: List[int]

    @classmethod
    def of(cls, config: Dict) -> "Graph":
        names = [f["name"] for f in config["functions"]]
        col = {n: i for i, n in enumerate(names)}
        preds: List[List[int]] = [[] for _ in names]
        succs: List[List[int]] = [[] for _ in names]
        for a, b in config["edges"]:
            succs[col[a]].append(col[b])
            preds[col[b]].append(col[a])
        indeg = [len(p) for p in preds]
        ready = [(names[v], v) for v in range(len(names)) if not indeg[v]]
        heapq.heapify(ready)
        topo: List[int] = []
        while ready:
            _, v = heapq.heappop(ready)
            topo.append(v)
            for s in succs[v]:
                indeg[s] -= 1
                if not indeg[s]:
                    heapq.heappush(ready, (names[s], s))
        if len(topo) != len(names):
            raise ValueError("the configuration's workflow has a cycle")
        return cls(names, preds, succs,
                   [v for v in range(len(names)) if not preds[v]], topo)


@dataclasses.dataclass
class Answer:
    """What one candidate's replay says: per-instance arrays and the
    three numbers a validation decides on."""

    finish: np.ndarray
    latency: np.ndarray
    queue: np.ndarray
    cold: np.ndarray
    cost: np.ndarray
    failed: np.ndarray
    p99: float
    hits: int              # instances within the SLO and not failed
    total_cost: float


def surface(config: Dict, cpu: np.ndarray, mem: np.ndarray, dtype):
    """Runtimes and OOM flags of (C, V) candidate sizes."""
    fns = config["functions"]
    col = lambda key: np.array([float(f[key]) for f in fns], dtype=dtype)
    scale = dtype(config["input_scale"])
    grows = np.array([bool(f["scale_mem"]) for f in fns])
    floor = np.where(grows, col("mem_floor") * scale, col("mem_floor"))
    knee = np.where(grows, col("mem_knee") * scale, col("mem_knee"))
    penalty, p = col("mem_penalty"), col("parallel_frac")
    cpu = cpu.astype(dtype)
    mem = mem.astype(dtype)
    one = dtype(1.0)
    failed = mem < floor
    sloped = ~failed & (mem < knee) & (knee > floor)
    span = np.where(knee > floor, knee - floor, one)
    frac = np.where(sloped, (knee - mem) / span, dtype(0.0))
    factor = np.where(failed, one + penalty, one + penalty * frac)
    amdahl = (one - p) + p / np.maximum(cpu, dtype(1e-6))
    runtime = col("io_time") + col("cpu_work") * scale * amdahl * factor
    return runtime, failed


def prices(config: Dict, runtime: np.ndarray, cpu: np.ndarray,
           mem: np.ndarray, dtype) -> np.ndarray:
    p = config["pricing"]
    rate = (dtype(p["mu0_per_vcpu_s"]) * cpu.astype(dtype)
            + dtype(p["mu1_per_mb_s"]) * mem.astype(dtype))
    return runtime * rate + dtype(p["mu2_per_invocation"])


def percentile(values: np.ndarray, q: float) -> float:
    """Linear interpolation between closest ranks; an infinite value in
    the interpolated pair makes the percentile infinite."""
    lat = np.sort(values)
    if not lat.size:
        return 0.0
    rank = q / 100.0 * (lat.size - 1)
    lo, hi = int(np.floor(rank)), int(np.ceil(rank))
    if not np.isfinite(lat[hi]):
        return float(lat[lo]) if rank == lo else float("inf")
    return float(lat[lo] + (lat[hi] - lat[lo]) * (rank - lo))


def _answer(finish, arrivals, queue, cold, cost, failed, slo, dtype):
    latency = finish - arrivals
    if dtype is np.float64:
        total = math.fsum(cost.tolist())
    else:
        total = np.sum(cost, dtype=dtype)
    return Answer(finish=finish, latency=latency, queue=queue, cold=cold,
                  cost=cost, failed=failed, p99=percentile(latency, 99.0),
                  hits=int(np.count_nonzero(~failed & (latency <= slo))),
                  total_cost=float(total))


def replay_fast(graph: Graph, runtime, failed, cost, arrivals, slo,
                dtype) -> List[Answer]:
    """Infinite cluster, no cold starts: every function starts when its
    predecessors have finished, so an instance finishes at its arrival
    plus the longest path."""
    t = arrivals.astype(dtype)[None, :]
    fin: Dict[int, np.ndarray] = {}
    for v in graph.topo:
        start = t
        if graph.preds[v]:
            start = fin[graph.preds[v][0]]
            for u in graph.preds[v][1:]:
                start = np.maximum(start, fin[u])
        fin[v] = start + runtime[:, v][:, None]
    finish = np.max(np.stack([fin[v] for v in graph.topo]), axis=0)
    inst_cost = np.zeros(runtime.shape[0], dtype=dtype)
    for v in graph.topo:
        inst_cost = inst_cost + cost[:, v]
    n = arrivals.size
    zeros = np.zeros(n, dtype=dtype)
    return [_answer(finish[c], t[0], zeros, zeros,
                    np.full(n, inst_cost[c], dtype=dtype),
                    np.full(n, bool(failed[c].any())), slo, dtype)
            for c in range(runtime.shape[0])]


_ARRIVE, _FINISH = 0, 1


def replay_cluster(graph: Graph, runtime, failed, cost, cpu, mem, arrivals,
                   slo, cluster: Dict, cold_start: Optional[Dict],
                   dtype) -> List[Answer]:
    """Finite cluster and cold starts: the discrete-event replay, one
    candidate at a time."""
    as_list = (lambda a: a.astype(dtype).tolist()) if dtype is np.float64 \
        else (lambda a: list(a.astype(dtype)))
    total_cpu = dtype(cluster["total_cpu"])
    total_mem = dtype(cluster["total_mem_mb"])
    delay_s = dtype(cold_start["delay_s"] if cold_start else 0.0)
    keep_s = dtype(cold_start["keep_alive_s"] if cold_start else 0.0)
    zero = dtype(0.0)
    rank = {v: k for k, v in enumerate(graph.topo)}
    times = as_list(arrivals)
    m = len(times)
    out = []
    for c in range(runtime.shape[0]):
        rt, price = as_list(runtime[c]), as_list(cost[c])
        size_cpu, size_mem = as_list(cpu[c]), as_list(mem[c])
        oom = failed[c].tolist()
        finish = [zero] * m
        queue = [zero] * m
        cold = [zero] * m
        bad = [False] * m
        waiting = [[len(p) for p in graph.preds] for _ in range(m)]
        spent: List[List[tuple]] = [[] for _ in range(m)]
        heap = [(times[i], i, _ARRIVE, i, -1) for i in range(m)]
        heapq.heapify(heap)
        seq = m
        ready: collections.deque = collections.deque()
        warm: Dict[int, List[list]] = collections.defaultdict(list)
        used_cpu = used_mem = zero
        while heap:
            now = heap[0][0]
            while heap and heap[0][0] == now:
                _, _, kind, i, v = heapq.heappop(heap)
                if kind == _ARRIVE:
                    ready.extend((now, i, s) for s in graph.sources)
                    continue
                used_cpu -= size_cpu[v]
                used_mem -= size_mem[v]
                if delay_s > zero and not oom[v]:
                    warm[v].append([now, now + keep_s])
                finish[i] = max(finish[i], now)
                for s in graph.succs[v]:
                    waiting[i][s] -= 1
                    if not waiting[i][s]:
                        ready.append((now, i, s))
            started = []
            while ready:
                _, _, v = ready[0]
                if (used_cpu + size_cpu[v] > total_cpu
                        or used_mem + size_mem[v] > total_mem):
                    break
                used_cpu += size_cpu[v]
                used_mem += size_mem[v]
                started.append(ready.popleft())
            for since, i, v in started:
                queue[i] += now - since
                bad[i] = bad[i] or oom[v]
                wait = zero
                if delay_s > zero and not _claim(warm[v], now):
                    wait = delay_s
                cold[i] += wait
                spent[i].append((rank[v], price[v]))
                heapq.heappush(heap, (now + wait + rt[v], seq, _FINISH, i, v))
                seq += 1
        inst_cost = []
        for items in spent:
            acc = zero
            for _, x in sorted(items, key=lambda kv: kv[0]):
                acc += x
            inst_cost.append(acc)
        arr = lambda xs: np.array(xs, dtype=dtype)
        out.append(_answer(arr(finish), arr(times), arr(queue), arr(cold),
                           arr(inst_cost), np.array(bad), slo, dtype))
    return out


def _claim(pool: List[list], now) -> bool:
    """Take the first unexpired container deposited by ``now``."""
    pool[:] = [c for c in pool if c[1] >= now]
    for k, (deposit, _) in enumerate(pool):
        if deposit <= now:
            del pool[k]
            return True
    return False


def validate(config: Dict, graph: Graph, cpu: np.ndarray, mem: np.ndarray,
             arrivals: np.ndarray, cluster: Optional[Dict],
             cold_start: Optional[Dict], dtype=np.float64) -> List[Answer]:
    """One validation: C candidates replayed over one arrival stream."""
    runtime, failed = surface(config, cpu, mem, dtype)
    cost = prices(config, runtime, cpu, mem, dtype)
    slo = dtype(config["slo_s"])
    if cluster is None and cold_start is None:
        return replay_fast(graph, runtime, failed, cost, arrivals, slo,
                           dtype)
    return replay_cluster(graph, runtime, failed, cost, cpu, mem, arrivals,
                          slo, cluster or {"total_cpu": np.inf,
                                           "total_mem_mb": np.inf},
                          cold_start, dtype)

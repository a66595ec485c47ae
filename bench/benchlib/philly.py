"""Philly-statistic jobs for every cell's traffic (paper section 4).

The paper takes inter-arrival and duration statistics from the
Microsoft Philly trace (Jeon et al., ATC '19) and draws job sizes from
a truncated exponential on [1, 4096], with shapes by its rule of thumb:
jobs of up to 256 XPUs are mostly 1D or 2D, larger ones 2D or 3D, one
factorization picked at random, every shape decomposable into at most
64 cubes of 4^3. Arrivals are Poisson at a target offered load,
durations lognormal.

This is the benchmark's own copy of that generator, so that a change
to the program cannot change the traffic it is measured with. One pool
of jobs is drawn from the mix's fixed ``pool_seed``. A run's ``--seed``
either permutes it (the jobs, and separately the gaps between
arrivals, within the prefill and within the rest apart), so that every
seed offers the same sizes, durations and gaps in another order; or
leaves it in the order drawn and draws only the job ids, so that every
seed offers the same arrivals in the same order (``order`` in the
mix).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

Dims = Tuple[int, int, int]


@dataclass(frozen=True)
class Job:
    job_id: int
    arrival: float      # simulated seconds
    duration: float     # simulated seconds
    shape: Dims

    @property
    def size(self) -> int:
        a, b, c = self.shape
        return a * b * c


def _factorizations3(n: int) -> List[Dims]:
    out = []
    for a in range(1, n + 1):
        if n % a:
            continue
        m = n // a
        for b in range(1, m + 1):
            if m % b == 0:
                out.append((a, b, m // b))
    return out


def _factor_pairs(n: int) -> List[Tuple[int, int]]:
    return [(a, n // a) for a in range(1, n + 1) if n % a == 0]


def _cubes_needed(dims: Sequence[int], n: int) -> int:
    out = 1
    for d in dims:
        out *= -(-int(d) // n)
    return out


def sample_shape(rng: np.random.Generator, size: int,
                 p: Dict[str, Any]) -> Dims:
    """The paper's shape rule; a size with no shape that fits the cube
    budget is bumped to the next even size."""
    def feasible(dims) -> bool:
        return _cubes_needed(dims, p["cube_n"]) <= p["cube_budget"]

    for _ in range(64):
        small = size <= p["small_threshold"]
        if small:
            want = "1d" if rng.uniform() < p["p_1d_small"] else "2d"
        else:
            want = "2d" if rng.uniform() < p["p_2d_large"] else "3d"
        if want == "3d":
            triples = [t for t in _factorizations3(size)
                       if min(t) > 1 and feasible(t)]
            if triples:
                return tuple(int(v) for v in
                             triples[rng.integers(len(triples))])
            want = "2d"
        if want == "2d":
            pairs = [q for q in _factor_pairs(size)
                     if min(q) > 1 and feasible((q[0], q[1], 1))]
            if pairs:
                a, b = pairs[rng.integers(len(pairs))]
                return (int(a), int(b), 1)
        if feasible((size, 1, 1)):
            return (size, 1, 1)
        size += 2
    raise RuntimeError(f"no feasible shape for size {size}")


def pool(p: Dict[str, Any], num_jobs: int) -> Tuple[List[Tuple[float, Dims]],
                                                    np.ndarray]:
    """``num_jobs`` (duration, shape) pairs and as many arrival gaps,
    drawn from ``p["pool_seed"]``; the draw order follows the program's
    generator (sizes, durations, gaps, then shapes)."""
    rng = np.random.default_rng(p["pool_seed"])
    fmax = 1.0 - math.exp(-p["size_max"] / p["size_scale"])
    u = rng.uniform(size=num_jobs)
    sizes = np.clip(np.ceil(-p["size_scale"] * np.log(1.0 - u * fmax)),
                    1, p["size_max"]).astype(np.int64)
    sizes = np.where(sizes > 1, (sizes + 1) // 2 * 2, sizes)
    durations = rng.lognormal(mean=math.log(p["duration_median_s"]),
                              sigma=p["duration_sigma"], size=num_jobs)
    # Offered load = rate * E[size * duration] / cluster XPUs.
    mean_gap = float(np.mean(sizes * durations)) / (
        p["load"] * p["cluster_xpus"])
    gaps = rng.exponential(mean_gap, size=num_jobs)
    shapes = [sample_shape(rng, int(s), p) for s in sizes]
    return list(zip((float(d) for d in durations), shapes)), gaps


def jobs(p: Dict[str, Any], num_jobs: int, seed: Any, head: int = 0,
         order: str = "seeded") -> List[Job]:
    """The pool with arrival times. ``order`` "seeded": in the order
    ``seed`` gives it, the first ``head`` jobs and gaps, and the rest,
    permuted apart (:func:`arrange`). ``order`` "drawn": in the order it
    was drawn, the same for every seed, which draws only the job ids
    (:func:`relabel`)."""
    if order == "drawn":
        return relabel(pool(p, num_jobs), seed)
    if order != "seeded":
        raise ValueError(f"no job order {order!r}")
    return arrange(pool(p, num_jobs), seed, head)


def relabel(drawn: Tuple[List[Tuple[float, Dims]], np.ndarray],
            seed: Any) -> List[Job]:
    """A pool drawn by :func:`pool`, in the order it was drawn; ``seed``
    draws which id each job carries. A served scheduler's work depends
    on the order of arrivals far more than on their mix (a queue that
    forms or not decides how many plan searches each completion
    triggers), so this order gives every seed the same work."""
    items, gaps = drawn
    ids = np.random.default_rng(seed).permutation(len(items))
    arrivals = np.cumsum(gaps)
    return [Job(job_id=int(ids[i]), arrival=float(arrivals[i]),
                duration=d, shape=s) for i, (d, s) in enumerate(items)]


def arrange(drawn: Tuple[List[Tuple[float, Dims]], np.ndarray], seed: Any,
            head: int = 0) -> List[Job]:
    """A pool drawn by :func:`pool`, in the order ``seed`` gives it."""
    items, gaps = drawn
    num_jobs = len(items)
    rng = np.random.default_rng(seed)

    def split_permutation() -> np.ndarray:
        return np.concatenate([rng.permutation(head),
                               head + rng.permutation(num_jobs - head)])

    order = split_permutation()
    arrivals = np.cumsum(gaps[split_permutation()])
    return [Job(job_id=i, arrival=float(arrivals[i]),
                duration=items[j][0], shape=items[j][1])
            for i, j in enumerate(order)]


def mean_gap(p: Dict[str, Any], num_jobs: int) -> float:
    """Mean simulated seconds between arrivals in the pool."""
    return float(np.mean(pool(p, num_jobs)[1]))

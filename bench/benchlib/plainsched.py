"""The plain reference of the scheduler above the fitmask engine: fold
candidates, RFold and Folding placement, the daemon's FIFO admission
with backfill, its state digest, its journal as written to disk, and the
job-level simulator with its summary. Written from the rules the paper
and the program state, it imports nothing of the program and takes
nothing the program made.

Rules (arXiv 2510.03891 sections 3 and 4):

* Folds of a job shape, its extents sorted descending: the identity in
  each axis order; a 1D ring on a Hamiltonian cycle of an even-volume
  box with at most one unit extent; for a 2D shape, one ring kept on
  box axis 0 and the other on a Hamiltonian cycle of the remaining 2D
  grid; the halving fold (A, B, 2) -> (A, B/2, 4). A Hamiltonian cycle
  needs no wrap. A ring of three or more that lies along a box axis
  closes through that axis's wrap, and so does the halving fold's B
  ring through the wrap of box axis 2; a ring whose wrap is missing is
  broken. Folds whose box exceeds the largest extent are left out.
* RFold on cubes of n^3: folds that are rotations of one another (same
  kind, same multiset of extent and needed wrap) count once. For each
  fold, every corner offset per axis that keeps the cube count at its
  minimum; the box splits into one piece per cube it crosses; pieces
  are assigned largest first, each to the cube in which its block is
  free that has the fewest free cells, then a used one before an empty
  one, then the lowest id, no cube twice. A wrap exists on an axis
  where the offset is 0 and the box spans whole cubes. The plan
  minimises (broken rings, cubes, OCS links, fresh cubes); on a tie the
  earlier fold, then the earlier offset, wins.
* Folding on a static torus: each fold at its first free origin in C
  order, the box not wrapping past an edge; a wrap exists on an axis the
  box spans in full. The plan minimises (broken rings, longest extent);
  on a tie the earlier fold wins.
* Admission (the daemon and the simulator alike): FIFO; with backfill a
  job behind a blocked head may start; a shape that cannot be placed on
  an empty cluster is dropped. The simulator retries a shape that
  failed only after the next completion.
"""
from __future__ import annotations

import hashlib
import heapq
import itertools
import json
import os
import struct
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .reference import PlainFitmask

Dims = Tuple[int, int, int]
Span = Tuple[int, int]
PLACED, QUEUED, DROPPED = "placed", "queued", "dropped"
BROKEN_RING_SLOWDOWN = 1.17      # the paper's measured penalty (3.1)
_FIT = PlainFitmask()


def _volume(dims: Sequence[int]) -> int:
    out = 1
    for d in dims:
        out *= int(d)
    return out


def _sorted_dims(dims: Sequence[int]) -> Dims:
    a, b, c = sorted((int(v) for v in dims), reverse=True)
    return (a, b, c)


def _factorizations3(n: int, max_dim: Optional[int]) -> List[Dims]:
    out = []
    for a in range(1, n + 1):
        if n % a or (max_dim is not None and a > max_dim):
            continue
        m = n // a
        for b in range(1, m + 1):
            if m % b:
                continue
            c = m // b
            if max_dim is not None and (b > max_dim or c > max_dim):
                continue
            out.append((a, b, c))
    return out


def _factor_pairs(n: int, max_dim: Optional[int]) -> List[Tuple[int, int]]:
    return [(a, n // a) for a in range(1, n + 1) if n % a == 0
            and (max_dim is None or (a <= max_dim and n // a <= max_dim))]


# -- folds -----------------------------------------------------------------

@dataclass(frozen=True)
class Fold:
    job_dims: Dims
    box: Dims
    kind: str
    variant: Tuple                       # which construction of the kind
    closures: Tuple[Tuple[int, int], ...]  # (ring axis, box axis of its wrap)

    @property
    def wrap_required(self) -> Tuple[bool, bool, bool]:
        need = {ax for _, ax in self.closures}
        return (0 in need, 1 in need, 2 in need)

    def broken(self, wrap: Sequence[bool]) -> Tuple[int, ...]:
        return tuple(sorted({a for a, ax in self.closures if not wrap[ax]}))

    def __str__(self) -> str:
        return (f"{'x'.join(map(str, self.job_dims))}->"
                f"{'x'.join(map(str, self.box))}[{self.kind}]")


_PERMS = list(set(itertools.permutations((0, 1, 2))))


def folds_of(dims: Sequence[int], max_dim: Optional[int]) -> List[Fold]:
    """Every fold of a shape, in the order the policies visit them."""
    d = _sorted_dims(dims)
    out: List[Fold] = []
    for perm in _PERMS:                  # ring axis a lies on box axis perm[a]
        box = tuple(d[perm.index(ax)] for ax in range(3))
        out.append(Fold(d, box, "identity",
                        tuple(perm[a] if d[a] > 1 else -1 for a in range(3)),
                        tuple((a, perm[a]) for a in range(3) if d[a] > 2)))
    nd = max(1, sum(1 for v in d if v > 1))
    if nd == 1 and d[0] % 2 == 0 and d[0] >= 4:
        out += [Fold(d, box, "cycle1d", (), ())
                for box in _factorizations3(d[0], max_dim)
                if sum(1 for v in box if v == 1) < 2]
    if nd == 2:
        for ring, keep, folded in ((0, d[0], d[1]), (1, d[1], d[0])):
            if folded % 2 or folded < 4 or (max_dim is not None
                                            and keep > max_dim):
                continue
            out += [Fold(d, (keep, b1, b2), "ring_x_ham", (ring,),
                         ((ring, 0),) if keep > 2 else ())
                    for b1, b2 in _factor_pairs(folded, max_dim)
                    if b1 >= 2 and b2 >= 2]
    if nd >= 2:
        for perm in _PERMS:              # (A, B, C) = extents of axes perm
            a, b, c = (d[p] for p in perm)
            if c != 2 or b % 2 or b < 4:
                continue
            out.append(Fold(d, (a, b // 2, 4), "halving3d", perm,
                            (((perm[0], 0),) if a > 2 else ())
                            + ((perm[1], 2),)))
    if max_dim is not None:
        out = [f for f in out if max(f.box) <= max_dim]
    seen, uniq = set(), []
    for f in out:
        key = (f.kind, f.box, f.variant)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq


# -- RFold on reconfigurable cubes -----------------------------------------

@dataclass
class _Candidate:
    prefix: Tuple[int, int, int]         # (broken rings, cubes, OCS links)
    fold: Fold
    offsets: Dims
    cube_grid: Dims
    wrap: Tuple[bool, bool, bool]
    broken: Tuple[int, ...]
    links: int
    pieces: List[Tuple[Dims, Tuple[Span, Span, Span]]]
    order: List[int]                     # largest piece first


class PlainRFold:
    name = "rfold"

    def __init__(self, num_xpus: int = 4096, cube_n: int = 4) -> None:
        self.n = int(cube_n)
        self.num_cubes = int(num_xpus) // self.n ** 3
        self.occ = np.zeros((self.num_cubes,) + (self.n,) * 3, bool)
        self.allocations: Dict[int, List[Tuple[int, Tuple[Span, ...]]]] = {}
        self._cands: Dict[Dims, List[_Candidate]] = {}

    @property
    def num_xpus(self) -> int:
        return self.occ.size

    @property
    def busy(self) -> int:
        return int(self.occ.sum())

    def folds(self, dims: Sequence[int]) -> List[Fold]:
        seen, out = set(), []
        for f in folds_of(dims, self.num_cubes * self.n):
            key = (f.kind, tuple(sorted(zip(f.box, f.wrap_required))))
            if key not in seen:
                seen.add(key)
                out.append(f)
        return out

    def _cubes(self, extent: int, offset: int = 0) -> int:
        return -(-(offset + extent) // self.n)

    def can_ever_place(self, dims: Sequence[int]) -> bool:
        return any(_volume([self._cubes(e) for e in f.box]) <= self.num_cubes
                   for f in self.folds(dims))

    def _spans(self, extent: int, offset: int) -> List[Tuple[int, Span]]:
        n = self.n
        return [(i, (max(offset, i * n) - i * n,
                     min(offset + extent, (i + 1) * n) - i * n))
                for i in range(self._cubes(extent, offset))]

    def candidates(self, dims: Sequence[int]) -> List[_Candidate]:
        """Every (fold, offset) plan shape, in the order the score and
        the tie rule visit them; independent of occupancy."""
        key = _sorted_dims(dims)
        if key in self._cands:
            return self._cands[key]
        rows = []
        for fi, fold in enumerate(self.folds(key)):
            box = fold.box
            per_axis = [range(self._cubes(e) * self.n - e + 1) for e in box]
            for ri, offs in enumerate(itertools.product(*per_axis)):
                spans = [self._spans(e, o) for e, o in zip(box, offs)]
                grid = tuple(len(s) for s in spans)
                if _volume(grid) > self.num_cubes:
                    continue
                wrap = tuple(o == 0 and e == g * self.n
                             for e, o, g in zip(box, offs, grid))
                a, b, c = box
                links = sum((g - 1 + w) * x for g, w, x in
                            zip(grid, wrap, (b * c, a * c, a * b)))
                pieces = [((ix, iy, iz), (sx, sy, sz))
                          for ix, sx in spans[0] for iy, sy in spans[1]
                          for iz, sz in spans[2]]
                sizes = [_volume([hi - lo for lo, hi in p[1]])
                         for p in pieces]
                broken = fold.broken(wrap)
                cand = _Candidate((len(broken), len(pieces), links), fold,
                                  tuple(offs), grid, wrap, broken, links,
                                  pieces, sorted(range(len(pieces)),
                                                 key=lambda i: -sizes[i]))
                rows.append(((cand.prefix, fi, ri), cand))
        rows.sort(key=lambda r: r[0])
        self._cands[key] = [c for _, c in rows]
        return self._cands[key]

    def try_place(self, job_id: int, dims: Sequence[int]
                  ) -> Optional[Tuple[Tuple[int, ...], Dict[str, Any]]]:
        """Commit the best plan; returns (broken rings, meta) or None."""
        if _volume(dims) > self.num_xpus - self.busy:
            return None
        n3 = self.n ** 3
        free_cnt = n3 - self.occ.reshape(self.num_cubes, -1).sum(axis=1)
        empty = free_cnt == n3
        best_fit = np.lexsort((np.arange(self.num_cubes),
                               free_cnt * 2 + empty))
        # Per block: the cubes where it is free, in best-fit order.
        block_free: Dict[Tuple[Span, ...], List[int]] = {}
        best = None
        for cand in self.candidates(dims):
            if best is not None and cand.prefix != best[0].prefix:
                break
            taken: set = set()
            chosen: Dict[int, int] = {}
            for i in cand.order:
                local = cand.pieces[i][1]
                fits = block_free.get(local)
                if fits is None:
                    (x0, x1), (y0, y1), (z0, z1) = local
                    ok = ~self.occ[:, x0:x1, y0:y1, z0:z1].any(axis=(1, 2, 3))
                    fits = block_free[local] = best_fit[ok[best_fit]].tolist()
                cube = next((c for c in fits if c not in taken), None)
                if cube is None:
                    break
                chosen[i] = cube
                taken.add(cube)
            if len(chosen) < len(cand.pieces):
                continue
            fresh = int(sum(empty[c] for c in chosen.values()))
            if best is None or fresh < best[2]:
                best = (cand, chosen, fresh)
        if best is None:
            return None
        cand, chosen, fresh = best
        pieces = []
        for i, (_, local) in enumerate(cand.pieces):
            (x0, x1), (y0, y1), (z0, z1) = local
            self.occ[chosen[i], x0:x1, y0:y1, z0:z1] = True
            pieces.append((chosen[i], local))
        self.allocations[job_id] = pieces
        return cand.broken, {
            "fold": str(cand.fold), "kind": cand.fold.kind,
            "box": cand.fold.box, "cube_grid": cand.cube_grid,
            "offsets": cand.offsets, "wrap": cand.wrap,
            "broken_rings": cand.broken, "num_cubes": len(pieces),
            "ocs_links": cand.links}

    def release(self, job_id: int) -> None:
        for cube, ((x0, x1), (y0, y1), (z0, z1)) in \
                self.allocations.pop(job_id):
            self.occ[cube, x0:x1, y0:y1, z0:z1] = False

    def placed_shape(self, dims: Sequence[int]) -> List[int]:
        return [int(v) for v in dims]

    def digest_arrays(self) -> List[bytes]:
        """Occupancy, cube dedication (none), failed cells (none), OCS
        ports (all up): the allocator state's arrays, in its order."""
        return [self.occ.tobytes(),
                np.full(self.num_cubes, -1, np.int64).tobytes(),
                np.zeros_like(self.occ).tobytes(),
                np.ones(self.num_cubes, bool).tobytes()]


# -- Folding on a static torus ---------------------------------------------

class PlainFolding:
    name = "folding"

    def __init__(self, dims: Sequence[int] = (16, 16, 16)) -> None:
        self.dims: Dims = tuple(int(d) for d in dims)  # type: ignore
        self.occ = np.zeros(self.dims, bool)
        self.allocations: Dict[int, Tuple[Dims, Dims]] = {}

    @property
    def num_xpus(self) -> int:
        return self.occ.size

    @property
    def busy(self) -> int:
        return int(self.occ.sum())

    def folds(self, dims: Sequence[int]) -> List[Fold]:
        return [f for f in folds_of(dims, max(self.dims))
                if all(b <= d for b, d in zip(f.box, self.dims))]

    def can_ever_place(self, dims: Sequence[int]) -> bool:
        return bool(self.folds(dims))

    def try_place(self, job_id: int, dims: Sequence[int]
                  ) -> Optional[Tuple[Tuple[int, ...], Dict[str, Any]]]:
        origins: Dict[Dims, Optional[Dims]] = {}
        best = None
        for fold in self.folds(dims):
            if fold.box not in origins:
                fits = _FIT.multibox(self.occ[None], [fold.box])[0, 0]
                origins[fold.box] = (tuple(int(v) for v in np.unravel_index(
                    int(np.argmax(fits)), fits.shape)) if fits.any() else None)
            origin = origins[fold.box]
            if origin is None:
                continue
            broken = fold.broken([b == d for b, d in zip(fold.box,
                                                          self.dims)])
            score = (len(broken), max(fold.box))
            if best is None or score < best[0]:
                best = (score, fold, origin, broken)
        if best is None:
            return None
        _, fold, origin, broken = best
        (ox, oy, oz), (a, b, c) = origin, fold.box
        self.occ[ox:ox + a, oy:oy + b, oz:oz + c] = True
        self.allocations[job_id] = (origin, fold.box)
        return broken, {"fold": str(fold), "kind": fold.kind,
                        "box": fold.box, "origin": origin,
                        "broken_rings": broken}

    def release(self, job_id: int) -> None:
        (ox, oy, oz), (a, b, c) = self.allocations.pop(job_id)
        self.occ[ox:ox + a, oy:oy + b, oz:oz + c] = False

    def placed_shape(self, dims: Sequence[int]) -> List[int]:
        return list(_sorted_dims(dims))

    def digest_arrays(self) -> List[bytes]:
        """Occupancy, failed cells (none), cut links (none)."""
        return [self.occ.tobytes(), np.zeros_like(self.occ).tobytes(),
                b"[]"]


def make_model(policy: str, policy_kw: Dict[str, Any]):
    if policy == "rfold":
        return PlainRFold(**policy_kw)
    if policy == "folding":
        return PlainFolding(**policy_kw)
    raise KeyError(f"no plain reference for policy {policy!r}")


# -- the served daemon -----------------------------------------------------

class PlainServed:
    """The daemon's submit and done, FIFO with optional backfill."""

    def __init__(self, policy: str, policy_kw: Dict[str, Any],
                 backfill: bool) -> None:
        self.model = make_model(policy, policy_kw)
        self.backfill = bool(backfill)
        self.queue: List[Tuple[int, Dims]] = []
        self.shapes: Dict[int, Dims] = {}
        self.next_id = 0

    def _place(self, job_id: int, dims: Dims) -> Optional[Dict[str, Any]]:
        got = self.model.try_place(job_id, dims)
        if got is None:
            return None
        broken, meta = got
        self.shapes[job_id] = dims
        return {"job_id": job_id, "shape": self.model.placed_shape(dims),
                "broken_rings": list(broken), "meta": meta}

    def apply(self, op: Dict[str, Any]) -> Dict[str, Any]:
        job_id = int(op["job_id"])
        if op["op"] == "submit":
            dims = tuple(int(v) for v in op["shape"])
            self.next_id = max(self.next_id, job_id + 1)
            if not self.model.can_ever_place(dims):
                return {"ok": True, "outcome": DROPPED, "job_id": job_id}
            placed = None
            if not self.queue or self.backfill:
                placed = self._place(job_id, dims)
            if placed is None:
                self.queue.append((job_id, dims))
                return {"ok": True, "outcome": QUEUED, "job_id": job_id,
                        "queue_depth": len(self.queue)}
            return {"ok": True, "outcome": PLACED, "job_id": job_id,
                    "placement": placed}
        if op["op"] != "done":
            raise ValueError(f"no plain reference for op {op['op']!r}")
        started: List[Dict[str, Any]] = []
        if job_id in self.model.allocations:
            self.model.release(job_id)
            self.shapes.pop(job_id)
            started = self._drain()
        else:
            self.queue = [(j, s) for j, s in self.queue if j != job_id]
        return {"ok": True, "job_id": job_id, "started": started,
                "queue_depth": len(self.queue)}

    def _drain(self) -> List[Dict[str, Any]]:
        started: List[Dict[str, Any]] = []
        i = 0
        while i < len(self.queue):
            job_id, dims = self.queue[i]
            if not self.model.can_ever_place(dims):
                self.queue.pop(i)
                started.append({"job_id": job_id, "outcome": DROPPED})
                continue
            placed = self._place(job_id, dims)
            if placed is None:
                if not self.backfill:
                    break
                i += 1
                continue
            self.queue.pop(i)
            started.append({"job_id": job_id, "outcome": PLACED,
                            "placement": placed})
        return started

    def digest(self) -> str:
        """The daemon's state digest over the same state: the model's
        arrays, then allocated ids, their shapes, the queue, the next id,
        each as JSON."""
        h = hashlib.sha256()
        for blob in self.model.digest_arrays():
            h.update(blob)
        h.update(json.dumps(sorted(self.model.allocations)).encode())
        h.update(json.dumps(sorted((j, list(d))
                                   for j, d in self.shapes.items())).encode())
        h.update(json.dumps(self.queue).encode())
        h.update(str(self.next_id).encode())
        return h.hexdigest()[:16]


# -- the journal on disk ---------------------------------------------------

WAL_MAGIC = b"RPROWAL1"
_FRAME = struct.Struct("<II")            # payload length, crc32


def _wal_records(path: str) -> List[Dict[str, Any]]:
    """Intact records of a write-ahead log: after the magic, frames of
    (length u32, crc32 u32, JSON payload), up to the first torn one."""
    with open(path, "rb") as f:
        data = f.read()
    if data[:len(WAL_MAGIC)] != WAL_MAGIC:
        return []
    out, off = [], len(WAL_MAGIC)
    while off + _FRAME.size <= len(data):
        length, crc = _FRAME.unpack_from(data, off)
        payload = data[off + _FRAME.size:off + _FRAME.size + length]
        if len(payload) < length or zlib.crc32(payload) != crc:
            break
        try:
            out.append(json.loads(payload))
        except ValueError:
            break
        off += _FRAME.size + length
    return out


def _snapshot(path: str) -> List[Dict[str, Any]]:
    """A snapshot's journal, or nothing when its self-CRC (over its
    canonical JSON without the CRC) does not match."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return []
    body = {k: v for k, v in rec.items() if k != "_crc32"}
    crc = zlib.crc32(json.dumps(body, sort_keys=True, default=str).encode())
    if "_crc32" in rec and int(rec["_crc32"]) != crc:
        return []
    return list(rec.get("journal", []))


def read_journal(directory: str) -> List[Dict[str, Any]]:
    """The journal one daemon left in ``directory``: its snapshot's ops,
    then the log's records from the snapshot's length on (each carries
    its index ``i``), stopping at a gap."""
    base: List[Dict[str, Any]] = []
    wal: List[Dict[str, Any]] = []
    for root, _dirs, files in os.walk(directory):
        for name in sorted(files):
            path = os.path.join(root, name)
            if name.endswith(".json"):
                base = _snapshot(path)
            elif name.endswith(".wal"):
                wal = _wal_records(path)
    tail: List[Dict[str, Any]] = []
    for rec in wal:
        i = rec.pop("i", None)
        expected = len(base) + len(tail)
        if i is not None and i < expected:
            continue
        if i is not None and i > expected:
            break
        tail.append(rec)
    return base + tail


# -- the simulator ---------------------------------------------------------

ARRIVAL, COMPLETION = 0, 1


@dataclass
class SimJob:
    job_id: int
    arrival: float
    duration: float
    shape: Dims
    start: Optional[float] = None
    finish: Optional[float] = None
    dropped: bool = False
    meta: Dict[str, Any] = field(default_factory=dict)


def simulate(policy: str, policy_kw: Dict[str, Any], jobs: Sequence[Any],
             backfill: bool) -> Dict[str, Any]:
    """Run ``jobs`` (job_id, arrival, duration, shape) through FIFO
    admission on the plain model; returns the schedule and the summary
    an operator reads."""
    model = make_model(policy, policy_kw)
    order = sorted((SimJob(int(j.job_id), float(j.arrival),
                           float(j.duration), tuple(j.shape))
                    for j in jobs), key=lambda j: j.arrival)
    events: List[Tuple[float, int, int, SimJob]] = []
    seq = itertools.count()
    for job in order:
        heapq.heappush(events, (job.arrival, ARRIVAL, next(seq), job))
    queue: List[SimJob] = []
    failed_shapes: set = set()
    samples: List[Tuple[float, float]] = []
    head_blocked = False

    def drain(now: float) -> bool:
        i = 0
        while i < len(queue):
            job = queue[i]
            if not model.can_ever_place(job.shape):
                job.dropped = True
                queue.pop(i)
                continue
            key = _sorted_dims(job.shape)
            if backfill and key in failed_shapes:
                i += 1
                continue
            got = model.try_place(job.job_id, job.shape)
            if got is None:
                if not backfill:
                    return True
                failed_shapes.add(key)
                i += 1
                continue
            queue.pop(i)
            broken, meta = got
            job.start, job.meta = now, meta
            job.finish = now + job.duration * (BROKEN_RING_SLOWDOWN
                                               if broken else 1.0)
            heapq.heappush(events, (job.finish, COMPLETION, next(seq), job))
        return False

    while events:
        t, kind, _, job = heapq.heappop(events)
        if kind == ARRIVAL:
            queue.append(job)
            if not backfill and head_blocked and len(queue) > 1:
                samples.append((t, model.busy / model.num_xpus))
                continue
        else:
            model.release(job.job_id)
            failed_shapes.clear()
        head_blocked = drain(t)
        samples.append((t, model.busy / model.num_xpus))
    levels, cdf = utilization_cdf(samples)
    return {"schedule": [[j.job_id, j.start, j.finish, j.dropped, j.meta]
                         for j in order],
            "summary": summarize(order, samples),
            "cdf_levels": [float(x) for x in levels],
            "cdf": [float(x) for x in cdf]}


def summarize(jobs: Sequence[SimJob],
              samples: Sequence[Tuple[float, float]]) -> Dict[str, Any]:
    """JCR, JCT percentiles (50, 90, 99) and the time-weighted
    utilization's mean and percentiles (paper section 4)."""
    out: Dict[str, Any] = {"jcr": (sum(1 for j in jobs if j.start is not None)
                                   / len(jobs)) if jobs else 1.0}
    jcts = np.array([j.finish - j.arrival for j in jobs
                     if j.finish is not None], dtype=np.float64)
    for q in (50, 90, 99):
        out[f"jct_p{q}"] = (float(np.percentile(jcts, q)) if jcts.size
                            else float("nan"))
    out.update({f"util_{k}": v for k, v in _util(samples).items()})
    out["num_jobs"] = len(jobs)
    out["num_dropped"] = sum(1 for j in jobs if j.dropped)
    return out


def _util(samples: Sequence[Tuple[float, float]]) -> Dict[str, float]:
    """Utilization as a step function over the sample times."""
    if len(samples) < 2:
        return {"mean": 0.0, "p50": 0.0, "p90": 0.0}
    ts = np.array([t for t, _ in samples])
    us = np.array([u for _, u in samples])
    w, vals = np.diff(ts), us[:-1]
    keep = w > 0
    vals, w = vals[keep], w[keep]
    if not vals.size:
        m = float(us.mean())
        return {"mean": m, "p50": m, "p90": m}
    order = np.argsort(vals)
    vals, w = vals[order], w[order]
    cum = np.cumsum(w) / w.sum()
    return {"mean": float((vals * w).sum() / w.sum()),
            "p50": float(vals[np.searchsorted(cum, 0.5)]),
            "p90": float(vals[np.searchsorted(cum, 0.9)])}


def utilization_cdf(samples: Sequence[Tuple[float, float]], grid: int = 101):
    """Time-weighted CDF of utilization at ``grid`` levels in [0, 1]."""
    ts = np.array([t for t, _ in samples])
    us = np.array([u for _, u in samples])
    w, vals = np.diff(ts), us[:-1]
    levels = np.linspace(0.0, 1.0, grid)
    cdf = np.array([(w[vals <= lv]).sum() for lv in levels]) / max(
        w.sum(), 1e-12)
    return levels, cdf

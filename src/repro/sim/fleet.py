"""Fleet simulation layer: one engine, many simulators.

The eval harness runs matrices of independent seeded simulations
(runs x policies x seeds). Driven naively, each :class:`Simulator`
owns its engine call path and issues batch-1 fitmask queries — the
multi-box kernel's grid-batch axis (the ``B`` of ``(B, K, X, Y, Z)``)
never sees more than one simulator's occupancy, so the very
amortization that makes the kernel fast goes unused in production.

This module runs many simulators *concurrently inside one process* as
cooperatively-scheduled steppers and funnels their per-epoch mask work
through a shared :class:`QueryBroker`:

  * Each simulator runs on its own thread. Simulation itself is plain
    python/numpy (GIL-serialized — process pools provide CPU
    parallelism one level up, see ``repro.eval.runner``); the threads
    exist so a simulator can *block inside its placement hot path*,
    exactly at the point where it used to call the engine inline.
  * A blocked simulator's query parks in the broker. Flushes are
    **continuously scheduled** (iteration-level, in the batched-LLM-
    serving sense): a round is answered when a *quorum* of live
    steppers is parked, when *everyone* live is parked, or when the
    oldest parked query exceeds a *deadline* — the fleet never stalls
    on its slowest simulator. Queries arriving while a flush is in
    flight simply park into the next round (they are "re-queued", not
    lost), and up to ``max_inflight`` flushes may overlap: the engine
    releases the GIL (XLA runs on its own threadpool; numpy kernels
    drop it too), so overlapping flushes genuinely parallelize.
  * Coalescing rules: requests are bucketed by grid cell shape (a
    16^3 static torus never stacks with 4^3 cubes), same-bucket grids
    are concatenated on the B axis, and candidate box sets are
    unioned on K — each request gets exactly its own planes back, in
    its own box order.
  * Compiled engines see a *small, stable* set of program shapes: per
    bucket, B is padded to the fleet hint or the next power of two and
    the K axis is served from a monotone per-bucket **box table**
    (engines that compile their boxes in, see below) —
    power-of-two padded while the table is still collecting boxes,
    exact-length once it stops growing — so XLA settles on one fused
    program per bucket instead of one per distinct flush union. The
    pad/no-pad decision is made per bucket from the engine's declared
    policy (``FitmaskEngine.pads_shapes``) plus bucket-local state;
    the host numpy engine is never padded (extra grids are pure waste
    there).
  * The table is for engines that compile their boxes into the
    program (``FitmaskEngine.compiles_boxes``). An engine that reads
    its boxes as data (``pallas``) runs any box set of one padded K on
    one program, so each flush is sent its own **union**, padded to a
    power of two and indexed per flush: K stays within the few shapes
    set-up can warm, and each flush computes and copies back the
    planes it asked for rather than the whole history's — a 16^3
    static torus meets hundreds of distinct boxes in a sweep, where a
    flush needs a few dozen.

Why schedules stay byte-identical to the single-sim path: every
``multibox``/``free_counts`` answer is a pure per-grid-per-box
function of the submitted occupancy — batching concatenates inputs
and slices outputs, it never mixes grids, and which other boxes share
the K axis (a table, a union, their padding duplicates) never changes
a box's plane — so a simulator cannot observe whether its query was
answered solo, in a quorum round of three, or in a timeout round of
one, from its bucket's table or from the round's union: *which* round
answers a query changes with interleaving, but the answer bytes
cannot (parity-tested across randomized interleavings, quorum
fractions, timeout firings and both K rules in ``tests/test_fleet.py``;
the per-sim epoch caches in the torus models are untouched and keep
deduplicating queries before they ever reach the broker).

The broker implements the ``repro.core.maskquery`` client contract,
so a policy takes it as ``mask_client=`` when it is built.

Containment & failover (PR 9): the broker tolerates the two ways a
fleet dies in practice.

  * **Dead steppers** — a registered simulator thread that exits
    without deactivating (killed, or a non-Python crash) would
    otherwise pin the live count forever: quorum never forms and the
    survivors hang. When ``register`` is given the thread handle (the
    :class:`Fleet` driver always passes it), parked waiters poll on a
    bounded watchdog tick, reap threads that are no longer alive
    (``steppers_reaped``), shrink the live quorum, and deliver an
    exception to any request the dead thread left parked — a killed
    stepper can delay a flush by at most the watchdog tick, never
    hang it.
  * **Dying engines** — an engine call that raises is retried once
    (``engine_retries``); if it raises again the broker fails over
    down the ``pallas → jax → numpy`` chain
    (:data:`repro.core.engineconfig.FAILOVER_CHAIN`), adopting the
    first backend that answers (``engine_failovers`` /
    ``failover_engine``) and resetting its compiled-shape bucket
    state. The first failover of a broker raises a ``RuntimeWarning``
    carrying the original exception. The first few post-failover
    multibox flushes are canary-checked against the host numpy oracle
    (``canary_checks``/``canary_mismatches``) — answers are a pure
    function of the inputs, so any mismatch is a real defect, not
    noise. Failover applies only to registry-named engines; a custom
    engine *instance* has no registry identity, so its errors
    propagate to the waiters unchanged (the historical contract).
    :meth:`QueryBroker.inject_engine_faults` arms synthetic failures
    for drills and tests.
"""
from __future__ import annotations

import hashlib
import math
import threading
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import numpy as np

from repro import obs
from repro.core.maskquery import Box, MaskQueryClient

# Engine-aware flush deadlines (seconds): the host engine answers a
# round in a few hundred microseconds, compiled engines in a few
# milliseconds — the deadline only exists to bound the wait for a
# quorum that never forms, so it sits a little above one flush cost.
_HOST_TIMEOUT = 0.002
_COMPILED_TIMEOUT = 0.005

_FC_CACHE_CAP = 4096       # content-addressed free-count entries
_PAD_BOX: Box = (1, 1, 1)  # K filler when a bucket's table is empty

# A bucket serves pow2-padded box tables while its table is growing
# (bounding shape churn during the growth burst) and switches to the
# exact-length table once this many consecutive flushes added no box —
# the exact program compiles once (the compile cache keys on the box
# tuple) and then every steady-state flush runs at exact K, paying
# zero pad-slot arithmetic.
_STABLE_FLUSHES = 3

# Bounded wait tick (seconds) for parked waiters while stepper threads
# are being watched: the reap latency for a dead stepper, and the
# upper bound on how long one can stall a flush.
_WATCHDOG_TICK = 0.05

# Post-failover parity canary: how many multibox flushes on the
# adopted engine are cross-checked against the host numpy oracle.
_CANARY_FLUSHES = 3


def _pow2(n: int) -> int:
    """Box slots for ``n`` boxes: the next power of two."""
    return max(1, 1 << (n - 1).bit_length())


@dataclass
class BrokerStats:
    """Coalescing + scheduling counters (the fleet bench asserts
    batching really happened — ``batched_calls > 0``,
    ``mean_grids_per_call > 1`` — and reports the flush-trigger
    breakdown and padding-waste fractions)."""

    requests: int = 0          # queries submitted by simulators
    flushes: int = 0           # scheduled rounds answered
    engine_calls: int = 0      # engine invocations actually issued
    batched_calls: int = 0     # engine calls coalescing > 1 request
    grids: int = 0             # real grids stacked on the B axis
    max_grids: int = 0         # largest single-call B (real grids)
    max_coalesced: int = 0     # most requests answered by one call
    # -- continuous-scheduling breakdown --
    flush_all_parked: int = 0  # rounds triggered by everyone parked
    flush_quorum: int = 0      # rounds triggered by the quorum rule
    flush_timeout: int = 0     # rounds triggered by the deadline
    requeued: int = 0          # queries parked while a flush was live
    # -- padding accounting (compiled-engine buckets) --
    padded_grids: int = 0      # pad rows added to reach a stable B
    k_slots: int = 0           # K slots dispatched (tables, padded)
    k_needed: int = 0          # K slots actually requested
    # -- free-count fast paths --
    fc_inline: int = 0         # answered inline on the host engine
    fc_cache_hits: int = 0     # answered from the content cache
    fc_cache_misses: int = 0   # parked for a batched round
    # -- containment & failover (PR 9) --
    steppers_reaped: int = 0   # dead stepper threads reaped
    engine_retries: int = 0    # engine calls retried after an error
    engine_failovers: int = 0  # chain steps taken (engine adopted)
    canary_checks: int = 0     # post-failover flushes parity-checked
    canary_mismatches: int = 0  # canary disagreed with the host oracle
    failover_engine: Optional[str] = None  # engine currently adopted

    def record_call(self, n_requests: int, n_grids: int,
                    n_padded: int = 0) -> None:
        self.engine_calls += 1
        self.grids += n_grids
        self.padded_grids += n_padded
        self.max_grids = max(self.max_grids, n_grids)
        self.max_coalesced = max(self.max_coalesced, n_requests)
        if n_requests > 1:
            self.batched_calls += 1

    def as_dict(self) -> Dict[str, Any]:
        d = dict(self.__dict__)
        d["mean_grids_per_call"] = (
            round(self.grids / self.engine_calls, 2)
            if self.engine_calls else None)
        total_b = self.grids + self.padded_grids
        d["b_pad_waste"] = (round(self.padded_grids / total_b, 4)
                            if total_b else 0.0)
        d["k_pad_waste"] = (round(1.0 - self.k_needed / self.k_slots, 4)
                            if self.k_slots else 0.0)
        return d


class _Request:
    __slots__ = ("kind", "occ", "boxes", "result", "error", "done", "t",
                 "owner", "trigger")

    def __init__(self, kind: str, occ: np.ndarray,
                 boxes: Optional[Tuple[Box, ...]] = None):
        self.kind = kind
        self.occ = occ
        self.boxes = boxes
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.t = time.monotonic()
        # The submitting thread: lets the watchdog error out requests
        # a dead stepper left parked.
        self.owner = threading.current_thread()
        # What flushed the round that answered it (set while recording).
        self.trigger: Optional[str] = None


class _Bucket:
    """Per-cell-shape flush state (compiled engines only): the monotone
    box table K answers are served from, and the largest padded B this
    bucket has dispatched (its stable batch shape)."""

    __slots__ = ("table", "index", "b_target", "since_growth")

    def __init__(self) -> None:
        self.table: List[Box] = []
        self.index: Dict[Box, int] = {}
        self.b_target = 0
        self.since_growth = 0  # flushes since the table last grew


class QueryBroker(MaskQueryClient):
    """Coalesces mask queries from concurrently running simulators
    into batched engine calls, scheduled continuously.

    Implements the :class:`~repro.core.maskquery.MaskQueryClient`
    contract, so a torus submits work to it exactly as it would to an
    inline client — the submitting thread just blocks until its round
    is answered. With no registered simulators (or only one live), a
    request flushes immediately: a broker is safe to use solo.

    ``engine`` is a registry name (``numpy``/``jax``/``pallas``/
    ``ref``), an engine instance, or ``None`` for ``numpy`` — note the fleet path always rides an *engine*, there is no
    brokered variant of the in-torus host integral-image path (the
    numpy engine is the same arithmetic, batched).

    Flush policy — a parked round is answered when the first of these
    fires (the trigger breakdown lands in :class:`BrokerStats`):

      * **all parked**: every live stepper is waiting (the classic
        cooperative barrier; also fired by :meth:`deactivate`);
      * **quorum**: at least ``max(2, ceil(quorum * live))`` steppers
        are waiting. ``quorum=1.0`` (the default here) degenerates to
        the barrier; fleets run ``quorum < 1`` so a round never waits
        on its slowest member. ``quorum=0`` is *drain mode*: any
        parked query flushes the moment an inflight slot is free —
        batching arises from queries parking behind a live flush, not
        from timed waiting (the host-engine policy: one engine pass
        is so cheap that waiting on a timer always loses);
      * **timeout**: the oldest parked query is older than ``timeout``
        seconds (``None`` disables the deadline).

    Latecomers that park while a flush is in flight join the next
    round; up to ``max_inflight`` rounds may be answered concurrently
    (engine calls release the GIL).

    ``pad_b="auto"`` defers to the engine's ``pads_shapes`` policy:
    compiled engines get per-bucket stable shapes — B padded up to the
    fleet hint / bucket high-water power of two, K served from the
    bucket's padded box table or the flush's padded union — while the
    host engine always sees exact shapes. Padding rows and spare K
    slots are sliced off before answers are handed back, so results
    are unchanged.
    """

    def __init__(self, engine=None, quorum: Optional[float] = 1.0,
                 timeout: Optional[float] = None, pad_b="auto",
                 max_inflight: int = 2):
        from repro.kernels.fitmask import ops
        if hasattr(engine, "multibox"):
            # Custom instance: no registry identity — never failed over.
            self.engine = engine
            self.engine_name: Optional[str] = None
        else:
            self.engine_name = ops.engine_name(engine)
            self.engine = ops.get_engine(self.engine_name)
        self._pad_auto = pad_b == "auto"
        self.pad_b = (bool(getattr(self.engine, "pads_shapes", False))
                      if self._pad_auto else bool(pad_b))
        self.quorum = quorum
        self.timeout = timeout
        self.max_inflight = max(1, int(max_inflight))
        self._host_free = bool(getattr(self.engine, "host_free", False))
        # Mirror the engine's host-ness on the client contract so
        # toruses can pick lazy (host) vs prefetch-all-seen (compiled)
        # mask strategies without reaching through the broker.
        self.host_free = self._host_free
        # With a hint (the fleet sets its simulator count), batches at
        # or below it pad exactly to it: single-grid-per-sim rounds —
        # the whole static-torus side — then share ONE compiled shape.
        # The *effective* hint shrinks with the live population (a
        # fleet of 8 down to 3 survivors pads to 3, not 8).
        self.pad_hint: Optional[int] = None
        self._lock = threading.Lock()
        self._active = 0
        self._pending: List[_Request] = []
        self._inflight = 0
        self._buckets: Dict[Tuple[int, ...], _Bucket] = {}
        self._fc_cache: "OrderedDict[bytes, np.ndarray]" = OrderedDict()
        # Containment & failover state (PR 9).
        self._watched: List[threading.Thread] = []  # stepper threads
        self._faults_left = 0        # armed synthetic engine failures
        self._canary_left = 0        # post-failover parity checks due
        self._failover_warned = False
        self.stats = BrokerStats()

    # -- simulator lifecycle ------------------------------------------
    def register(self, thread: Optional[threading.Thread] = None) -> None:
        """Declare one more live simulator (call before it starts).
        With ``thread``, the watchdog tracks it: if it dies without
        deactivating, parked waiters reap it, shrink the quorum and
        error out any requests it left behind."""
        with self._lock:
            self._active += 1
            if thread is not None:
                self._watched.append(thread)

    def deactivate(self) -> None:
        """A simulator finished (or died): it submits no further
        queries. If the survivors' round is now ready (all parked, or
        quorum/deadline met), flush it — nobody else may trigger it."""
        cur = threading.current_thread()
        with self._lock:
            self._active -= 1
            # A clean exit from a watched thread unwatches it — the
            # watchdog must not double-decrement when it later dies.
            if cur in self._watched:
                self._watched.remove(cur)
            round_ = self._take_round_locked(deadline_ok=True)
        if round_ is not None:
            self._lead(*round_)

    def _reap_locked(self) -> bool:
        """Reap watched threads that died without deactivating: shrink
        the live count (so quorum/all-parked reflect survivors only)
        and deliver an exception to any request they left parked.
        Returns True when anything was reaped."""
        dead = [t for t in self._watched
                if t.ident is not None and not t.is_alive()]
        for t in dead:
            self._watched.remove(t)
            self._active -= 1
            self.stats.steppers_reaped += 1
            for r in [r for r in self._pending if r.owner is t]:
                self._pending.remove(r)
                r.error = RuntimeError(
                    f"stepper thread {t.name!r} died with this query "
                    "parked")
                r.done.set()
        return bool(dead)

    # -- MaskQueryClient contract -------------------------------------
    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        boxes = tuple(tuple(int(v) for v in b) for b in boxes)
        return self._submit(_Request("multibox", np.asarray(occ), boxes))

    def free_counts(self, occ) -> np.ndarray:
        occ = np.asarray(occ)
        if occ.ndim != 4:
            raise ValueError("broker expects (B, X, Y, Z) occupancy, "
                             f"got shape {occ.shape}")
        if self._host_free:
            # Host reduction: cheaper than a park/flush round-trip.
            out = np.asarray(self.engine.free_counts(occ))
            with self._lock:
                self.stats.requests += 1
                self.stats.fc_inline += 1
                self.stats.record_call(1, occ.shape[0])
            return out.astype(np.int64)
        key = self._fc_key(occ)
        with self._lock:
            hit = self._fc_cache.get(key)
            if hit is not None:
                self._fc_cache.move_to_end(key)
                self.stats.requests += 1
                self.stats.fc_cache_hits += 1
                return hit.copy()
            self.stats.fc_cache_misses += 1
        return self._submit(_Request("free_counts", occ))

    @staticmethod
    def _fc_key(occ: np.ndarray) -> bytes:
        h = hashlib.blake2b(digest_size=16)
        h.update(repr(occ.shape).encode())
        h.update(np.ascontiguousarray(occ))
        return h.digest()

    def _submit(self, req: _Request) -> np.ndarray:
        if req.occ.ndim != 4:
            raise ValueError("broker expects (B, X, Y, Z) occupancy, "
                             f"got shape {req.occ.shape}")
        # From parking to answered; the rounds this thread leads
        # meanwhile are its ``broker.flush`` children.
        with obs.span("broker.wait") as sp:
            with self._lock:
                self._pending.append(req)
                self.stats.requests += 1
                if self._inflight:
                    self.stats.requeued += 1
                round_ = self._take_round_locked(deadline_ok=False)
            if round_ is not None:
                self._lead(*round_)
            # Park until answered; on each deadline tick, check whether
            # a waiting round (possibly ours, possibly a successor
            # round) is now flushable and lead it if so. With watched
            # stepper threads the tick is bounded by the watchdog
            # period, so a killed stepper delays a flush by at most
            # _WATCHDOG_TICK — it can never hang the broker.
            while not req.done.wait(self._wait_tick()):
                with self._lock:
                    self._reap_locked()
                    round_ = self._take_round_locked(deadline_ok=True)
                if round_ is not None:
                    self._lead(*round_)
            if sp.recording:
                sp.tag(kind=req.kind, grids=req.occ.shape[0],
                       trigger=req.trigger)
        if req.error is not None:
            raise req.error
        assert req.result is not None
        return req.result

    def _wait_tick(self) -> Optional[float]:
        """Parked-waiter wakeup period: the flush deadline, bounded by
        the watchdog tick while stepper threads are being watched
        (``None`` — wait forever — only when neither applies)."""
        if self._watched:
            return (_WATCHDOG_TICK if self.timeout is None
                    else min(self.timeout, _WATCHDOG_TICK))
        return self.timeout

    # -- continuous scheduling ----------------------------------------
    def _take_round_locked(
            self, deadline_ok: bool) -> Optional[Tuple[List[_Request], str]]:
        """Decide (under the lock) whether a round flushes now; if so,
        claim the batch and an inflight slot and return it with what
        triggered it (``all_parked``, ``quorum`` or ``timeout``). The
        caller answers it outside the lock."""
        n = len(self._pending)
        if not n or self._inflight >= self.max_inflight:
            return None
        active = self._active
        if active <= 0 or n >= active:
            self.stats.flush_all_parked += 1
            trigger = "all_parked"
        elif (self.quorum is not None and self.quorum < 1.0
              and n >= max(1 if self.quorum <= 0.0 else 2,
                           math.ceil(self.quorum * active))):
            # quorum=0 is *drain mode*: any parked query flushes the
            # moment an inflight slot is free — batching arises from
            # queries that park while a flush is live, not from timed
            # waiting (the right trade when one engine pass is cheap).
            self.stats.flush_quorum += 1
            trigger = "quorum"
        elif (deadline_ok and self.timeout is not None
              and time.monotonic() - self._pending[0].t >= self.timeout):
            self.stats.flush_timeout += 1
            trigger = "timeout"
        else:
            return None
        batch, self._pending = self._pending, []
        self._inflight += 1
        self.stats.flushes += 1
        return batch, trigger

    def _lead(self, batch: List[_Request], trigger: str) -> None:
        """Answer rounds until none is ready: the leader that finishes
        a flush immediately chains into any round that became flushable
        while it was computing (its own waiters were woken the moment
        their results landed)."""
        while True:
            with obs.span("broker.flush") as sp:
                plans: List[Tuple[str, int, int]] = []
                try:
                    plans = self._answer(batch)
                except BaseException as e:  # noqa: BLE001 — must wake waiters
                    for r in batch:
                        if r.result is None and r.error is None:
                            r.error = e
                if sp.recording:
                    sp.tag(trigger=trigger, requests=len(batch),
                           grids=sum(r.occ.shape[0] for r in batch))
                    if plans:
                        sp.tag(rule="+".join(sorted({p[0] for p in plans})),
                               k_served=sum(p[1] for p in plans),
                               k_needed=sum(p[2] for p in plans))
                    for r in batch:
                        r.trigger = trigger
            for r in batch:
                r.done.set()
            with self._lock:
                self._inflight -= 1
                round_ = self._take_round_locked(deadline_ok=True)
            if round_ is None:
                return
            batch, trigger = round_

    # -- coalescing ----------------------------------------------------
    def _answer(self, batch: List[_Request]) -> List[Tuple[str, int, int]]:
        """Answer a round; returns the K plan of each multibox bucket
        (rule, box slots served, box slots needed)."""
        plans = []
        for kind in ("multibox", "free_counts"):
            reqs = [r for r in batch if r.kind == kind]
            # Bucket by grid cell shape: only same-shape grids can
            # share an engine pass.
            by_cell: Dict[Tuple[int, ...], List[_Request]] = {}
            for r in reqs:
                by_cell.setdefault(r.occ.shape[1:], []).append(r)
            for cell, group in by_cell.items():
                if kind == "multibox":
                    plans.append(self._answer_multibox(cell, group))
                else:
                    self._answer_free_counts(cell, group)
        return plans

    # Per-bucket padding plan: the decision is bucket-local, not
    # engine-global — each bucket tracks its own stable B target (the
    # fleet hint capped by the live population, or its high-water
    # power of two) and its own box table.
    def _pad_target_locked(self, bucket: _Bucket, b: int) -> int:
        hint = self.pad_hint
        if hint and self._active > 0:
            hint = min(hint, self._active)
        if hint and b <= hint:
            target = hint
        else:
            target = 1 << (b - 1).bit_length()   # next power of two
        # Never shrink below the bucket's high-water shape while the
        # population is steady: reusing the compiled program beats
        # saving a pad row or two.
        if bucket.b_target >= target and (
                not hint or bucket.b_target <= max(hint, target)):
            target = bucket.b_target
        bucket.b_target = target
        return target

    def _stack(self, cell: Tuple[int, ...],
               group: List[_Request]) -> Tuple[np.ndarray, int, int]:
        """Concatenate a bucket's grids on B; returns (stacked, real_b,
        pad_rows). Compiled engines get the bucket's stable padded B."""
        occs = [r.occ for r in group]
        b = sum(o.shape[0] for o in occs)
        pad = 0
        if self.pad_b:
            with self._lock:
                bucket = self._buckets.setdefault(cell, _Bucket())
                target = self._pad_target_locked(bucket, b)
            if target > b:
                pad = target - b
                occs.append(np.zeros((pad,) + occs[0].shape[1:],
                                     dtype=occs[0].dtype))
        if len(occs) == 1:
            return occs[0], b, pad
        return np.concatenate(occs, axis=0), b, pad

    def _boxes_for(self, cell: Tuple[int, ...], needed: Tuple[Box, ...]
                   ) -> Tuple[Tuple[Box, ...], Dict[Box, int], str]:
        """K plan for one flush: the boxes to send, each needed box's
        index among them, and the rule that chose them (``table`` or
        ``union``). Host engines get exactly the needed union. Engines
        that read boxes as data get the union padded to a power of two
        with a duplicate, indexed per flush. Engines that compile their
        boxes in (``compiles_boxes``) are served from the bucket's
        monotone box table: power-of-two padded while the table is
        growing (spare slots filled with a *duplicate* of an existing
        box, which the fused program's trace-time dedup makes nearly
        free), then exact-length once the table has been stable for
        ``_STABLE_FLUSHES`` flushes — the steady state is one compiled
        program at exact K, reused for every flush."""
        kidx = {b: k for k, b in enumerate(needed)}
        if not self.pad_b:
            return needed, kidx, "union"
        with self._lock:
            if not getattr(self.engine, "compiles_boxes", False):
                filler = needed[0] if needed else _PAD_BOX
                boxes = needed + (filler,) * (_pow2(len(needed))
                                              - len(needed))
                rule = "union"
            else:
                bucket = self._buckets.setdefault(cell, _Bucket())
                before = len(bucket.table)
                for b in needed:
                    if b not in bucket.index:
                        bucket.index[b] = len(bucket.table)
                        bucket.table.append(b)
                if len(bucket.table) != before:
                    bucket.since_growth = 0
                else:
                    bucket.since_growth += 1
                boxes = tuple(bucket.table)
                if bucket.since_growth < _STABLE_FLUSHES:
                    filler = boxes[0] if boxes else _PAD_BOX
                    boxes = boxes + (filler,) * (_pow2(len(boxes))
                                                 - len(boxes))
                kidx = dict(bucket.index)
                rule = "table"
            self.stats.k_slots += len(boxes)
            self.stats.k_needed += len(needed)
        return boxes, kidx, rule

    # -- engine dispatch: retry, failover, canary ---------------------
    def inject_engine_faults(self, n: int) -> None:
        """Arm ``n`` synthetic engine failures (chaos drills / tests):
        the next ``n`` raw engine invocations raise. Two faults walk
        the full retry-then-failover path; more walk further down the
        chain."""
        with self._lock:
            self._faults_left = int(n)

    def _dispatch_engine(self, kind: str, occ: np.ndarray,
                         boxes: Optional[Tuple[Box, ...]] = None):
        """One raw invocation on the *current* engine — resolved per
        call, because failover swaps the engine underneath inflight
        flushes. Armed synthetic faults fire here, upstream of the
        real engine, so they exercise the identical recovery path."""
        with self._lock:
            if self._faults_left > 0:
                self._faults_left -= 1
                raise RuntimeError("injected engine fault")
        if kind == "multibox":
            fn = getattr(self.engine, "multibox_bucketed", None)
            if fn is not None:
                planes, free = fn(occ, boxes)
                return np.asarray(planes), np.asarray(free)
            return np.asarray(self.engine.multibox(occ, boxes)), None
        return np.asarray(self.engine.free_counts(occ)).astype(np.int64)

    def _failover_names(self) -> Tuple[str, ...]:
        if self.engine_name is None:
            return ()  # custom instance: errors propagate unchanged
        from repro.core.engineconfig import failover_candidates
        return failover_candidates(self.engine_name)

    def _adopt_engine(self, name: str) -> bool:
        """Switch to ``name`` after the current engine failed its
        retry. Compiled-shape bucket state is engine-specific and is
        dropped; the pad policy re-derives when it was ``"auto"``.
        Returns False when the backend cannot even be constructed
        (runtime not installed) — the chain just moves on."""
        from repro.kernels.fitmask import ops
        try:
            eng = ops.get_engine(name)
        except Exception:  # noqa: BLE001 — any backend boot failure
            return False
        with self._lock:
            self.engine = eng
            self.engine_name = name
            self._host_free = bool(getattr(eng, "host_free", False))
            self.host_free = self._host_free
            if self._pad_auto:
                self.pad_b = bool(getattr(eng, "pads_shapes", False))
            self._buckets = {}
            self._canary_left = _CANARY_FLUSHES
            self.stats.engine_failovers += 1
            self.stats.failover_engine = name
        return True

    def _warn_failover(self, exc: BaseException,
                       names: Tuple[str, ...]) -> None:
        """Say, once per broker, that the configured engine is being
        left and why: a failover keeps the answers right, but it also
        moves the work off the device, which the counters alone do not
        make visible to whoever reads the run's output."""
        with self._lock:
            if self._failover_warned:
                return
            self._failover_warned = True
            name = self.engine_name
        warnings.warn(
            f"fitmask engine {name!r} failed its retry with "
            f"{type(exc).__name__}: {exc}; the query broker fails over "
            f"to {' -> '.join(names)}", RuntimeWarning, stacklevel=3)

    def _engine_call(self, kind: str, occ: np.ndarray,
                     boxes: Optional[Tuple[Box, ...]] = None):
        """Engine invocation with containment: retry once on the same
        engine, then fail over down the chain; raises the last error
        only when the numpy floor itself failed (or the engine has no
        registry identity)."""
        last: Optional[BaseException] = None
        for attempt in range(2):
            try:
                return self._dispatch_engine(kind, occ, boxes)
            except Exception as e:  # noqa: BLE001 — contained below
                last = e
                if attempt == 0:
                    with self._lock:
                        self.stats.engine_retries += 1
        names = self._failover_names()
        if names:
            self._warn_failover(last, names)
        for name in names:
            if not self._adopt_engine(name):
                continue
            try:
                return self._dispatch_engine(kind, occ, boxes)
            except Exception as e:  # noqa: BLE001 — keep walking
                last = e
        assert last is not None
        raise last

    def _maybe_canary(self, occ: np.ndarray, boxes: Tuple[Box, ...],
                      planes: np.ndarray) -> None:
        """Parity-check the first few post-failover flushes against
        the host numpy oracle. Engines agree on the fit *mask* (the
        nonzero pattern), so that is what is compared; any mismatch is
        a real defect — answers are pure functions of the inputs."""
        take = False
        with self._lock:
            if self._canary_left > 0 and self.engine_name != "numpy":
                self._canary_left -= 1
                take = True
        if not take:
            return
        from repro.kernels.fitmask import ops
        ref = np.asarray(ops.get_engine("numpy").multibox(occ, boxes))
        ok = np.array_equal(np.asarray(planes) != 0, ref != 0)
        with self._lock:
            self.stats.canary_checks += 1
            if not ok:
                self.stats.canary_mismatches += 1

    def _answer_multibox(self, cell: Tuple[int, ...],
                         group: List[_Request]) -> Tuple[str, int, int]:
        """Answer one bucket's multibox requests in one engine call;
        returns the K rule with the box slots served and needed."""
        union = tuple(sorted({b for r in group for b in r.boxes}))
        boxes, kidx, rule = self._boxes_for(cell, union)
        occ, real_b, pad = self._stack(cell, group)
        planes, free = self._engine_call("multibox", occ, boxes)
        self._maybe_canary(occ, boxes, planes)
        with self._lock:
            self.stats.record_call(len(group), real_b, pad)
        lo = 0
        fc_entries = []
        for r in group:
            hi = lo + r.occ.shape[0]
            sub = planes[lo:hi]
            perm = [kidx[b] for b in r.boxes]
            if perm != list(range(sub.shape[1])):
                sub = sub[:, perm]
            r.result = sub
            if free is not None and not self._host_free:
                fc_entries.append((self._fc_key(r.occ),
                                   free[lo:hi].astype(np.int64)))
            lo = hi
        if fc_entries:
            # The fused program computed free counts anyway; remember
            # them so a follow-up free_counts on the same occupancy is
            # answered without parking.
            with self._lock:
                for key, val in fc_entries:
                    self._fc_cache[key] = val
                    self._fc_cache.move_to_end(key)
                while len(self._fc_cache) > _FC_CACHE_CAP:
                    self._fc_cache.popitem(last=False)
        return rule, len(boxes), len(union)

    def _answer_free_counts(self, cell: Tuple[int, ...],
                            group: List[_Request]) -> None:
        occ, real_b, pad = self._stack(cell, group)
        out = self._engine_call("free_counts", occ)
        with self._lock:
            self.stats.record_call(len(group), real_b, pad)
        lo = 0
        for r in group:
            hi = lo + r.occ.shape[0]
            r.result = out[lo:hi]
            lo = hi


class Fleet:
    """Run a set of simulation units concurrently, sharing one broker.

    Each *unit* is a callable receiving the broker (build your policy
    with ``mask_client=broker``, then run the simulation)
    and returning an arbitrary result. Units run on daemon threads and
    are registered with the broker *before* any of them starts, so the
    first scheduled round already coalesces across the whole fleet.

    ``quorum``/``timeout``/``max_inflight`` default to ``"auto"`` /
    ``None``, which resolve engine-aware. The host engine gets drain
    mode (``quorum=0``, one inflight lane): its rounds are never
    padded and one engine pass is nearly free, so any parked query
    flushes as soon as the engine is idle and batching arises from
    queries parking behind the live flush — timed waiting on a cheap
    engine only ever stalls mismatched-pace fleets. Compiled engines
    keep the full barrier quorum with two inflight lanes (a
    quorum-split round is padded back up to the stable batch shape,
    doubling arithmetic for no latency win — bigger B per dispatch is
    what amortizes their overhead) plus a ~5 ms deadline: it is the
    deadline, not the quorum, that makes compiled fleets
    *continuously* scheduled — a straggler can delay a round by at
    most the timeout. Pass ``quorum=1.0, timeout=None`` for the
    strict all-parked barrier.

    ``run`` returns per-unit results in input order; the first unit
    exception (if any) is re-raised after every thread has stopped —
    a dying simulator deactivates itself, so survivors keep batching
    among themselves rather than deadlocking.
    """

    def __init__(self, engine=None, quorum="auto", timeout="auto",
                 max_inflight: Optional[int] = None):
        from repro.core.engineconfig import EngineConfig
        from repro.kernels.fitmask import ops
        if isinstance(engine, EngineConfig):
            # One typed value carries both backend and flush policy;
            # explicit kwargs (non-"auto") still win over its fields.
            if quorum == "auto":
                quorum = engine.quorum
            if timeout == "auto":
                timeout = engine.timeout
            if max_inflight is None:
                max_inflight = engine.max_inflight
            engine = engine.resolve_name()
        eng = (engine if hasattr(engine, "multibox")
               else ops.get_engine(engine))
        host = bool(getattr(eng, "host_free", False))
        if quorum == "auto":
            quorum = 0.0 if host else 1.0
        if timeout == "auto":
            timeout = _HOST_TIMEOUT if host else _COMPILED_TIMEOUT
        if max_inflight is None:
            # Host drain mode wants exactly one engine lane: queries
            # park behind the live flush and drain as one batch.
            # Compiled engines overlap two (dispatch releases the GIL).
            max_inflight = 1 if host else 2
        # Pass the *spec* (name/None/instance), not the resolved
        # singleton: a registry name gives the broker the identity the
        # failover chain keys on; an instance stays failover-exempt.
        self.broker = QueryBroker(engine, quorum=quorum, timeout=timeout,
                                  max_inflight=max_inflight)

    def run(self, units: Sequence[Callable[[QueryBroker], Any]]) -> List[Any]:
        results: List[Any] = [None] * len(units)
        errors: List[Optional[BaseException]] = [None] * len(units)
        broker = self.broker

        def work(i: int, unit: Callable[[QueryBroker], Any]) -> None:
            try:
                results[i] = unit(broker)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors[i] = e
            finally:
                broker.deactivate()

        threads = [threading.Thread(target=work, args=(i, u), daemon=True)
                   for i, u in enumerate(units)]
        # Register with the thread handles *before* any unit starts:
        # the first round coalesces across the whole fleet, and the
        # watchdog can reap a unit that dies without deactivating.
        for t in threads:
            broker.register(thread=t)
        if broker.pad_hint is None:
            broker.pad_hint = len(units)
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e
        return results

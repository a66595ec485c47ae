"""Pluggable fitmask engine layer.

Every placement policy reduces to the same primitive — "for each origin
of each grid, does box k fit in free space?" — so the engines live
behind one registry and the allocator picks at runtime:

  * ``numpy``  — batched integral-image window sums on the host
    (`repro.core.fitmask`). The simulator's default and the parity
    oracle for everything else. **Pure numpy**: no jax call, no device
    round-trip (tested).
  * ``jax``    — the same algorithm as jitted XLA ops; the CPU/GPU
    accelerator path and the apples-to-apples baseline for the kernel.
  * ``pallas`` — the Pallas TPU kernel: one VMEM integral-image pass
    per grid answering all K candidate boxes
    (`kernel.fitmask_multibox`); interpreted on the CPU backend. The
    same program returns each grid's occupied-cell count, so a
    ``free_counts`` that follows a ``multibox`` on the same occupancy
    is answered without a second device call.
  * ``ref``    — `jax.lax.reduce_window` oracle.

Selection: :func:`get_engine` is the one place a name becomes an
engine; an unnamed selection means ``numpy`` and an unknown name raises
``KeyError``. All engines share the contract
``multibox(occ, boxes) -> (B, K, X, Y, Z) int32`` with every plane
padded to the full grid (0 where the box overhangs or cannot fit), so
callers never special-case engine, K, or infeasible boxes — plus
``free_counts(occ) -> (B,)`` (free cells per grid), which the
reconfigurable torus uses for best-fit cube ordering so accelerator
runs never rebuild the host integral image. The ``jax`` and ``pallas``
engines answer with host numpy arrays, copied back inside the engine,
so that a profiled run can split each device call into launch, wait
and copy (:func:`_on_device`, ``repro.obs``).
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Optional, Sequence, Tuple, Type

import numpy as np

from repro import obs
from repro.core import fitmask as np_engine

Box = Tuple[int, int, int]

# Compile-cache caps. Per-box window programs and per-bucket fused
# programs are cached per distinct key; a long multi-shape sweep keeps
# minting new keys, so the caches are LRU-bounded rather than unbounded
# ``functools.cache`` (evicting a program only costs a re-jit if the
# shape ever comes back — it cannot change results).
WINDOW_CACHE_SIZE = 256   # distinct boxes (allocator candidate sets)
BUCKET_CACHE_SIZE = 64    # distinct fused (box-table, grid) programs


def _canon_boxes(boxes: Sequence[Box]) -> Tuple[Box, ...]:
    return tuple(tuple(int(v) for v in b) for b in boxes)  # type: ignore


def _window_fits(ii, box: Box):
    """Cropped (..., X-a+1, Y-b+1, Z-c+1) int32 fit mask for one box
    from a prebuilt integral image over the trailing 3 axes (leading
    axes are batch dims). Nested per-axis differencing — three
    slice-subtractions — is algebraically the 8-corner
    inclusion/exclusion at less than half the op count."""
    a, b, c = box
    s = ii[..., a:, :, :] - ii[..., :-a, :, :]
    s = s[..., b:, :] - s[..., :-b, :]
    s = s[..., c:] - s[..., :-c]
    return (s == 0).astype(np.int32)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _on_device(engine: "FitmaskEngine", kind: str, inputs: tuple, launch,
               **shape):
    """Run one device call and copy its answer to the host.

    ``launch()`` dispatches the program and returns its device
    array(s); the answer comes back as numpy, one array or a tuple as
    launched. ``inputs`` are the call's input shapes, so the
    ``engine.call`` span can say whether this engine is called with
    them for ``kind`` for the first time (``new_shape``): such a call
    compiles its program or loads it from the compile cache. Spans:
    ``engine.call`` around ``engine.launch`` (padding already done,
    host-to-device copy, dispatch), ``engine.wait`` (the device's own
    time, waited for only while recording) and ``engine.fetch`` (the
    device-to-host copy, tagged with its bytes). ``shape`` (real and
    padded B and K) goes on ``engine.call``. With nothing recording,
    this is the launch and one ``np.asarray`` per array."""
    program = (kind, inputs)
    new = program not in engine._programs
    if new:
        engine._programs.add(program)
    with obs.span("engine.call") as call:
        with obs.span("engine.launch"):
            out = launch()
        if call.recording:
            import jax
            with obs.span("engine.wait"):
                jax.block_until_ready(out)
        with obs.span("engine.fetch") as fetch:
            if isinstance(out, tuple):
                # Start every copy before waiting on any.
                for o in out:
                    o.copy_to_host_async()
                host = tuple(np.asarray(o) for o in out)
                nbytes = sum(h.nbytes for h in host)
            else:
                host = np.asarray(out)
                nbytes = host.nbytes
            if fetch.recording:
                fetch.tag(bytes=nbytes)
        if call.recording:
            call.tag(engine=engine.name, kind=kind, new_shape=new, **shape)
    return host


class FitmaskEngine:
    """One fitmask backend. Subclasses implement :meth:`multibox` and
    :meth:`free_counts`; :meth:`fitmask` is the single-box convenience
    on top of :meth:`multibox`.

    Two capability flags drive the fleet broker's per-bucket padding
    policy (``repro.sim.fleet``):

    ``pads_shapes``
        True for compiled backends, where every distinct (B, K) input
        shape traces/compiles a fresh XLA program — the broker then
        pads flushes to a small set of bucketed shapes. False for the
        host engine, where padding is pure wasted arithmetic.
    ``host_free``
        True when ``free_counts`` is a cheap host reduction that is
        faster answered inline than coalesced through a broker round.
    ``compiles_boxes``
        True when the box set is compiled into the program, so that
        only a monotone per-bucket box table keeps the broker's flushes
        to a few programs. False for an engine that reads its boxes as
        data: the broker sends each flush its own union.
    """

    name = "base"
    pads_shapes = False
    host_free = False
    compiles_boxes = False

    def __init__(self) -> None:
        # Input shapes of the device programs called so far.
        self._programs: set = set()

    def multibox(self, occ, boxes: Sequence[Box]):
        """(B, X, Y, Z) x K boxes -> (B, K, X, Y, Z) int32."""
        raise NotImplementedError

    def free_counts(self, occ):
        """Free-cell count per grid: (B, X, Y, Z) -> (B,) int. The
        reconfigurable torus orders cubes best-fit by this every
        occupancy epoch; engines answer it natively so accelerator runs
        never rebuild the host integral image (ROADMAP item)."""
        raise NotImplementedError

    def multibox_bucketed(self, occ, boxes: Sequence[Box]):
        """The fleet broker's flush entry: one engine pass answering
        all K boxes AND the per-grid free counts together, as
        ``(planes, free)`` — planes (B, K, X, Y, Z), *nonzero where
        the box fits* (any integer/bool dtype; the classic
        :meth:`multibox` int32 contract is one valid encoding), free
        (B,) integer. Engines with a fused program override this so a
        flush is a single dispatch; the default is the two classic
        calls, so every engine is broker-servable (one dispatch on the
        ``pallas`` engine, whose ``free_counts`` reuses the counts its
        ``multibox`` brought back)."""
        return self.multibox(occ, boxes), self.free_counts(occ)

    def fitmask(self, occ, box: Box):
        """(B, X, Y, Z) -> (B, X, Y, Z) int32 for one box."""
        return self.multibox(occ, (box,))[:, 0]


class NumpyEngine(FitmaskEngine):
    """Host integral-image engine — the sim hot path and the oracle
    arbiter. Deliberately references no jax symbol: results stay numpy
    unless the caller converts (regression-tested).

    ``multibox`` runs the genuinely batched (B, K) vectorized form
    (``fit_mask_multi_fast``: one stacked int16 integral image, nested
    per-axis differencing, no per-grid python loop); the straight-line
    ``fit_mask_multi`` is retained in ``repro.core.fitmask`` as its
    parity oracle."""

    name = "numpy"
    host_free = True

    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        return np_engine.fit_mask_multi_fast(np.asarray(occ),
                                             _canon_boxes(boxes))[0]

    def multibox_bucketed(self, occ, boxes: Sequence[Box]):
        masks, free = np_engine.fit_mask_multi_fast(
            np.asarray(occ), _canon_boxes(boxes), out_dtype=bool)
        return masks, free

    def free_counts(self, occ) -> np.ndarray:
        return np_engine.free_counts(np.asarray(occ))


class JaxEngine(FitmaskEngine):
    """Jitted XLA ops (no Pallas): the shared-integral-image algorithm,
    batched over grids. The integral image jits once per grid shape and
    each distinct box jits one small window-extraction program — so
    when the allocator's candidate set grows by a box, only that box
    compiles (a single K-static program would recompile the whole,
    ever-larger, unrolled loop on every growth).

    The fleet broker instead calls :meth:`multibox_bucketed`, whose
    box set is a *stable padded table* (one per bucket): there the
    whole-table fused single-dispatch program wins, because it is
    compiled once and re-run for every flush of the bucket."""

    name = "jax"
    pads_shapes = True
    compiles_boxes = True

    @staticmethod
    @functools.cache
    def _ii_fn():
        import jax
        import jax.numpy as jnp

        def ii(occ):
            acc = jnp.pad(occ.astype(jnp.int32),
                          ((0, 0), (1, 0), (1, 0), (1, 0)))
            for ax in (1, 2, 3):
                acc = jnp.cumsum(acc, axis=ax)
            return acc

        return jax.jit(ii)

    @staticmethod
    @functools.lru_cache(maxsize=WINDOW_CACHE_SIZE)
    def _window_fn(box: Box):
        import jax
        import jax.numpy as jnp
        a, b, c = box

        def window(ii):
            bsz = ii.shape[0]
            x, y, z = (d - 1 for d in ii.shape[1:])
            if a > x or b > y or c > z:
                return jnp.zeros((bsz, x, y, z), jnp.int32)
            fits = _window_fits(ii, box)
            out = jnp.zeros((bsz, x, y, z), jnp.int32)
            return jax.lax.dynamic_update_slice(out, fits, (0, 0, 0, 0))

        return jax.jit(window)

    def multibox(self, occ, boxes: Sequence[Box]):
        import jax.numpy as jnp
        boxes = _canon_boxes(boxes)
        if not boxes:
            bsz, x, y, z = occ.shape
            return jnp.zeros((bsz, 0, x, y, z), jnp.int32)

        def launch():
            ii = self._ii_fn()(jnp.asarray(occ))
            return jnp.stack([self._window_fn(b)(ii) for b in boxes],
                             axis=1)
        b = occ.shape[0]
        return _on_device(self, "multibox", (occ.shape, len(boxes)), launch,
                          b=b, b_pad=b, k=len(boxes), k_pad=len(boxes))

    @staticmethod
    @functools.lru_cache(maxsize=BUCKET_CACHE_SIZE)
    def _bucket_fn(boxes: Tuple[Box, ...]):
        """One fused jitted program for a *stable* box table: int16
        integral image (memory-bound halving; exact up to 31^3 cells),
        nested per-axis differencing (three subtractions, as the Pallas
        kernel does), bool planes, and the free counts read off the
        integral-image corner — a flush is a single XLA dispatch.
        Retraces per (B, cell) shape, which is exactly what the
        broker's bucketed padding keeps small.

        Three trace-time tricks keep the program lean on top of the
        shared integral image: partial differences are memoised per
        ``a`` and per ``(a, b)`` prefix (candidate tables cluster on
        shared extents, so most boxes pay only the final axis);
        duplicate boxes — the broker pads table capacity with repeats
        — reuse the already traced comparison instead of recomputing
        it; and every plane is written straight into one
        ``(B, K, X, Y, Z)`` output buffer through a chain of
        ``dynamic_update_slice`` ops that XLA turns into in-place
        writes — no per-plane zero template and no final ``stack``
        copy."""
        import jax
        import jax.numpy as jnp

        def run(occ):
            bsz, x, y, z = occ.shape
            vol = x * y * z
            dt = jnp.int16 if vol <= 32767 else jnp.int32
            ii = jnp.pad(occ.astype(dt),
                         ((0, 0), (1, 0), (1, 0), (1, 0)))
            for ax in (1, 2, 3):
                ii = jnp.cumsum(ii, axis=ax)
            sx, sxy, fits = {}, {}, {}
            out = jnp.zeros((bsz, len(boxes), x, y, z), jnp.bool_)
            for k, box in enumerate(boxes):
                if box not in fits:
                    a, b, c = box
                    if a > x or b > y or c > z:
                        fits[box] = None   # infeasible: stays zero
                    else:
                        if a not in sx:
                            sx[a] = ii[:, a:, :, :] - ii[:, :-a, :, :]
                        if (a, b) not in sxy:
                            s = sx[a]
                            sxy[(a, b)] = (s[:, :, b:, :]
                                           - s[:, :, :-b, :])
                        s = sxy[(a, b)]
                        s = s[:, :, :, c:] - s[:, :, :, :-c]
                        fits[box] = s == 0
                if fits[box] is not None:
                    out = jax.lax.dynamic_update_slice(
                        out, fits[box][:, None], (0, k, 0, 0, 0))
            free = vol - ii[:, -1, -1, -1].astype(jnp.int32)
            return out, free

        return jax.jit(run)

    def multibox_bucketed(self, occ, boxes: Sequence[Box]):
        import jax.numpy as jnp
        boxes = _canon_boxes(boxes)
        if not boxes:
            bsz, x, y, z = occ.shape
            return (jnp.zeros((bsz, 0, x, y, z), jnp.bool_),
                    self.free_counts(occ))
        # The broker has padded B and K already.
        b = occ.shape[0]
        return _on_device(self, "multibox_bucketed", (occ.shape, len(boxes)),
                          lambda: self._bucket_fn(boxes)(jnp.asarray(occ)),
                          b=b, b_pad=b, k=len(boxes), k_pad=len(boxes))

    @staticmethod
    @functools.cache
    def _free_counts_fn():
        import jax
        import jax.numpy as jnp

        def free(occ):
            n3 = occ.shape[1] * occ.shape[2] * occ.shape[3]
            return n3 - jnp.sum(occ.astype(jnp.int32), axis=(1, 2, 3))

        return jax.jit(free)

    def free_counts(self, occ):
        import jax.numpy as jnp
        b = occ.shape[0]
        return _on_device(self, "free_counts", (occ.shape,),
                          lambda: self._free_counts_fn()(jnp.asarray(occ)),
                          b=b, b_pad=b)


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode — the one place
    that decides it, from JAX's default backend: compiled on a TPU,
    interpreted on the CPU (tests and rehearsals), and refused on any
    other backend rather than silently interpreted there."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas fitmask kernels compile for a TPU (or run "
        f"interpreted on the CPU backend); JAX's backend is {backend!r}")


def _pad_grids(occ: np.ndarray) -> np.ndarray:
    """Pad the grid axis with empty grids up to a power of two."""
    bsz = occ.shape[0]
    if _pow2(bsz) == bsz:
        return occ
    pad = np.zeros((_pow2(bsz) - bsz,) + occ.shape[1:], occ.dtype)
    return np.concatenate([occ, pad])


class PallasEngine(FitmaskEngine):
    """The multi-box Pallas kernel: one VMEM pass for all K boxes,
    compiled on a TPU, interpreted on the CPU (:func:`pallas_interpret`).
    The kernel reads its boxes as data, so a program is compiled per
    (B, K, grid) shape; both calls pad B (with empty grids) and K (with
    repeats of the last box) up to powers of two and slice the answer
    back on the host, which keeps the served path — whose B and K
    change every occupancy epoch — to a handful of programs, hence
    ``pads_shapes``.

    ``multibox`` is one device call that answers the planes and each
    grid's free count together (`kernel.fitmask_multibox_counts`). The
    engine keeps those counts, one entry per thread (the fleet broker
    flushes on two threads at once), and the thread's next
    ``free_counts`` returns them with no device call if it asks about
    the same occupancy — compared by shape and bytes against a kept
    copy — opening an ``engine.reuse`` span. Any ``free_counts`` uses
    the entry up; another occupancy dispatches ``occupancy_counts``.
    So a refresh that asks for planes, then counts, costs one device
    call, and so does the default ``multibox_bucketed``.

    The boxes are data, so the fleet broker sends each flush its own
    box union, padded to a power of two, rather than a bucket's whole
    box table. On large grids the kernel cuts K into tiles
    (``kernel.k_tile``); ``engine.call`` carries ``k_tiles``."""

    name = "pallas"
    pads_shapes = True

    def __init__(self) -> None:
        super().__init__()
        # Per thread: (occupancy, free counts) of the last multibox.
        self._answered = threading.local()

    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        from . import kernel as _kernel
        occ = np.asarray(occ)
        boxes = _canon_boxes(boxes)
        bsz, k = occ.shape[0], len(boxes)
        if not k:
            return np.zeros((bsz, 0) + occ.shape[1:], np.int32)
        table = boxes + boxes[-1:] * (_pow2(k) - k)
        grids = _pad_grids(occ)
        tiles = len(table) // _kernel.k_tile(len(table), occ.shape[1:])
        planes, used = _on_device(
            self, "multibox", (grids.shape, len(table)),
            lambda: _kernel.fitmask_multibox_counts(
                grids, table, interpret=pallas_interpret()),
            b=bsz, b_pad=grids.shape[0], k=k, k_pad=len(table),
            k_tiles=tiles)
        n3 = int(np.prod(occ.shape[1:]))
        self._answered.last = (occ.copy(), n3 - used[:bsz])
        return planes[:bsz, :k]

    def free_counts(self, occ) -> np.ndarray:
        from . import kernel as _kernel
        occ = np.asarray(occ)
        bsz, n3 = occ.shape[0], int(np.prod(occ.shape[1:]))
        last = getattr(self._answered, "last", None)
        self._answered.last = None
        if last is not None and np.array_equal(last[0], occ):
            with obs.span("engine.reuse", kind="free_counts", b=bsz):
                return last[1]
        grids = _pad_grids(occ)
        used = _on_device(
            self, "free_counts", (grids.shape,),
            lambda: _kernel.occupancy_counts(grids,
                                             interpret=pallas_interpret()),
            b=bsz, b_pad=grids.shape[0])
        return n3 - used[:bsz]


class RefEngine(FitmaskEngine):
    """reduce_window oracle (jax, unjitted per box)."""

    name = "ref"

    def multibox(self, occ, boxes: Sequence[Box]):
        import jax.numpy as jnp
        from . import ref as _ref
        occ = jnp.asarray(occ)
        boxes = _canon_boxes(boxes)
        if not boxes:
            bsz, x, y, z = occ.shape
            return jnp.zeros((bsz, 0, x, y, z), jnp.int32)
        return jnp.stack([_ref.fitmask_reference(occ, b) for b in boxes],
                         axis=1)

    def free_counts(self, occ):
        import jax.numpy as jnp
        occ = jnp.asarray(occ)
        n3 = occ.shape[1] * occ.shape[2] * occ.shape[3]
        return n3 - jnp.sum(occ.astype(jnp.int32), axis=(1, 2, 3))


_REGISTRY: Dict[str, Type[FitmaskEngine]] = {}
_INSTANCES: Dict[str, FitmaskEngine] = {}
# What an unnamed engine selection means.
DEFAULT_ENGINE = "numpy"


def register_engine(cls: Type[FitmaskEngine]) -> Type[FitmaskEngine]:
    _REGISTRY[cls.name] = cls
    _INSTANCES.pop(cls.name, None)
    return cls


for _cls in (NumpyEngine, JaxEngine, PallasEngine, RefEngine):
    register_engine(_cls)


def available_engines() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def engine_name(name: Optional[str] = None) -> str:
    """The registry name an engine selection means: ``None`` is
    :data:`DEFAULT_ENGINE`; an unknown name raises ``KeyError``."""
    if name is None:
        return DEFAULT_ENGINE
    if name not in _REGISTRY:
        raise KeyError(f"unknown fitmask engine {name!r}; "
                       f"have {available_engines()}")
    return name


def get_engine(name: Optional[str] = None) -> FitmaskEngine:
    """The process-wide instance of the named engine (``None``: numpy).
    Methods are looked up on it at call time, so whatever is set on the
    instance answers every client built from it."""
    name = engine_name(name)
    inst = _INSTANCES.get(name)
    if inst is None:
        inst = _INSTANCES[name] = _REGISTRY[name]()
    return inst


def fitmask(occ, box: Box, engine: Optional[str] = None):
    """occ: (B, X, Y, Z) or (X, Y, Z). Returns the int32 fit mask of
    the same (batched) shape. ``engine=None`` means numpy. The numpy
    engine returns a numpy array — no device round-trip; the ``jax``
    and ``pallas`` engines copy their answer back to the host too, so
    callers that want a jax array convert (or pick ``ref``)."""
    squeeze = occ.ndim == 3
    if squeeze:
        occ = occ[None]
    out = get_engine(engine).fitmask(occ, box)
    return out[0] if squeeze else out


def fitmask_multi(occ, boxes: Sequence[Box], engine: Optional[str] = None):
    """All K candidate boxes in one engine pass: (B, X, Y, Z) or
    (X, Y, Z) -> (B, K, X, Y, Z) / (K, X, Y, Z) int32."""
    squeeze = occ.ndim == 3
    if squeeze:
        occ = occ[None]
    out = get_engine(engine).multibox(occ, boxes)
    return out[0] if squeeze else out


def free_counts(occ, engine: Optional[str] = None):
    """Free-cell count per grid: (B, X, Y, Z) -> (B,) int, or a single
    (X, Y, Z) grid -> scalar. Routed through the selected engine, so
    accelerator backends answer it without a host integral-image
    build."""
    squeeze = occ.ndim == 3
    if squeeze:
        occ = occ[None]
    out = get_engine(engine).free_counts(occ)
    return out[0] if squeeze else out

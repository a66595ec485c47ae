"""Fitmask engine benchmark: single-box vs multi-box kernel.

The claim under test is the multi-box design itself: one VMEM
integral-image pass answering all K candidate fold boxes must beat K
independent single-box ``pallas_call``s (each rebuilding the integral
image). Sweeps batch x K x grid size over the Pallas kernel — compiled
on a TPU, interpreted on the CPU backend (the engine's choice,
``ops.pallas_interpret``) — and the jitted jax and numpy engines for
scale, and emits ``BENCH_fitmask.json``. A CPU run times the
interpreter, not a device.

  PYTHONPATH=src python -m benchmarks.fitmask_bench [--out BENCH_fitmask.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, List, Tuple

import numpy as np

from repro.compile_cache import enable_compile_cache

# Candidate pool in fold-enumeration spirit: the flat/compact box shapes
# RFold actually queries. Truncated to K and filtered per grid.
CANDIDATE_BOXES: List[Tuple[int, int, int]] = [
    (4, 4, 4), (8, 4, 2), (2, 2, 2), (16, 2, 2), (8, 8, 1), (4, 2, 1),
    (16, 4, 1), (2, 4, 8), (8, 2, 4), (1, 1, 1), (16, 16, 1), (4, 8, 2),
    (3, 3, 3), (6, 2, 2), (12, 2, 1), (2, 8, 4), (5, 2, 2), (2, 6, 2),
    (4, 4, 1), (7, 1, 1), (1, 8, 2), (2, 2, 5), (6, 4, 1), (3, 2, 4),
]


def boxes_for(grid: Tuple[int, int, int], k: int):
    out = [b for b in CANDIDATE_BOXES
           if all(e <= d for e, d in zip(b, grid))]
    assert len(out) >= k, (grid, k)
    return tuple(out[:k])


def _time_ms(fn: Callable, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e3


def run_sweep(grids, batches, ks, iters: int = 3):
    import jax
    import jax.numpy as jnp

    from repro.core import fitmask as np_engine
    from repro.kernels.fitmask import kernel as _kernel
    from repro.kernels.fitmask import ops as _ops

    rng = np.random.default_rng(0)
    jax_engine = _ops.get_engine("jax")
    interpret = _ops.pallas_interpret()
    rows = []
    for grid in grids:
        for bsz in batches:
            occ_np = rng.uniform(size=(bsz,) + grid) < 0.3
            occ = jnp.asarray(occ_np)
            for k in ks:
                boxes = boxes_for(grid, k)
                multi = _time_ms(lambda: jax.block_until_ready(
                    _kernel.fitmask_multibox(occ, boxes, interpret=interpret)),
                    iters=iters)
                single = _time_ms(lambda: jax.block_until_ready(
                    _kernel.fitmask_multibox_singlepass_baseline(
                        occ, boxes, interpret=interpret)), iters=iters)
                jax_ms = _time_ms(lambda: jax.block_until_ready(
                    jax_engine.multibox(occ, boxes)), iters=iters)
                numpy_ms = _time_ms(
                    lambda: np_engine.fit_mask_multi(occ_np, boxes),
                    iters=iters)
                rows.append({
                    "grid": "x".join(map(str, grid)), "batch": bsz, "k": k,
                    "pallas_multibox_ms": round(multi, 3),
                    "pallas_singlebox_x_k_ms": round(single, 3),
                    "jax_ms": round(jax_ms, 3),
                    "numpy_ms": round(numpy_ms, 3),
                    "multibox_speedup": round(single / multi, 2)
                    if multi > 0 else None,
                })
                print(f"fitmask {rows[-1]['grid']} B={bsz} K={k}: "
                      f"multi {multi:.1f}ms single x K {single:.1f}ms "
                      f"({rows[-1]['multibox_speedup']}x) "
                      f"jax {jax_ms:.2f}ms numpy {numpy_ms:.2f}ms")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="BENCH_fitmask.json")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="headline cell only (16^3, B=8, K=4)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.quick:
        grids, batches, ks = [(16, 16, 16)], [8], [4]
    else:
        grids = [(8, 8, 8), (16, 16, 16)]
        batches = [1, 8, 64]
        ks = [1, 4, 8, 16]
    rows = run_sweep(grids, batches, ks, iters=args.iters)

    # Headline: the acceptance cell — K>=4 on a 16^3 grid must favor
    # the multi-box kernel over K independent single-box calls.
    import jax
    from repro.kernels.fitmask.ops import pallas_interpret
    mode = "interpret" if pallas_interpret() else "compiled"
    head = [r for r in rows if r["grid"] == "16x16x16" and r["k"] >= 4]
    headline = {
        "criterion": "multibox beats K single-box pallas_calls "
                     f"(K>=4, 16^3, {mode})",
        "min_speedup": min(r["multibox_speedup"] for r in head),
        "max_speedup": max(r["multibox_speedup"] for r in head),
        "pass": all(r["multibox_speedup"] > 1.0 for r in head),
    } if head else {}
    dev = jax.devices()[0]
    out = {"sweep": rows, "headline": headline,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "note": f"{mode} pallas wall clock; "
                   "jax/numpy engines jitted/host for scale"}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if headline:
        print(f"# headline: multibox {headline['min_speedup']}x-"
              f"{headline['max_speedup']}x vs single-box "
              f"(pass={headline['pass']})")
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()

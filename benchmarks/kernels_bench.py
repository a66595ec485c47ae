"""Microbenchmarks: kernels (oracle engines on CPU; the Pallas kernels
are TPU-targeted and only validated in interpret mode), allocator and
simulator throughput. Emits ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache


def _time(fn: Callable, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_flash_attention(emit=print) -> None:
    from repro.kernels.flash_attention import ref as fa_ref
    rng = np.random.default_rng(0)
    for s in (256, 1024):
        q = jnp.array(rng.normal(size=(1, s, 8, 64)), jnp.bfloat16)
        k = jnp.array(rng.normal(size=(1, s, 2, 64)), jnp.bfloat16)
        v = jnp.array(rng.normal(size=(1, s, 2, 64)), jnp.bfloat16)
        f = jax.jit(lambda a, b, c: fa_ref.attention_reference(a, b, c))
        us = _time(lambda: jax.block_until_ready(f(q, k, v)))
        flops = 4 * s * s * 8 * 64 / 2  # causal
        emit(f"attention_ref_s{s},{us:.0f},{flops / us / 1e3:.1f}GFLOPs")


def bench_ssd(emit=print) -> None:
    from repro.kernels.ssd_scan import ref as ssd_ref
    rng = np.random.default_rng(1)
    B, S, H, P, N = 1, 2048, 8, 64, 64
    x = jnp.array(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.array(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    a = jnp.array(-rng.uniform(0.5, 2, (H,)), jnp.float32)
    b = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    c = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    for chunk in (64, 256):
        f = jax.jit(lambda *t, ch=chunk: ssd_ref.ssd_reference(
            *t, chunk=ch)[0])
        us = _time(lambda: jax.block_until_ready(f(x, dt, a, b, c)))
        emit(f"ssd_chunk{chunk},{us:.0f},{S * B / (us / 1e6) / 1e6:.2f}Mtok/s")


def bench_fitmask(emit=print) -> None:
    from repro.core import fitmask as np_engine
    from repro.kernels.fitmask import ref as fit_ref
    rng = np.random.default_rng(2)
    occ = rng.uniform(size=(16, 16, 16)) < 0.3
    us = _time(lambda: np_engine.fit_mask(occ, (4, 4, 4)), iters=50)
    emit(f"fitmask_numpy_16cube,{us:.0f},{1e6 / us:.0f}searches/s")
    occ_b = jnp.array(rng.uniform(size=(64, 4, 4, 4)) < 0.3)
    f = jax.jit(lambda o: fit_ref.fitmask_reference(o, (2, 2, 2)))
    us = _time(lambda: jax.block_until_ready(f(occ_b)), iters=20)
    emit(f"fitmask_reduce_window_64cubes,{us:.0f},batched")


def bench_allocator(emit=print) -> None:
    from repro.core.allocator import make_policy
    from repro.traces.generator import TraceConfig, generate_trace
    jobs = generate_trace(TraceConfig(num_jobs=60, seed=0))
    for name, kw in (("firstfit", dict(dims=(16, 16, 16))),
                     ("rfold", dict(num_xpus=4096, cube_n=4))):
        pol = make_policy(name, **kw)
        t0 = time.perf_counter()
        placed = sum(1 for j in jobs
                     if pol.try_place(j.job_id, j.shape) is not None)
        dt = time.perf_counter() - t0
        emit(f"alloc_{name},{dt / len(jobs) * 1e6:.0f},"
             f"{placed}/{len(jobs)}placed")


def bench_simulator(emit=print) -> None:
    from repro.core.allocator import make_policy
    from repro.sim.simulator import Simulator
    from repro.traces.generator import TraceConfig, generate_trace
    jobs = generate_trace(TraceConfig(num_jobs=150, seed=1))
    pol = make_policy("rfold", num_xpus=4096, cube_n=4)
    t0 = time.perf_counter()
    Simulator(pol, jobs).run()
    dt = time.perf_counter() - t0
    emit(f"sim_rfold_150jobs,{dt * 1e6:.0f},{150 / dt:.0f}jobs/s")


def main(emit=print) -> None:
    enable_compile_cache()
    emit("name,us_per_call,derived")
    bench_fitmask(emit)
    bench_allocator(emit)
    bench_simulator(emit)
    bench_flash_attention(emit)
    bench_ssd(emit)


if __name__ == "__main__":
    main()

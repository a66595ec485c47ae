"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU-native design: grid (batch, head, chunk) with the chunk axis
sequential ("arbitrary") — the inter-chunk state (P x N, fp32) lives in
VMEM scratch and is carried across chunk iterations, while the
intra-chunk quadratic form (Q x Q) runs on the MXU. This replaces the
CUDA warp-level scan of the original Mamba2 kernels with a
grid-carried-scratch recurrence, which is the idiomatic TPU structure.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, fs_ref, state_scr, *, chunk: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q,)
    a = a_ref[0].astype(jnp.float32)                # scalar
    b = b_ref[0, 0, 0].astype(jnp.float32)          # (Q, N)
    c = c_ref[0, 0, 0].astype(jnp.float32)          # (Q, N)
    d = d_ref[0].astype(jnp.float32)                # scalar

    @pl.when(ki == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    log_a = dt * a                                  # (Q,)
    cum = jnp.cumsum(log_a)                         # (Q,)
    total = cum[-1]

    # intra-chunk quadratic form (causal)
    seg = cum[:, None] - cum[None, :]               # (Q, Q): t, s
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where(si <= ti, seg, NEG_INF)
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)
    m = cb * jnp.exp(seg) * dt[None, :]
    y = jnp.dot(m, x, preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    state = state_scr[...]                          # (P, N)
    y += jnp.exp(cum)[:, None] * jnp.dot(
        c, state.T, preferred_element_type=jnp.float32)

    # state update: decay + chunk contribution
    w = dt * jnp.exp(total - cum)                   # (Q,)
    contrib = jnp.dot((w[:, None] * x).T, b,
                      preferred_element_type=jnp.float32)   # (P, N)
    state_scr[...] = jnp.exp(total) * state + contrib

    y = y + x * d
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ki == nk - 1)
    def _emit_state():
        fs_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, c: jnp.ndarray,
                    d_skip: Optional[jnp.ndarray] = None,
                    chunk: int = 64, interpret: bool = True
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Same contract as ref.ssd_reference (init_state=None).

    x: (B,S,H,P); dt: (B,S,H); a: (H,); b,c: (B,S,H,N)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0
    k = s // chunk
    d = d_skip if d_skip is not None else jnp.zeros((h,), jnp.float32)

    # (B, H, K, Q, ...)
    xt = jnp.moveaxis(x, 2, 1).reshape(bsz, h, k, chunk, p)
    dtt = jnp.moveaxis(dt, 2, 1).reshape(bsz, h, k, chunk)
    bt = jnp.moveaxis(b, 2, 1).reshape(bsz, h, k, chunk, n)
    ct = jnp.moveaxis(c, 2, 1).reshape(bsz, h, k, chunk, n)

    y, fs = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bsz, h, k),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ki: (bi, hi, ki, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1,), lambda bi, hi, ki: (hi,)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bi, hi, ki: (bi, hi, ki, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bi, hi, ki: (bi, hi, ki, 0, 0)),
            pl.BlockSpec((1,), lambda bi, hi, ki: (hi,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ki: (bi, hi, ki, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, k, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, a, bt, ct, d)

    y = jnp.moveaxis(y.reshape(bsz, h, s, p), 1, 2)
    return y, fs

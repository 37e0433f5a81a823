"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

One program instance owns one (batch·head, chunk) tile; the chunk axis is
the minor grid dimension, so the inter-chunk SSM state [N, P] lives in VMEM
scratch and flows sequentially across chunk steps (the recurrent part),
while the within-chunk quadratic term runs on the MXU:

    y_diag = (C B^T ⊙ L) · (dt x)        L = exp(segsum(dt A))   [L x L]
    y_off  = exp(cum dA) ⊙ (C · state)
    state <- exp(sum dA) state + (B ⊙ decay_to_end)^T (dt x)

VMEM working set per step: x/B/C chunks (L x P, L x N), the L x L decay
matrix, and the [N, P] state — with the default L=128, N=128, P=64 this is
~0.3 MB, comfortably inside a v5e core's VMEM, and every matmul dimension is
a multiple of the 128-lane MXU tiling.

Inputs are pre-chunked by ops.ssd_scan: xdt [BH, NC, L, P] (x·dt),
dA [BH, NC, 1, L] (dt·A, one lane row per chunk, so its block's last two
dims are the array's), Bm/Cm [BH, NC, L, N] (group-expanded).
Validated in interpret mode against repro.kernels.ref.ssd_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, dA_ref, b_ref, c_ref, o_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0, 0].astype(jnp.float32)           # [L, P]
    dA = dA_ref[0, 0].astype(jnp.float32)             # [1, L]
    Bm = b_ref[0, 0].astype(jnp.float32)              # [L, N]
    Cm = c_ref[0, 0].astype(jnp.float32)              # [L, N]

    # Mosaic lowers no cumsum, so the inclusive prefix sum runs on the MXU
    # against the lower-triangular ones matrix, once per orientation: that
    # also spares relaying the [1, L] row out as an [L, 1] column.
    # HIGHEST keeps the products in f32.
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (li >= lj).astype(jnp.float32)
    dA_rows = jnp.broadcast_to(dA, (chunk, chunk))    # [r, k] = dA_k
    nt = (((1,), (1,)), ((), ()))
    cs_i = jax.lax.dot_general(tril, dA_rows, nt,     # [i, r] = cs_i
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    cs_j = jax.lax.dot_general(dA_rows, tril, nt,     # [r, j] = cs_j
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    cs = cs_i[:, :1]                                  # [L, 1]
    cs_end = jnp.sum(dA)                              # chunk total
    # within-chunk decay matrix: L[i,j] = exp(cs_i - cs_j), i >= j
    Lmat = jnp.where(li >= lj, jnp.exp(cs_i - cs_j), 0.0)

    S = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, L]
    y_diag = jax.lax.dot_general(S * Lmat, xdt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_scr[...]                            # [N, P]
    y_off = jax.lax.dot_general(Cm, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(cs)

    decay_to_end = jnp.exp(cs_end - cs)               # [L, 1]
    state_new = (jnp.exp(cs_end) * state
                 + jax.lax.dot_general(Bm * decay_to_end, xdt,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32))
    state_scr[...] = state_new
    o_ref[0, 0] = (y_diag + y_off).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan_chunked(xdt: jax.Array, dA: jax.Array, Bm: jax.Array,
                     Cm: jax.Array, *, interpret: bool = True) -> jax.Array:
    """xdt [BH, NC, L, P]; dA [BH, NC, 1, L]; Bm/Cm [BH, NC, L, N] ->
    y [BH, NC, L, P]."""
    bh, nc, l, p = xdt.shape
    n = Bm.shape[-1]
    grid = (bh, nc)

    def ix(b, c):
        return (b, c, 0, 0)

    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, l, p), ix),
            pl.BlockSpec((1, 1, 1, l), ix),
            pl.BlockSpec((1, 1, l, n), ix),
            pl.BlockSpec((1, 1, l, n), ix),
        ],
        out_specs=pl.BlockSpec((1, 1, l, p), ix),
        out_shape=jax.ShapeDtypeStruct((bh, nc, l, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xdt, dA, Bm, Cm)

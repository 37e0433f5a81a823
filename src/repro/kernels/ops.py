"""jit'd public wrappers around the Pallas kernels.

Each op prepares kernel-friendly layouts, dispatches to the Pallas kernel
(compiled for the TPU it lowers to, interpreted on CPU — ``_pallas``), and
exposes a differentiable version via jax.custom_vjp whose backward pass is
the grad of the pure-jnp oracle algorithm (recompute — a standard
production pattern: optimized forward, reference backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_chunked
from repro.models.attention import chunked_causal_attention
from repro.models.ssm import ssd_chunked


def _pallas(kernel, *args, **static):
    """``kernel(*args, **static)`` with ``interpret`` chosen per lowering
    platform: the Mosaic kernel wherever the call lowers for a TPU, the
    Pallas interpreter on CPU (the only backend that needs it)."""
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(kernel, interpret=True, **static),
        default=functools.partial(kernel, interpret=False, **static))


# ---------------------------------------------------------------------------
# Flash attention (differentiable)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, block_q: int = 512, block_kv: int = 512):
    return _pallas(flash_attention_fwd, q, k, v, block_q=block_q,
                   block_kv=block_kv)


def _fa_fwd(q, k, v, block_q, block_kv):
    out = flash_attention(q, k, v, block_q, block_kv)
    return out, (q, k, v)


def _fa_bwd(block_q, block_kv, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_causal_attention(
            q_, k_, v_, block_q=block_q, block_kv=block_kv), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def ssd_scan(x, dt, A, B, C, *, chunk: int = 128):
    """Pallas SSD. x [b,s,h,p]; dt [b,s,h]; A [h]; B,C [b,s,g,n] -> y."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        padded = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = padded(x), padded(dt), padded(B), padded(C)
        s2 = s + pad
    else:
        s2 = s
    nc = s2 // chunk
    rep = h // g
    dtf = dt.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dtf[..., None])
    dA = dtf * A.astype(jnp.float32)[None, None, :]
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    # [b,s,h,*] -> [b*h, nc, L, *]
    def chunked(a, feat):
        a = jnp.moveaxis(a, 2, 1)                  # [b,h,s,*]
        return a.reshape(b * h, nc, chunk, *feat)
    xdt_c = chunked(xdt, (p,))
    dA_c = chunked(dA, ())[:, :, None, :]           # [b*h, nc, 1, L]
    B_c = chunked(Bh.astype(jnp.float32), (n,))
    C_c = chunked(Ch.astype(jnp.float32), (n,))
    y = _pallas(ssd_scan_chunked, xdt_c, dA_c, B_c, C_c)
    y = y.reshape(b, h, s2, p)
    y = jnp.moveaxis(y, 1, 2)[:, :s]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

def rglru_recurrence(a, b, *, block_s: int = 256, block_w: int = 512):
    """Pallas diagonal recurrence h_t = a_t h_{t-1} + b_t; [B,S,W] -> f32."""
    bb, s, w = a.shape
    bs = min(block_s, s)
    while s % bs:
        bs //= 2
    bw = min(block_w, w)
    while w % bw:
        bw //= 2
    return _pallas(rglru_scan_pallas, a, b, block_s=max(bs, 1),
                   block_w=max(bw, 1))

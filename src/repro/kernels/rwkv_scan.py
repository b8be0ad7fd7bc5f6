"""RWKV-6 recurrence — Pallas TPU kernel (beyond-paper §Perf hillclimb #1).

The jnp scan reads+writes the (B, H, hs, hs) wkv state from HBM every
step; this kernel keeps the state in a VMEM scratch across the whole
sequence, so HBM traffic drops to one read of r/k/v/w + one write of the
output (+ state in/out once per sequence).

Tiling: grid (B, H, T/CHUNK_T) with the last grid dim sequential — the
scratch persists across T-chunks (standard TPU accumulation pattern; the
chunk bounds VMEM at CHUNK_T x hs per input).  hs = 64 keeps the per-head
state (64x64 f32 = 16 KB) resident.

Validated on CPU in interpret mode against a ref-equivalent jnp scan;
not yet compiled for a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_T = 256


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sf_ref,
                 S_ref, *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        S_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                   # (hs,)

    def step(t, _):
        r = r_ref[0, 0, t].astype(jnp.float32)         # (hs,)
        k = k_ref[0, 0, t].astype(jnp.float32)
        v = v_ref[0, 0, t].astype(jnp.float32)
        w = w_ref[0, 0, t].astype(jnp.float32)
        S = S_ref[...]
        kv = k[:, None] * v[None, :]                   # (hs, hs)
        out = jnp.sum(r[:, None] * (S + u[:, None] * kv), axis=0)
        o_ref[0, 0, t] = out.astype(o_ref.dtype)
        S_ref[...] = w[:, None] * S + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ic == n_chunks - 1)
    def _final():
        sf_ref[0, 0] = S_ref[...].astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "chunk_t"))
def rwkv_scan(r, k, v, w, u, s0, *, interpret: bool,
              chunk_t: int = CHUNK_T):
    """r/k/v/w: (B, H, T, hs); u: (H, hs); s0: (B, H, hs, hs).
    Returns (out (B,H,T,hs), s_final (B,H,hs,hs))."""
    B, H, T, hs = r.shape
    chunk = min(chunk_t, T)
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    kernel = functools.partial(_rwkv_kernel, chunk=chunk,
                               n_chunks=n_chunks)
    io_spec = pl.BlockSpec((1, 1, chunk, hs), lambda b, h, i: (b, h, i, 0))
    state_spec = pl.BlockSpec((1, 1, hs, hs), lambda b, h, i: (b, h, 0, 0))
    out, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, hs), lambda b, h, i: (h, 0)),
                  state_spec],
        out_specs=[io_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, hs), r.dtype),
                   jax.ShapeDtypeStruct((B, H, hs, hs), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return out, s_final

"""Blocked causal flash attention (prefill) — Pallas TPU kernel.

Tiling: grid (B, H, T/BLOCK_Q).  Each program holds one (BLOCK_Q, D) query
tile in VMEM and streams (BLOCK_K, D) key/value tiles with an online
softmax (running max / sum), so VMEM holds O(BLOCK_Q x BLOCK_K) scores
instead of the O(T x S) full matrix.  Block sizes are multiples of 128 so
the QK^T and PV matmuls land on MXU-aligned shapes; accumulation is f32.

Validated on CPU in interpret mode against ref.flash_attention_ref; not yet compiled
for a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, seq_k,
                  block_k, offset):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (BQ, D)
    bq, d = q.shape
    # `offset` = S - T aligns query positions when a cached prefix makes
    # the key sequence longer than the query block range
    q_pos = iq * bq + jax.lax.iota(jnp.int32, bq) + offset

    k_all = k_ref[0, 0]                                  # (S, D) in VMEM
    v_all = v_ref[0, 0]

    def kv_step(j, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(
            k_all, j * block_k, block_k, 0).astype(jnp.float32)  # (BK, D)
        v = jax.lax.dynamic_slice_in_dim(
            v_all, j * block_k, block_k, 0).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        k_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    n_k = seq_k // block_k
    if causal:
        # only blocks at or left of the diagonal contribute
        n_k_eff = jnp.minimum(
            n_k, ((iq + 1) * bq + offset + block_k - 1) // block_k)
    else:
        n_k_eff = n_k
    m, l, acc = jax.lax.fori_loop(0, n_k_eff, kv_step, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    interpret: bool, block_q: int = BLOCK_Q,
                    block_k: int = BLOCK_K):
    """q:(B,H,T,D) k/v:(B,H,S,D) -> (B,H,T,D)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    assert T % block_q == 0 and S % block_k == 0, (T, S)
    scale = D ** -0.5 if scale is None else scale
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               seq_k=S, block_k=block_k, offset=S - T)
    return pl.pallas_call(
        kernel,
        grid=(B, H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
    )(q, k, v)

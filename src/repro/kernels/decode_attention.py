"""GQA flash-decode — Pallas TPU kernel for single-token decode against a
long KV cache.

Tiling: grid (B, KV).  Each program handles one (batch, kv-head) pair: the
G = H/KV query heads that share this kv-head form a (G, D) tile (so the
GQA "repeat" never materializes), and the (S, D) cache streams through
VMEM in (BLOCK_S, D) tiles with an online softmax.  This is the hot loop
of decode_32k / long_500k serving.

Validated on CPU in interpret mode against ref.decode_attention_ref; not yet compiled
for a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, seq_k, block_s):
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    g, d = q.shape

    k_all = k_ref[0, :, 0, :]                            # (S, D) in VMEM
    v_all = v_ref[0, :, 0, :]

    def step(j, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(
            k_all, j * block_s, block_s, 0).astype(jnp.float32)  # (BS, D)
        v = jax.lax.dynamic_slice_in_dim(
            v_all, j * block_s, block_s, 0).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, BS)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    a0 = jnp.zeros((g, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, seq_k // block_s, step, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_s"))
def decode_attention(q, k, v, *, scale=None, interpret: bool,
                     block_s: int = BLOCK_S):
    """q:(B,H,D) one new token; k/v:(B,S,KV,D) cache -> (B,H,D)."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, KV, G, D)
    kernel = functools.partial(_decode_kernel, scale=scale, seq_k=S,
                               block_s=block_s)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, kv: (b, kv, 0, 0)),
            pl.BlockSpec((1, S, 1, D), lambda b, kv: (b, 0, kv, 0)),
            pl.BlockSpec((1, S, 1, D), lambda b, kv: (b, 0, kv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, kv: (b, kv, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(qg, k, v)
    return out.reshape(B, H, D)

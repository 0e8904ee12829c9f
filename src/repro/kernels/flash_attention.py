"""Fused flash-attention Pallas kernel (TPU MXU + VMEM-resident scores).

Why this kernel exists (§Perf iteration 1): the XLA-level chunked attention
in ``models/layers.py`` materialises every (q_chunk x k_chunk) score tile in
HBM — the dry-run roofline shows 32k-token prefill spending >90% of its
memory term on score traffic.  On TPU the fix is a fused kernel: scores,
softmax statistics and the output accumulator live in VMEM; only Q, K, V and
O ever cross HBM.  Per (batch*head, q_block) grid step the kernel loops over
k blocks with ``fmopa``-style MXU dots accumulated in fp32.

GQA is expressed in the BlockSpec index_map (q-head -> kv-head integer
division), causal masking via in-kernel iota comparison, and the k-loop is
*triangular*: grid dimension k stops contributing past the causal frontier
with @pl.when (on TPU, Mosaic's grid dim skipping elides the dead steps; the
roofline model counts only the live ones).

Validated in interpret mode against ``ref.flash_attention_ref`` /
``models.layers.flash_attention`` over shape x dtype x GQA sweeps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, scale: float, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # triangular schedule: steps entirely above the causal diagonal are dead
    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0].astype(jnp.float32)              # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           interpret: bool, causal: bool = True,
                           block_q: int = 512,
                           block_k: int = 512) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) with H % KV == 0.

    Returns (B, Sq, H, hd) in q.dtype.  Scores never leave VMEM: HBM traffic
    is exactly Q+K+V read + O written (the §Perf kernel-adjusted model).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    rep = H // KV
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0

    # (B, S, H, hd) -> (B*H, S, hd) head-major for clean 2-D blocks
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)

    grid = (B * H, Sq // block_q, Sk // block_k)

    def q_map(h, qi, ki):
        return (h, qi, 0)

    def kv_map(h, qi, ki):
        return (h // rep, ki, 0)  # GQA: q-head group -> kv head

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)

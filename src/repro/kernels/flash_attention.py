"""Flash attention Pallas TPU kernel.

TPU adaptation of the flash-attention access pattern: q blocks stay
resident in VMEM while k/v stream through in MXU-aligned (block_k × d)
tiles; online-softmax statistics (m, l) and the f32 accumulator live in
VMEM scratch that persists across the sequential kv grid dimension.  GQA is
handled in the k/v index_map (kv head = q head // group) — no repeated kv
in HBM at all, improving on the XLA fallback path which repeats per chunk.

Grid: (batch, q_heads, q_blocks, kv_blocks), kv innermost & sequential.
Block shapes are (1, 1, block_q, d) / (1, 1, block_k, d) — multiples of
128 on the sequence dims for MXU alignment at production sizes; the
interpret-mode tests sweep smaller shapes for correctness.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, kv_len: int, causal: bool,
                  window: int, scale: float):
    i = pl.program_id(2)           # q block
    j = pl.program_id(3)           # kv block (sequential)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                # (block_q, d)
    k = k_ref[0, 0]                # (block_k, d)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (block_q, block_k)

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kv_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = kv_pos < kv_len
    if causal:
        mask &= kv_pos <= q_pos
    if window > 0:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D), H % Hkv == 0."""
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          kv_len=Skv, causal=causal, window=window,
                          scale=scale),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :Sq, :]
    return out

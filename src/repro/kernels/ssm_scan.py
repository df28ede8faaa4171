"""Selective-scan (Mamba recurrence) Pallas TPU kernel.

TPU adaptation of the CUDA selective-scan: instead of a warp-parallel
recurrence, the SSM state h (d_block × N) lives in VMEM scratch and
persists across a *sequential* chunk grid dimension — HBM traffic is one
read of (decay, inc, C) and one write of y, while the recurrence itself
runs at VMEM/VREG speed.  The channel dimension is tiled (d_block) so the
working set fits VMEM; channels are embarrassingly parallel, which is also
the axis the model shards with TP.

    h_t = decay_t ⊙ h_{t-1} + inc_t        (d_block, N) per step
    y_t = Σ_n h_t[:, n] · C_t[n]

Grid: (batch, d_blocks, chunks) — chunks innermost & sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(decay_ref, inc_ref, c_ref, y_ref, h_ref, *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        h = decay_ref[0, t] * h + inc_ref[0, t]          # (bd, N)
        y_ref[0, t] = jnp.sum(h * c_ref[0, t][None, :], axis=-1)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def ssm_scan_kernel(decay, inc, C, *, chunk: int = 16,
                    d_block: int = 256, interpret: bool = False):
    """decay/inc: (B, S, d, N) f32; C: (B, S, N) f32 → y: (B, S, d).

    The recurrence runs in f32 regardless of input dtype (state stability);
    S must divide by ``chunk`` (pad upstream), d by ``d_block`` (clamped).
    A (chunk, d_block, N) block pads N = 16 to 128 lanes in VMEM, so the
    defaults keep the two double-buffered inputs at 8 MiB, inside v5e's
    16 MiB scoped VMEM (chunk 32 already exceeds it).
    """
    B, S, d, N = decay.shape
    chunk = min(chunk, S)
    d_block = min(d_block, d)
    assert S % chunk == 0, (S, chunk)
    assert d % d_block == 0, (d, d_block)
    nc = S // chunk
    nd = d // d_block

    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block, N),
                         lambda b, dblk, c: (b, c, dblk, 0)),
            pl.BlockSpec((1, chunk, d_block, N),
                         lambda b, dblk, c: (b, c, dblk, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, dblk, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block),
                               lambda b, dblk, c: (b, c, dblk)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d_block, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(decay.astype(jnp.float32), inc.astype(jnp.float32),
      C.astype(jnp.float32))

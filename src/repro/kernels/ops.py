"""Jitted public wrappers for the Pallas kernels.

``use_pallas`` selects the kernel (TPU) or the pure-XLA fallback (CPU and
the dry-run path, whose HLO mirrors the same chunked access pattern).  The
kernels compile for the chip by default; the test suite passes
``interpret=True`` to run them on the CPU against the ``ref`` oracles.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.ssm_scan import ssm_scan_kernel


def default_backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                   "interpret", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_pallas: bool = False, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128):
    """(B, H, S, D) attention; kernel or oracle path, identical semantics."""
    if use_pallas:
        return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@partial(jax.jit, static_argnames=("chunk", "d_block", "use_pallas",
                                   "interpret"))
def ssm_scan(decay, inc, C, *, chunk: int = 16, d_block: int = 256,
             use_pallas: bool = False, interpret: bool = False):
    """(B, S, d, N) selective scan; kernel or oracle path."""
    if use_pallas:
        return ssm_scan_kernel(decay, inc, C, chunk=chunk, d_block=d_block,
                               interpret=interpret)
    return ref.ssm_scan_ref(decay, inc, C)

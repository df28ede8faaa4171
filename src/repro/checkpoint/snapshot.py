"""The device→host snapshot a save takes, shard by shard.

The snapshot is the only synchronous part of a checkpoint save: the
next train step donates the state's device buffers, so every byte must
be on the host before ``save()`` returns.  It walks each leaf's distinct
addressable shards (those with ``replica_id == 0``) in two interleaved
phases:

1. **issue**: start ``copy_to_host_async`` on the shards of the leaves
   ahead, up to :data:`IN_FLIGHT_BYTES` beyond the leaf being collected;
2. **collect**: take each shard's host buffer, kept with its index into
   the leaf.

The window is bounded because more copies in flight are slower, not
faster: on one v5e chip, starting the copies of a whole 6.15 GB train
state at once took 3.8–5.1 s against 2.0–2.5 s one leaf at a time and
2.2 s with 1 GiB in flight (PERF.md, findings).

Nothing is gathered here.  A leaf whose one distinct shard is the whole
leaf (every leaf on one device, and replicated leaves) comes back as the
numpy array ``np.asarray`` gives.  A leaf of several distinct shards
comes back as a :class:`HostShards`: the writer takes its
canonical-stream windows straight out of the shard buffers
(``pytree_io._owned_windows``, which gathers a leaf whose shards split
into many short runs instead), and a consumer that needs the whole array
(digests, deflate chunks) gets it from ``np.asarray``, which gathers it
on the thread that asks: the background save, not the stall.

Counters (with a trace collector active): ``ckpt.snapshot.shards``
counts the host shard buffers taken, ``ckpt.snapshot.gathered_bytes``
the bytes copied into a whole-leaf host buffer.
"""
from __future__ import annotations

import math
from typing import Any, List, Sequence, Tuple

import jax
import numpy as np

from repro.core import trace as _trace


class HostShards:
    """One leaf's distinct shards on the host: ``shards`` holds
    ``(index, buffer)`` pairs, ``index`` a tuple of slices into the
    global ``shape``."""

    __slots__ = ("shape", "dtype", "shards")

    def __init__(self, shape: Sequence[int], dtype,
                 shards: List[Tuple[Any, np.ndarray]]) -> None:
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.shards = shards

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * self.dtype.itemsize

    @property
    def complete(self) -> bool:
        """The shards cover the whole leaf (on one host they always do)."""
        return sum(buf.size for _, buf in self.shards) == \
            math.prod(self.shape)

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        if not self.complete:
            raise ValueError("the shards on this host do not cover the "
                             "whole leaf; it cannot be gathered here")
        out = np.empty(self.shape, self.dtype)
        for index, buf in self.shards:
            out[index] = buf
        c = _trace.collector()
        if c is not None:
            c.metrics.count("ckpt.snapshot.gathered_bytes", out.nbytes)
        return out if dtype is None else out.astype(dtype, copy=False)


#: Bytes of device→host copies started ahead of the leaf being collected
#: (that leaf's own copies always start).
IN_FLIGHT_BYTES = 1 << 30


def _distinct_shards(x: jax.Array) -> list:
    return [s for s in x.addressable_shards if s.replica_id == 0]


def _issue(shards: list) -> int:
    """Start the copies of ``shards``; returns their bytes."""
    for s in shards:
        s.data.copy_to_host_async()
    return sum(s.data.nbytes for s in shards)


def _collect(x: jax.Array, shards: list):
    bufs = [(s.index, np.asarray(s.data)) for s in shards]
    c = _trace.collector()
    if c is not None:
        c.metrics.count("ckpt.snapshot.shards", len(bufs))
    if len(bufs) == 1 and bufs[0][1].shape == x.shape:
        return bufs[0][1]
    return HostShards(x.shape, x.dtype, bufs)


def leaf_to_host(x: jax.Array):
    """One leaf's snapshot (see :func:`snapshot_to_host`)."""
    shards = _distinct_shards(x)
    _issue(shards)
    return _collect(x, shards)


def snapshot_to_host(tree):
    """``tree`` with every ``jax.Array`` leaf copied to the host: a numpy
    array where one shard is the whole leaf, else a :class:`HostShards`.
    Other leaves pass through unchanged."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    taken = [_distinct_shards(x) if isinstance(x, jax.Array) else None
             for x in leaves]
    out: List[Any] = []
    issued, in_flight = 0, 0
    for x, shards in zip(leaves, taken):
        with _trace.span("snapshot.issue", "ckpt"):
            while issued < len(leaves) and (
                    issued <= len(out) or in_flight < IN_FLIGHT_BYTES):
                if taken[issued] is not None:
                    in_flight += _issue(taken[issued])
                issued += 1
        if shards is None:
            out.append(x)
            continue
        with _trace.span("snapshot.collect", "ckpt"):
            out.append(_collect(x, shards))
        in_flight -= sum(s.data.nbytes for s in shards)
    return jax.tree_util.tree_unflatten(treedef, out)

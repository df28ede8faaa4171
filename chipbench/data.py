"""Seeded token traffic: the benchmark's own copy of the program's
synthetic pipeline (``repro.data.pipeline.SyntheticTokens``), so that no
change to the program can change the yardstick.

Every (step, row) is a pure function of the seed: Zipf-distributed ids,
one Philox stream per global row, so row contents do not depend on how
the batch is split over devices.  ``train()`` takes an object with this
interface as its ``data``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0


class SyntheticTokens:
    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        ranks = np.arange(1, cfg.vocab + 1, dtype=np.float64)
        probs = 1.0 / ranks
        self._cdf = np.cumsum(probs / probs.sum())

    def global_batch_shard(self, step: int, row_start: int,
                           rows: int) -> Dict[str, np.ndarray]:
        """tokens/labels of rows [row_start, row_start + rows) at ``step``."""
        cfg = self.cfg
        out = np.empty((rows, cfg.seq_len + 1), np.int32)
        for i in range(rows):
            rng = np.random.Generator(np.random.Philox(
                np.random.SeedSequence(entropy=cfg.seed,
                                       spawn_key=(step, row_start + i))))
            u = rng.random(cfg.seq_len + 1)
            # A u above the last cdf entry (rounding) must stay in range.
            out[i] = np.minimum(np.searchsorted(self._cdf, u),
                                cfg.vocab - 1).astype(np.int32)
        return {"tokens": out[:, :-1], "labels": out[:, 1:]}

    def sharded_batch(self, step: int, mesh=None):
        """The global batch at ``step`` as device arrays, split over the
        mesh's data axes when a mesh is given."""
        import jax
        cfg = self.cfg
        host = self.global_batch_shard(step, 0, cfg.global_batch)
        if mesh is None:
            return {k: jax.device_put(v) for k, v in host.items()}
        from jax.sharding import NamedSharding, PartitionSpec as P
        data_axes = tuple(a for a in mesh.axis_names if a != "model")
        axis = data_axes if len(data_axes) > 1 else data_axes[0]
        spec = P(axis, None) if cfg.global_batch % _size(mesh, data_axes) \
            == 0 else P(None, None)
        return {k: jax.device_put(v, NamedSharding(mesh, spec))
                for k, v in host.items()}


def _size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n

"""A tiny cell of each mix, for CPU tests: the benchmark's own files,
with the sizes of the configuration and the traffic cut down."""
import copy
import tempfile
import time
from pathlib import Path

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]

TINY_SIZES = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256}


class TinyBench(harness.Bench):
    """BENCHMARK.json as committed, with every configuration and mix cut
    to CPU size (``moe`` keeps 4 experts, top 2)."""

    def __init__(self, seq_len=32, batch=2, save_every=4, window_steps=5,
                 limits=None):
        super().__init__(ROOT)
        self.seq_len, self.batch, self.save_every = seq_len, batch, save_every
        self.window_steps = window_steps
        self._limits = limits

    def config(self, cell):
        c = dict(super().config(cell), **TINY_SIZES)
        if c.get("num_local_experts"):
            c.update(num_local_experts=4, num_experts_per_tok=2,
                     intermediate_size=32)
        return c

    def traffic(self, cell):
        t = copy.deepcopy(super().traffic(cell))
        t.update(seq_len=self.seq_len, global_batch=self.batch)
        if t["mix"] == "train":
            t.update(save_every=self.save_every,
                     window_steps=self.window_steps)
        else:
            t["resumes"] = 2
        return t

    def limits(self, cell):
        return dict(super().limits(cell), **(self._limits or {}))


def run(cell, seed=7, seconds=60.0, trace=False, **kw):
    """One tiny run, in a directory of its own: test workers run side by
    side, and each run starts by clearing its checkpoint directory."""
    with tempfile.TemporaryDirectory(prefix="chipbench-") as d:
        return harness.run_cell(cell, seed, seconds, trace,
                                t_proc0=time.perf_counter(),
                                bench=TinyBench(**kw), require_tpu=False,
                                workdir=d, log=lambda m: None)

"""The four-chip train mix (``traffic/train_ckpt_x4.json``: qwen3 on a
(data 2, model 2) mesh) on four virtual CPU devices, in a child process
(the device count is fixed when JAX starts): a sound run is correct, and
a run whose step leaves out the exchange between chips is not.  The
cell is not in BENCHMARK.json yet (PERF.md, Open questions); the test
adds it, with the one-chip qwen3 train cell's limits.  Without the gradient's reduction over the data axis each replica
steps on its own rows; the state device 0 holds is then the state of a
step on half of the batch, which is how the fault is planted."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tiny import ROOT

CELL = "qwen3-1.7b-l4.x4.train_ckpt"

CHILD = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src", {tests!r}]
    import tiny
    class X4(tiny.TinyBench):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.doc["workloads"].append({{"name": {cell!r},
                "config": "qwen3-1.7b-l4", "traffic": "train_ckpt_x4",
                "chips": 4, "why": "test"}})
        def limits(self, cell):
            return super().limits(self.cell("qwen3-1.7b-l4.train_ckpt"))
    tiny.TinyBench = X4
    if {fault!r}:
        import repro.train.loop as loop_mod
        real = loop_mod.make_train_step
        def make(cfg, opt, **kw):
            step = real(cfg, opt, **kw)
            def no_exchange(params, opt_state, batch):
                rows = batch["tokens"].shape[0] // 2   # data shard 0's rows
                return step(params, opt_state,
                            {{k: v[:rows] for k, v in batch.items()}})
            return no_exchange
        loop_mod.make_train_step = make
    print(json.dumps(tiny.run({cell!r}, batch=4)))
""")


def _run(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), tests=str(ROOT / "chipbench/tests"),
                        fault=fault, cell=CELL)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [False, True])
def test_x4_cell(fault):
    r = _run(fault)
    assert r["device"]["count"] == 4
    assert r["correct"] is (not fault), r["checks"]

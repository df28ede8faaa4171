"""The harness finds each cell's pieces by file name, refuses to run
without a TPU, and a sound tiny run of each mix comes out correct."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from chipbench import harness
import tiny
from tiny import ROOT

CELLS = [c["name"] for c in harness.Bench(ROOT).doc["workloads"]]
#: Cells this process can run: one CPU device (test_chipbench_x4 runs the
#: four-chip cell on four virtual devices).
ONE_CHIP = [c["name"] for c in harness.Bench(ROOT).doc["workloads"]
            if c["chips"] == 1]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return env


def test_every_cell_has_its_files():
    bench = harness.Bench(ROOT)
    for name in CELLS:
        cell = bench.cell(name)
        assert bench.config(cell)["name"] == cell["config"]
        assert bench.traffic(cell)["mix"] in ("train", "resume")
        assert bench.limits(cell)
        for m in bench.per_layer(cell):
            assert callable(harness.reader(m["name"]).read)


def test_new_cell_is_new_files_only(tmp_path):
    """A configuration, a mix, limits and a metric that exist only as new
    files and new entries are found and run."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "chipbench").mkdir()
    for sub in ("configs", "traffic", "limits", "metrics"):
        (tmp_path / "chipbench" / sub).mkdir()
    # The architecture's modules, which the configuration names, are
    # already there.
    for name in ("reference.py", "program.py"):
        shutil.copy(ROOT / "chipbench" / name, tmp_path / "chipbench" / name)
    cfg = json.loads((ROOT / "chipbench/configs/qwen3-1.7b-l4.json")
                     .read_text())
    cfg.update(tiny.TINY_SIZES, name="new-model")
    (tmp_path / "chipbench/configs/new-model.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "chipbench/traffic/train_ckpt.json").read_text())
    mix.update(seq_len=32, global_batch=2, save_every=4, window_steps=5)
    (tmp_path / "chipbench/traffic/new_mix.json").write_text(json.dumps(mix))
    (tmp_path / "chipbench/limits/new-model.new_mix.json").write_text(
        (ROOT / "chipbench/limits/qwen3-1.7b-l4.train_ckpt.json").read_text())
    (tmp_path / "chipbench/metrics/saves_seen.py").write_text(
        "def read(m):\n    return float(len(m.records.saves))\n")
    doc["configs"].append({"name": "new-model", "source": "https://x",
                           "file": "chipbench/configs/new-model.json",
                           "reduced": [], "why": "test"})
    doc["workloads"] = [{"name": "new-model.new_mix", "config": "new-model",
                         "traffic": "new_mix", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"]:
        m.pop("workloads", None)
    doc["per_layer"] = [{"name": "saves_seen", "unit": "1", "better": "lower",
                         "source": "program_counter", "layer": "test",
                         "moves": "save_stall_s"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = harness.Bench(tmp_path)
    r = harness.run_cell("new-model.new_mix", 3, 60.0, True, t_proc0=0.0,
                         bench=bench, require_tpu=False, log=lambda m: None)
    assert r["correct"] is True
    assert r["metrics"]["saves_seen"]["value"] >= 1


#: The shared reference with an output head of its own, as the program
#: builds one where ``tie_embeddings`` is off: ``lm_head`` (d, vocab),
#: drawn from the key the program draws it from.  The module runs the
#: shared reference's source in its own namespace and replaces
#: ``init_params`` and ``loss_fn``, which its ``train`` then calls.
UNTIED_REFERENCE = textwrap.dedent('''
    from pathlib import Path
    exec(compile(Path(__file__).with_name("reference.py").read_text(),
                 "reference.py", "exec"))

    _tied_init_params = init_params


    def init_params(a, seed):
        params = _tied_init_params(a, seed)
        key = jax.random.split(jax.random.PRNGKey(seed), 8)[1]
        params["lm_head"] = jax.jit(lambda: jax.random.normal(
            key, (a.d, a.vocab), F32) / math.sqrt(a.d))()
        return params


    def loss_fn(a, params, tokens, labels, *, precision="f32", fault=None):
        if fault is not None:
            raise ValueError(f"unknown reference fault {fault!r}")
        ops = _Ops(precision)
        x = params["embed"][tokens]
        aux = jnp.zeros((), F32)
        for i in range(a.layers):
            lp = jax.tree_util.tree_map(lambda w, i=i: w[i], params["layers"])
            x = x + _attention(a, ops, lp["attn"], _rms(x, lp["ln1"], a.eps))
            h = _rms(x, lp["ln2"], a.eps)
            if a.experts:
                y, aux_l = _moe(a, ops, lp["moe"], h)
                aux = aux + aux_l
            else:
                y = _swiglu(ops, lp["mlp"], h)
            x = x + y
        x = _rms(x, params["final_norm"], a.eps)
        logits = ops.ein("bsd,dv->bsv", x, params["lm_head"])
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - gold
        return jnp.mean(nll) + a.aux_weight * aux
''')

UNTIED_PROGRAM = textwrap.dedent('''
    def model_config(c):
        from repro.configs.base import ModelConfig
        return ModelConfig(
            name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], vocab=c["vocab_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            qk_norm=c["qk_norm"], rope_base=float(c["rope_theta"]),
            d_ff=c["intermediate_size"], mlp_type="swiglu",
            tie_embeddings=c["tie_word_embeddings"],
            norm_eps=float(c["rms_norm_eps"]), dtype=c["compute_dtype"])
''')


def test_new_architecture_is_new_files_only(tmp_path):
    """An architecture the shared reference cannot express (an untied
    output head) runs the train and the resume mix correct from new files
    alone: its configuration, its reference, its program mapping, mixes
    and limits.  Its program paired with the shared, tied reference is
    not correct."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench_dir = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (bench_dir / sub).mkdir(parents=True)
    shutil.copy(ROOT / "chipbench/reference.py", bench_dir / "reference.py")
    (bench_dir / "untied_reference.py").write_text(UNTIED_REFERENCE)
    (bench_dir / "untied_program.py").write_text(UNTIED_PROGRAM)
    base = json.loads((ROOT / "chipbench/configs/qwen3-1.7b-l4.json")
                      .read_text())
    configs = {"untied": "chipbench/untied_reference.py",
               "untied-tied-ref": "chipbench/reference.py"}
    for name, ref in configs.items():
        cfg = dict(base, **tiny.TINY_SIZES, name=name,
                   tie_word_embeddings=False, reference=ref,
                   program="chipbench/untied_program.py")
        (bench_dir / f"configs/{name}.json").write_text(json.dumps(cfg))
        doc["configs"].append({"name": name, "source": "https://x",
                               "file": f"chipbench/configs/{name}.json",
                               "reduced": [], "why": "test"})
    cells = {"untied.train": "train", "untied.resume": "resume",
             "untied-tied-ref.train": "train"}
    doc["workloads"] = [{"name": c, "config": c.split(".")[0],
                         "traffic": f"tiny_{mix}", "chips": 1, "why": "test"}
                        for c, mix in cells.items()]
    for mix, qwen_cell in (("train", "train_ckpt"), ("resume", "resume")):
        t = json.loads((ROOT / f"chipbench/traffic/{qwen_cell}.json")
                       .read_text())
        t.update(seq_len=32, global_batch=2)
        if mix == "train":
            t.update(save_every=4, window_steps=5)
        else:
            t["resumes"] = 2
        (bench_dir / f"traffic/tiny_{mix}.json").write_text(json.dumps(t))
        limits = (ROOT / f"chipbench/limits/qwen3-1.7b-l4.{qwen_cell}.json"
                  ).read_text()
        for c in cells:
            if cells[c] == mix:
                (bench_dir / f"limits/{c}.json").write_text(limits)
    for m in doc["end_to_end"]:
        if "workloads" in m:
            mix = "resume" if m["name"] == "resume_s" else "train"
            m["workloads"] = [c for c in cells if cells[c] == mix]
    doc["per_layer"] = []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = harness.Bench(tmp_path)
    untied = bench.config(bench.cell("untied.train"))
    assert "lm_head" in bench.reference(untied).init_params(
        bench.reference(untied).Arch.from_config(untied), 1)

    def run(cell):
        return harness.run_cell(cell, 2**31 + 13, 60.0, False, t_proc0=0.0,
                                bench=bench, require_tpu=False,
                                workdir=str(tmp_path / "run"),
                                log=lambda m: None)
    for cell in ("untied.train", "untied.resume"):
        r = run(cell)
        assert r["correct"] is True, (cell, r["checks"])
        assert r["failed"] == 0 and r["attempted"] >= 1
    r = run("untied-tied-ref.train")
    assert r["correct"] is False
    assert r["checks"]["grad_gap"][0] > r["checks"]["grad_gap"][1]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_tiny_run_is_correct(cell):
    """A fixed amount of work: the train window's steps 3..7 hold one save
    (at step 4), the resume window two resumes (``tiny.TinyBench``)."""
    r = tiny.run(cell, seed=2**31 + 11)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] == (1 if "train" in cell
                                                   else 2)
    assert list(r)[-1] == "checks"
    for k, (v, lim) in r["checks"].items():
        assert v <= lim, k
    assert set(r["metrics"]) >= {"setup_s"}


def test_no_tpu_means_no_result():
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout and "metrics" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ has no program:
    the command fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_moe_configuration_runs_correct():
    """The MoE configuration (not in a cell yet, PERF.md) through the
    train mix: program and reference agree at a tiny size."""
    class WithMoE(tiny.TinyBench):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.doc["configs"].append({
                "name": "granite-moe-3b-a800m-l4",
                "file": "chipbench/configs/granite-moe-3b-a800m-l4.json"})
            self.doc["workloads"].append({
                "name": "moe.train_ckpt", "config": "granite-moe-3b-a800m-l4",
                "traffic": "train_ckpt", "chips": 1})

        def limits(self, cell):
            # The widths of the gaps the chip runs read for this
            # configuration (PERF.md): a router's top-k flips on bf16
            # rounding, and the worst leaf shows it.
            return {"loss_gap": 0.01, "grad_gap": 0.2, "update_gap": 0.05,
                    "ckpt_mismatch": 0}

    import tempfile
    import time
    with tempfile.TemporaryDirectory() as d:
        r = harness.run_cell("moe.train_ckpt", 5, 60.0, False,
                             t_proc0=time.perf_counter(),
                             bench=WithMoE(batch=1, seq_len=64),
                             require_tpu=False, workdir=d, log=lambda m: None)
    assert r["correct"] is True, r["checks"]

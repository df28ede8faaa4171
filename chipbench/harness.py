"""Finds a cell's files by the names in ``BENCHMARK.json``, runs it, and
builds its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

* ``chipbench/configs/<config>.json``: the configuration's sizes (the
  ``file`` of its entry in ``BENCHMARK.json``), and the two modules of
  its architecture, each a file under ``chipbench/`` named by its path
  from the root: ``"reference"``, the plain float32 reference
  (``Arch.from_config``, ``Adam``, ``init_params``, ``leaf_norms``,
  ``train`` with the control's ``precision="fp8"`` and
  ``fault="half_batch"``, and ``train_flops_per_token``), which imports
  nothing of the program, and ``"program"``, whose
  ``model_config(config)`` gives the program's ``ModelConfig``;
* ``chipbench/traffic/<traffic>.json``: the mix's parameters, read by
  ``drive.Run``;
* ``chipbench/limits/<cell>.json``: the limit of each compared number;
* ``chipbench/metrics/<metric>.py``: the reader of a per-layer metric.

A new cell, configuration, architecture, mix or metric is new files and
new entries.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import statistics
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
WORKDIR_NAME = ".chipbench_run"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class Bench:
    def __init__(self, root: Path = ROOT):
        self.root = root
        self.doc = json.loads((root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, cell: Dict[str, Any]) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {cell['config']!r}")

    def reference(self, config: Dict[str, Any]):
        """The module of the configuration's plain reference."""
        return arch_module(config, "reference", self.root)

    def program(self, config: Dict[str, Any]):
        """The module that maps the configuration to the program's model."""
        return arch_module(config, "program", self.root)

    def traffic(self, cell: Dict[str, Any]) -> Dict[str, Any]:
        return json.loads((self.root / "chipbench" / "traffic" /
                           f"{cell['traffic']}.json").read_text())

    def limits(self, cell: Dict[str, Any]) -> Dict[str, float]:
        p = self.root / "chipbench" / "limits" / f"{cell['name']}.json"
        return {k: float(v) for k, v in
                json.loads(p.read_text())["limits"].items()}

    def _reports(self, metric: Dict[str, Any], cell: Dict[str, Any]) -> bool:
        return "workloads" not in metric or cell["name"] in metric["workloads"]

    def end_to_end(self, cell) -> List[Dict[str, Any]]:
        return [m for m in self.doc["end_to_end"] if self._reports(m, cell)]

    def per_layer(self, cell) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if m["moves"] in e2e and self._reports(m, cell)]


def reader(name: str, root: Path = ROOT):
    """The module of ``chipbench/metrics/<name>.py``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics._" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(config: Dict[str, Any], key: str, root: Path):
    """The module of the file under ``chipbench/`` that a configuration
    names under ``key``, loaded once a process."""
    if key not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"{key!r} module")
    path = (root / config[key]).resolve()
    if (root / "chipbench").resolve() not in path.parents:
        raise ValueError(f"configuration {config.get('name')!r}: {key} "
                         f"{config[key]!r} is not a file under chipbench/")
    name = "chipbench._arch_" + hashlib.sha1(
        str(path).encode()).hexdigest()[:16]
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        # A dataclass looks its module up in sys.modules while it is made.
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return mod


def peak(kind: str) -> Dict[str, Any]:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def require_chips(n: int):
    """The devices of this process, when they are at least ``n`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r}); "
                     f"the benchmark runs on the chip only")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    peak(devices[0].device_kind)
    return devices


class MetricInput:
    """What a per-layer reader reads from."""

    def __init__(self, run, outcome, device, peak_row, chips):
        self.records = outcome.records
        self.spans = run.spans
        self.device = device
        self.config, self.traffic = run.config, run.traffic
        self.chips = chips
        self.peak = peak_row
        self.tokens_per_step = outcome.records.tokens_per_step
        self.flops_per_token = run.ref.train_flops_per_token(
            run.config, run.traffic["seq_len"])


def end_to_end_values(outcome, t_proc0: float) -> Dict[str, float]:
    r = outcome.records
    out: Dict[str, float] = {}
    if r.t_window is not None:
        out["setup_s"] = (r.t_window - t_proc0 - r.check_s
                          - r.init_compile_s)
    if r.t_window is not None and r.t_close is not None and r.steps:
        out["train_tokens_per_s"] = (len(r.steps) * r.tokens_per_step
                                     / (r.t_close - r.t_window))
    saves = [s for s in r.saves if r.t_window is not None
             and s.t_call >= r.t_window]
    if saves:
        out["save_stall_s"] = statistics.fmean(s.t_return - s.t_call
                                               for s in saves)
    done = [x for x in r.resumes if x.loss is not None]
    if done:
        out["resume_s"] = statistics.fmean(x.t_first_loss - x.t_call
                                           for x in done)
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_proc0: float, bench: Optional[Bench] = None,
             require_tpu: bool = True, workdir: Optional[str] = None,
             log=print) -> Dict[str, Any]:
    """Run one cell and return its result line (a dict).  Checkpoints and
    traces go to ``workdir``, by default a fixed directory of the
    checkout."""
    import jax
    from chipbench import check, drive, trace_reduce
    bench = bench or Bench()
    cell = bench.cell(name)
    config, traffic = bench.config(cell), bench.traffic(cell)
    limits = bench.limits(cell)
    if require_tpu:
        devices = require_chips(cell["chips"])
        peak_row = peak(devices[0].device_kind)
    else:
        devices, peak_row = jax.devices(), None
    workdir = workdir or os.path.join(bench.root, WORKDIR_NAME)
    os.makedirs(workdir, exist_ok=True)
    run = drive.Run(config, traffic, bench.reference(config),
                    bench.program(config), seed, seconds, trace, workdir,
                    devices=devices[:cell["chips"]])
    outcome = run.run()
    e2e = end_to_end_values(outcome, t_proc0)
    checks = check.verdict(outcome.numbers, limits) or {}
    correct = bool(checks) and check.passes(checks) and \
        outcome.failed == 0 and not outcome.records.errors
    for e in outcome.records.errors:
        log(f"chipbench: {e}")
    for k, v in outcome.details.items():
        log(f"reading {k} {json.dumps(v)}")
    r = outcome.records
    log(f"reading init_compile_s {r.init_compile_s!r} (left out of setup_s)")
    if r.capped:
        log(f"chipbench: the window reached its cap of {2 * seconds!r} s")
    gaps = [b[1] - a[1] for a, b in zip(r.steps, r.steps[1:])]
    if gaps:
        log(f"reading step_gap_s median {statistics.median(gaps)!r} "
            f"max {max(gaps)!r} steps {len(r.steps)}")
    dev = devices[0]
    device: Dict[str, Any] = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": outcome.memory_peak_bytes}
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": outcome.attempted,
                              "failed": outcome.failed}
    if trace:
        xp = trace_reduce.find_xplane(run.tracer.dir)
        dt = trace_reduce.reduce(xp) if xp else None
        if dt is not None:
            device["busy_s"] = dt.busy_s
            device["window_s"] = dt.window_s
        m = MetricInput(run, outcome, dt, peak_row, cell["chips"])
        metrics = {}
        for spec in bench.per_layer(cell):
            v = reader(spec["name"], bench.root).read(m)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        result["metrics"] = metrics
        if dt is not None:
            result["breakdown"] = {"device_ops": [list(x) for x in dt.top_ops],
                                   "idle_gaps": [list(x) for x in
                                                 dt.idle_gaps]}
    else:
        metrics = {}
        for spec in bench.end_to_end(cell):
            if spec["name"] in e2e:
                metrics[spec["name"]] = {"value": e2e[spec["name"]],
                                         "unit": spec["unit"]}
            else:
                correct = result["correct"] = False
                log(f"chipbench: no reading of {spec['name']}")
        result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]], t_proc0: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json "
                                             "on the TPU it is started on.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the program ({ROOT / 'src' / 'repro'}) is not in "
              f"this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_proc0=t_proc0, log=log)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    for k, (v, lim) in result["checks"].items():
        log(f"check {k} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0

"""Runs one cell's traffic through the program's own
entry (``repro.train.loop.train`` with its ``CheckpointManager``) and
records what the metrics are read from.

A traffic file (``chipbench/traffic/<name>.json``) is data for one of two
mixes, named by its ``"mix"`` key:

``train``
    Set-up initialises the state from the seed (the loop's jitted init),
    compiles the step, and runs the first ``checked_steps`` steps, which
    the reference follows.  The window then runs the next
    ``window_steps`` steps of the same loop, with a save every
    ``save_every`` steps: a fixed amount of work, so that the save falls
    at the same step of every run and its background write runs beside
    the steps that follow it.  ``--seconds`` only caps the window (at
    twice its value).  The device state at the window's last save is
    checksummed before the save; after the window that save is read back
    through the scda reader and its checksums compared.
``resume``
    Set-up trains ``save_at + 1`` steps, saves at ``save_at`` (a durable
    commit), and dies there through the loop's ``should_die`` hook.  The
    window makes ``resumes`` cold resumes: drop the checkpoint's pages
    from the page cache, call ``train()`` on the directory, and let it
    die right after the first step after the restore.

Set-up time leaves out the compile of the program's init in the first
``train()`` call (``init_compile_s``): the program builds that program
with the seed as a constant, so every new seed compiles it anew, and a
run's set-up would otherwise depend on whether its seed ran before.

This module wraps the step call, ``CheckpointManager.save`` and each
``train()`` call in ``jax.profiler.TraceAnnotation``s (``chipbench.*``)
and times them on the host clock; the program itself is not changed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from chipbench import check, reference
from chipbench.data import DataConfig, SyntheticTokens

#: Bytes kept free on the checkpoint's file system beyond two states.
DISK_MARGIN = 2 << 30


@dataclasses.dataclass
class Save:
    step: int
    t_call: float
    t_return: float
    committed: Optional[bool] = None


@dataclasses.dataclass
class Resume:
    t_call: float
    t_first_loss: float = float("nan")
    start_step: Optional[int] = None
    loss: Optional[float] = None
    sums: Any = None          # device checksums of the restored state
    error: Optional[str] = None


@dataclasses.dataclass
class Records:
    """Host-clock readings of one run (``time.perf_counter`` seconds)."""
    t_window: Optional[float] = None
    t_close: Optional[float] = None
    steps: List[tuple] = dataclasses.field(default_factory=list)
    saves: List[Save] = dataclasses.field(default_factory=list)
    resumes: List[Resume] = dataclasses.field(default_factory=list)
    #: set-up seconds spent only on the correctness check's captures
    check_s: float = 0.0
    #: seconds JAX spent compiling (or loading) the first train() call's
    #: init program, which the program keys by the seed
    init_compile_s: float = 0.0
    #: the window reached the cap of twice ``--seconds`` before its steps
    capped: bool = False
    tokens_per_step: int = 0
    errors: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Outcome:
    records: Records
    numbers: Dict[str, float]
    attempted: int
    failed: int
    memory_peak_bytes: int
    #: readings behind the numbers, printed for whoever sets the limits
    details: Dict[str, Any] = dataclasses.field(default_factory=dict)


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) feed-forwards are driven")
    experts = c.get("num_local_experts", 0)
    return ModelConfig(
        name=c["name"], family="moe" if experts else "dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        vocab=c["vocab_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        qk_norm=c["qk_norm"], rope_base=float(c["rope_theta"]),
        d_ff=c["intermediate_size"], mlp_type="swiglu",
        n_experts=experts, experts_top_k=c.get("num_experts_per_tok", 0),
        capacity_factor=c.get("moe_capacity_factor", 1.25),
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=float(c["rms_norm_eps"]), dtype=c["compute_dtype"])


@contextlib.contextmanager
def instrumented(rec: Records, managers: list):
    """Wrap the loop's step and save calls for the duration."""
    import repro.train.loop as loop_mod
    base_mgr, base_jit = loop_mod.CheckpointManager, loop_mod.jit_train_step

    class TimedManager(base_mgr):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            managers.append(self)

        def save(self, step, tree, **kw):
            s = Save(step, time.perf_counter(), float("nan"))
            rec.saves.append(s)
            prev = [p for p in rec.saves[:-1] if p.committed is None]
            try:
                with jax.profiler.TraceAnnotation("chipbench.save"):
                    super().save(step, tree, **kw)
            finally:
                s.t_return = time.perf_counter()
                # save() joined the previous save first: it is on disk
                # now, or it failed (and save() raised its error).
                on_disk = set(self.all_steps())
                for p in prev:
                    p.committed = p.step in on_disk

    def jit_step(*a, **kw):
        fn = base_jit(*a, **kw)

        def step(*args):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                return fn(*args)
        return step

    loop_mod.CheckpointManager, loop_mod.jit_train_step = \
        TimedManager, jit_step
    try:
        yield
    finally:
        loop_mod.CheckpointManager, loop_mod.jit_train_step = \
            base_mgr, base_jit


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the Records whose ``init_compile_s`` takes the compile events now
_compile_sink: List[Records] = []


def _on_compile_event(event: str, secs: float, **_: Any) -> None:
    # JAX reports this event for a compile and for a load from the
    # persistent cache alike.
    if event == _COMPILE_EVENT and _compile_sink:
        _compile_sink[-1].init_compile_s += secs


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


class _Tracer:
    """Starts the profiler and the program's span collector when the
    window opens (with ``--trace 1``), and stops them when it closes."""

    def __init__(self, on: bool, trace_dir: str):
        self.on, self.dir = on, trace_dir
        self.collector = None
        self.epoch = None   # perf_counter seconds at the collector's 0

    def start(self):
        if not self.on:
            return
        from repro.core import trace as rtrace
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.collector = rtrace.TraceCollector()
        rtrace.install(self.collector)
        t = rtrace.TraceCollector.now()
        self.collector.end("chipbench.epoch", "bench", t)
        ev = self.collector.chrome()["traceEvents"][-1]
        self.epoch = t / 1e9 - ev["ts"] / 1e6

    def stop_profiler(self):
        if self.on and self.collector is not None:
            jax.profiler.stop_trace()
            self.on = False

    def spans(self) -> List[Dict[str, Any]]:
        """The program's spans, with ``t0``/``t1`` on the host clock."""
        if self.collector is None:
            return []
        from repro.core import trace as rtrace
        out = []
        for e in self.collector.chrome()["traceEvents"]:
            if e.get("ph") != "X":
                continue
            t0 = self.epoch + e["ts"] / 1e6
            out.append({"name": e["name"], "cat": e.get("cat"),
                        "args": e.get("args") or {}, "t0": t0,
                        "t1": t0 + e["dur"] / 1e6})
        if rtrace.collector() is self.collector:
            rtrace.uninstall()
        return out


def _host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def _diff_norm(a: np.ndarray, b: np.ndarray) -> float:
    a, b = a.reshape(-1), b.reshape(-1)
    acc, step = 0.0, 1 << 24
    for i in range(0, a.size, step):
        d = a[i:i + step].astype(np.float64) - b[i:i + step]
        acc += float(np.dot(d, d))
    return float(np.sqrt(acc))


def evict(directory: str) -> None:
    """Flush and drop every checkpoint file's pages from the page cache."""
    for name in os.listdir(directory):
        p = os.path.join(directory, name)
        if not os.path.isfile(p):
            continue
        fd = os.open(p, os.O_RDONLY)
        try:
            os.fsync(fd)
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        finally:
            os.close(fd)


def _check_disk(directory: str, state_bytes: int) -> None:
    free = shutil.disk_usage(directory).free
    need = 2 * state_bytes + DISK_MARGIN
    if free < need:
        raise RuntimeError(f"{directory}: {free} bytes free, the run needs "
                           f"{need} (two train states and a margin)")


def _state_bytes(cfg) -> int:
    from repro.train.loop import abstract_state
    return sum(a.size * a.dtype.itemsize for a in
               jax.tree_util.tree_leaves(abstract_state(cfg)))


def _memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _free(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


class _Capture:
    """The program's own readings of its first steps, for the reference:
    losses, the first gradient (from AdamW's first moment after step 0:
    ``mu_1 = (1 - b1) g``) and the parameters' change over the steps."""

    def __init__(self, n: int, b1: float, rec: Records):
        self.n, self.b1, self.rec = n, b1, rec
        self.losses: List[float] = []
        self.grad_norms: Dict[str, float] = {}
        self.update_norms: Dict[str, float] = {}
        self._p0: Dict[str, np.ndarray] = {}

    def timed(self, fn):
        t = time.perf_counter()
        try:
            fn()
        finally:
            self.rec.check_s += time.perf_counter() - t

    def start(self, state) -> None:
        def grab():
            self._p0 = {jax.tree_util.keystr(k): _host(v) for k, v in
                        jax.tree_util.tree_leaves_with_path(state["params"])}
        self.timed(grab)

    def step(self, step: int, state, loss: float) -> None:
        if step >= self.n:
            return
        self.losses.append(loss)
        if step == 0:
            def grads():
                mu = reference.leaf_norms(state["opt"].mu)
                self.grad_norms = {k: v / (1 - self.b1)
                                   for k, v in mu.items()}
            self.timed(grads)
        if step == self.n - 1:
            def change():
                for k, v in jax.tree_util.tree_leaves_with_path(
                        state["params"]):
                    name = jax.tree_util.keystr(k)
                    self.update_norms[name] = _diff_norm(_host(v),
                                                         self._p0[name])
                self._p0 = {}
            self.timed(change)

    def readings(self) -> Dict[str, Any]:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "update_norms": self.update_norms}


class Run:
    """One run of one cell: ``config`` and ``traffic`` are the parsed
    files, ``workdir`` a fixed directory inside the checkout."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, seconds: float, trace: bool, workdir: str,
                 devices=None):
        self.config, self.traffic = config, traffic
        self.seed = seed % (1 << 32)   # the program's PRNG key holds 32 bits
        self.seconds, self.workdir = seconds, workdir
        self.devices = devices or jax.devices()
        self.rec = Records()
        self.tracer = _Tracer(trace, os.path.join(workdir, "trace"))
        self.cfg = model_config(config)
        self.arch = reference.Arch.from_config(config)
        o = traffic["optimizer"]
        self.adam = reference.Adam(**o)
        self.ckpt_dir = os.path.join(workdir, "ckpt")
        self.managers: list = []
        self.spans: List[Dict[str, Any]] = []

    # -- pieces shared by the mixes ----------------------------------------
    def _setup_common(self):
        from repro.launch.mesh import make_host_mesh
        from repro.optim.adamw import AdamWConfig
        tr = self.traffic
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
        os.makedirs(self.ckpt_dir)
        _check_disk(self.ckpt_dir, _state_bytes(self.cfg))
        os.sync()   # a settled page cache: no earlier run's dirty pages
        data_axes, model_axes = tr["mesh"]
        self.mesh = make_host_mesh(data_axes, model_axes)
        self.data = SyntheticTokens(DataConfig(
            vocab=self.cfg.vocab, seq_len=tr["seq_len"],
            global_batch=tr["global_batch"], seed=self.seed))
        self.opt_cfg = AdamWConfig(**tr["optimizer"])
        self.rec.tokens_per_step = tr["seq_len"] * tr["global_batch"]

    def _loop_cfg(self, ckpt_every: int):
        from repro.train.loop import TrainLoopConfig
        tr = self.traffic
        return TrainLoopConfig(
            total_steps=tr["optimizer"]["total_steps"], ckpt_every=ckpt_every,
            ckpt_dir=self.ckpt_dir, ckpt_keep=tr.get("ckpt_keep", 1),
            ckpt_compressed=tr.get("ckpt_compressed", False),
            log_every=1 << 40, seed=self.seed)

    def _train(self, loop, hooks, count_init=False) -> Optional[Dict[str, Any]]:
        """One ``train()`` call; a death by ``should_die`` returns None.
        With ``count_init`` the compiles until ``on_start`` (the init's)
        go to ``init_compile_s``."""
        from repro.train.loop import train
        tr = self.traffic
        if count_init:
            on_start = hooks.get("on_start", lambda s, st: None)

            def started(start_step, state):
                _compile_sink.clear()
                on_start(start_step, state)
            hooks = {**hooks, "on_start": started}
            _compile_sink[:] = [self.rec]
        with jax.profiler.TraceAnnotation("chipbench.train"):
            try:
                return train(self.cfg, loop, self.opt_cfg, data=self.data,
                             mesh=self.mesh, seq_len=tr["seq_len"],
                             global_batch=tr["global_batch"], hooks=hooks)
            except SystemExit:
                return None
            finally:
                _compile_sink.clear()

    def _reference(self, steps: int, change_after: int) -> Dict[str, Any]:
        batches = [self.data.global_batch_shard(s, 0,
                                                self.traffic["global_batch"])
                   for s in range(steps)]
        return reference.train(self.arch, self.adam, self.seed, batches,
                               change_after=change_after,
                               device=self.devices[0])

    # -- the mixes -------------------------------------------------------
    def run(self) -> Outcome:
        mix = self.traffic["mix"]
        if mix not in ("train", "resume"):
            raise ValueError(f"unknown traffic mix {mix!r}")
        self._setup_common()
        with instrumented(self.rec, self.managers):
            return self._run_train() if mix == "train" \
                else self._run_resume()

    def _cap(self) -> bool:
        """The window has run twice ``--seconds``: it closes early."""
        if time.perf_counter() - self.rec.t_window < 2 * self.seconds:
            return False
        self.rec.capped = True
        return True

    def _run_train(self) -> Outcome:
        tr, rec = self.traffic, self.rec
        n_check, every = tr["checked_steps"], tr["save_every"]
        last_step = n_check + tr["window_steps"] - 1
        cap = _Capture(n_check, self.adam.b1, rec)
        saved_sums: Dict[int, Any] = {}

        def on_start(start_step, state):
            cap.start(state)

        def on_step(step, state, metrics):
            t = time.perf_counter()
            loss = float(metrics["loss"])
            if rec.t_window is None:
                cap.step(step, state, loss)
                if step == n_check - 1:
                    # Compile (or load) the checksums before the window.
                    cap.timed(lambda: check.checksum_host(
                        check.checksums(state)))
                    self.tracer.start()
                    rec.t_window = time.perf_counter()
                return
            rec.steps.append((step, t))
            if step % every == 0:
                # Dispatched before the save; the next step, which takes
                # these buffers over, runs after it.
                saved_sums[step] = check.checksums(state)

        def should_die(step):
            if rec.t_window is None or (step < last_step and not self._cap()):
                return False
            rec.t_close = time.perf_counter()
            self.tracer.stop_profiler()
            return True

        hooks = {"on_start": on_start, "on_step": on_step,
                 "should_die": should_die}
        try:
            self._train(self._loop_cfg(every), hooks, count_init=True)
        except Exception as e:  # noqa: BLE001 - a failed save surfaces here
            rec.errors.append(f"train: {type(e).__name__}: {e}")
        self.tracer.stop_profiler()
        self.spans = self.tracer.spans()
        peak = _memory_peak(self.devices)
        gc.collect()
        saves = [s for s in rec.saves if rec.t_window is not None
                 and s.t_call >= rec.t_window]
        mgr = self.managers[-1] if self.managers else None
        on_disk = set(mgr.all_steps()) if mgr is not None else set()
        for s in saves:
            if s.committed is None:
                s.committed = s.step in on_disk
        failed = sum(not s.committed for s in saves)
        numbers: Dict[str, float] = {}
        last = saves[-1] if saves else None
        if last is None or not last.committed or last.step not in saved_sums:
            numbers["ckpt_mismatch"] = float("inf")
        else:
            from repro.checkpoint import pytree_io
            from repro.train.loop import abstract_state
            want = check.checksum_host(saved_sums[last.step])
            restored, _ = pytree_io.restore(
                mgr.path_for(last.step), abstract_state(self.cfg, self.mesh))
            got = check.checksum_host(check.checksums(restored))
            _free(restored)
            numbers["ckpt_mismatch"] = float(check.count_mismatch(got, want))
        saved_sums.clear()
        ref = self._reference(n_check, n_check)
        numbers.update(check.gaps(cap.readings(), ref))
        return Outcome(rec, numbers, len(saves), failed, peak,
                       check.details(cap.readings(), ref))

    def _run_resume(self) -> Outcome:
        tr, rec = self.traffic, self.rec
        n_check, at = tr["checked_steps"], tr["save_at"]
        cap = _Capture(n_check, self.adam.b1, rec)
        want: Dict[str, Any] = {}
        setup_losses: Dict[int, float] = {}

        def on_step(step, state, metrics):
            loss = float(metrics["loss"])
            cap.step(step, state, loss)
            setup_losses[step] = loss
            if step == at:
                t = time.perf_counter()
                want["sums"] = check.checksum_host(check.checksums(state))
                rec.check_s += time.perf_counter() - t

        loop = self._loop_cfg(at)
        self._train(loop, {"on_start": lambda s, st: cap.start(st),
                           "on_step": on_step,
                           "should_die": lambda step: step == at},
                    count_init=True)
        os.sync()
        self.tracer.start()
        rec.t_window = time.perf_counter()
        for _ in range(tr["resumes"]):
            self._one_resume(loop, at)
            if self._cap():
                break
        rec.t_close = time.perf_counter()
        self.tracer.stop_profiler()
        self.spans = self.tracer.spans()
        peak = _memory_peak(self.devices)
        failed, bad_sums, losses = 0, 0, []
        for r in rec.resumes:
            sums = check.checksum_host(r.sums) if r.sums is not None else {}
            mismatch = check.count_mismatch(sums, want.get("sums", {}))
            bad_sums += mismatch > 0
            if r.error or r.start_step != at or r.loss is None or mismatch:
                failed += 1
            if r.loss is not None:
                losses.append(r.loss)
            r.sums = None
        ref = self._reference(at + 2, n_check)
        got = {**cap.readings(),
               "losses": [setup_losses.get(i, float("nan"))
                          for i in range(at + 1)]}
        numbers = check.gaps(got, {**ref, "losses": ref["losses"][:at + 1]})
        numbers["resume_loss_gap"] = check.loss_gap(
            losses, ref["losses"][at + 1]) if losses else float("inf")
        numbers["restore_mismatch"] = float(bad_sums + (not rec.resumes))
        return Outcome(rec, numbers, len(rec.resumes), failed, peak,
                       check.details(got, {**ref, "losses":
                                           ref["losses"][:at + 1]}))

    def _one_resume(self, loop, at: int) -> None:
        rec = self.rec
        with jax.profiler.TraceAnnotation("chipbench.evict"):
            evict(self.ckpt_dir)
        r = Resume(t_call=time.perf_counter())
        rec.resumes.append(r)

        def on_start(start_step, state):
            r.start_step = start_step
            r.sums = check.checksums(state)   # dispatched, read later

        def on_step(step, state, metrics):
            r.t_first_loss = time.perf_counter()
            r.loss = float(metrics["loss"])

        try:
            out = self._train(loop, {"on_start": on_start, "on_step": on_step,
                                     "should_die": lambda step: True})
        except Exception as e:  # noqa: BLE001 - a failed resume is counted
            r.error = f"{type(e).__name__}: {e}"
            return
        if out is not None:
            r.error = "the resumed run did not stop after its first step"

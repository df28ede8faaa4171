"""The fault-tolerant training loop: restore-or-init, step, async checkpoint.

Every run is a restart: boot always goes through
``CheckpointManager.restore_or_init`` so a fresh start, a crash recovery,
and an elastic resize are the same code path (the scda serial-equivalence
guarantee is what makes the third case trivial).  Checkpoint failures are
caught and logged — the paper's §A.6 "file errors should never crash the
simulation" — while training continues.

With a ``repro.core.trace`` collector active, each step records
``train.batch`` (the next batch), ``train.step`` (the step call: its
first call holds the trace, the compile and the transfer of its
arguments), ``train.loss_read`` (the wait for the step's loss) and a
``train.hook`` span around each hook call, named by its ``hook``
argument.  ``train.compile`` spans are JAX's own compile-stage durations
(tracing, lowering, compile or persistent-cache load), each ending where
JAX reported it, on the thread that compiled.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, Optional

import jax

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core import trace as _trace
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.distributed import sharding as sh
from repro.launch import specs
from repro.models import init_lm
from repro.optim import adamw
from repro.train.step import make_train_step

log = logging.getLogger("repro.train")

#: Prefix of the ``jax.monitoring`` duration events that become
#: ``train.compile`` spans.
COMPILE_EVENTS = "/jax/core/compile/"
_compiles_watched = False


def _record_compile(event: str, secs: float, **kw: Any) -> None:
    """``jax.monitoring`` listener: a compile-stage duration as a
    ``train.compile`` span ending now, while a collector is active."""
    c = _trace.collector()
    if c is None or not event.startswith(COMPILE_EVENTS):
        return
    c.end("compile", "train", c.now() - int(secs * 1e9),
          {"event": event, "fun": kw.get("fun_name")})


def _watch_compiles() -> None:
    global _compiles_watched
    if not _compiles_watched:
        _compiles_watched = True
        jax.monitoring.register_event_duration_secs_listener(_record_compile)


def _hook(hooks: Dict[str, Callable], name: str, tc, *args: Any) -> Any:
    """Call hook ``name`` if there is one: a ``train.hook`` span when a
    collector ``tc`` is active."""
    fn = hooks.get(name)
    if fn is None:
        return None
    if tc is None:
        return fn(*args)
    t0 = tc.now()
    try:
        return fn(*args)
    finally:
        tc.end("hook", "train", t0, {"hook": name})


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro-ckpts"
    ckpt_keep: int = 3
    ckpt_compressed: bool = False
    log_every: int = 10
    seed: int = 0
    grad_compress: bool = False


def init_state(cfg: ModelConfig, seed: int) -> Dict[str, Any]:
    """A fresh train state: parameters plus AdamW moments."""
    params = init_lm(cfg, jax.random.PRNGKey(seed))
    return {"params": params, "opt": adamw.init(params)}


def abstract_state(cfg: ModelConfig, mesh=None):
    """The train state's shapes; under a mesh each leaf also carries the
    NamedSharding it lives under (``launch.specs``), so a restore lands
    every shard on its device and a fresh init is placed the same way."""
    if mesh is None:
        return jax.eval_shape(lambda: init_state(cfg, 0))
    params = specs.abstract_params(cfg, mesh)
    return {"params": params,
            "opt": specs.abstract_opt_state(cfg, mesh, params)}


def jit_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                   like, mesh=None, *, loss_chunk: int = 256,
                   grad_transform: Optional[Callable] = None):
    """The jitted step; under a mesh its outputs keep the state's
    shardings, so every step runs one executable."""
    step = make_train_step(cfg, opt_cfg, loss_chunk=loss_chunk,
                           grad_transform=grad_transform)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1))
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, like)
    return jax.jit(step, donate_argnums=(0, 1),
                   out_shardings=(shardings["params"], shardings["opt"],
                                  sh.replicated(mesh)))


def train(cfg: ModelConfig, loop: TrainLoopConfig,
          opt_cfg: Optional[adamw.AdamWConfig] = None,
          data: Optional[SyntheticTokens] = None,
          mesh=None,
          seq_len: int = 128, global_batch: int = 8,
          hooks: Optional[Dict[str, Callable]] = None) -> Dict[str, Any]:
    """Run (or resume) a training job; returns final metrics + state.

    ``hooks``: ``on_start(start_step, state)`` once the state is restored
    or initialized, ``on_step(step, state, metrics)`` after every step,
    ``should_die(step)`` after its checkpoint (failure injection).
    """
    prev = sh.get_policy()
    if mesh is not None:
        sh.set_mesh(mesh)
    try:
        return _train(cfg, loop, opt_cfg, data, mesh, seq_len,
                      global_batch, hooks or {})
    finally:
        sh.set_mesh(prev.mesh, prev.sp_decode_axis)


def _train(cfg, loop, opt_cfg, data, mesh, seq_len, global_batch, hooks):
    opt_cfg = opt_cfg or adamw.AdamWConfig(total_steps=loop.total_steps)
    data = data or SyntheticTokens(DataConfig(
        vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch,
        seed=loop.seed))

    grad_transform = None
    if loop.grad_compress:
        from repro.distributed.grad_compress import compress_grads
        grad_transform = compress_grads

    # like = the abstract state tree: restore rebuilds the exact structure
    # (incl. the optimizer NamedTuple) under any current topology.
    like = abstract_state(cfg, mesh)
    step_fn = jit_train_step(cfg, opt_cfg, like, mesh,
                             loss_chunk=min(256, data.cfg.seq_len),
                             grad_transform=grad_transform)
    init_fn = functools.partial(init_state, cfg, loop.seed)
    if mesh is not None:
        init_fn = jax.jit(init_fn, out_shardings=jax.tree_util.tree_map(
            lambda a: a.sharding, like))

    _watch_compiles()
    mgr = CheckpointManager(loop.ckpt_dir, keep=loop.ckpt_keep,
                            compressed=loop.ckpt_compressed)
    state, start_step = mgr.restore_or_init(init_fn, like=like)
    if start_step >= 0:
        log.info("resumed from checkpoint at step %d", start_step)
    _hook(hooks, "on_start", _trace.collector(), start_step, state)
    metrics: Dict[str, Any] = {}
    losses = []
    t0 = time.time()
    for step in range(start_step + 1, loop.total_steps):
        # Looked up once a step: a collector may be installed mid-run.
        tc = _trace.collector()
        ts = 0 if tc is None else tc.now()
        batch = data.sharded_batch(step, mesh)
        if tc is not None:
            tc.end("batch", "train", ts, {"step": step})
            ts = tc.now()
        params, opt, metrics = step_fn(state["params"], state["opt"], batch)
        state = {"params": params, "opt": opt}
        if tc is not None:
            tc.end("step", "train", ts, {"step": step})
            ts = tc.now()
        losses.append(float(metrics["loss"]))
        if tc is not None:
            tc.end("loss_read", "train", ts, {"step": step})
        if step % loop.log_every == 0 or step == loop.total_steps - 1:
            log.info("step %d loss %.4f gnorm %.3f lr %.2e (%.2fs)",
                     step, float(metrics["loss"]),
                     float(metrics["grad_norm"]), float(metrics["lr"]),
                     time.time() - t0)
        _hook(hooks, "on_step", tc, step, state, metrics)
        if loop.ckpt_every and step % loop.ckpt_every == 0 and step > 0:
            try:
                mgr.save(step, state)
            except Exception as e:  # noqa: BLE001 — never crash the job
                log.error("checkpoint save failed (continuing): %s", e)
        if _hook(hooks, "should_die", tc, step):
            # failure-injection hook used by tests/examples
            mgr.wait()
            raise SystemExit(f"injected failure at step {step}")
    try:
        mgr.save(loop.total_steps - 1, state, blocking=True)
    except Exception as e:  # noqa: BLE001
        log.error("final checkpoint failed: %s", e)
    return {"state": state, "metrics": metrics, "losses": losses,
            "start_step": start_step, "manager": mgr}

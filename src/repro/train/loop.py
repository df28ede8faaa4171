"""The fault-tolerant training loop: restore-or-init, step, async checkpoint.

Every run is a restart: boot always goes through
``CheckpointManager.restore_or_init`` so a fresh start, a crash recovery,
and an elastic resize are the same code path (the scda serial-equivalence
guarantee is what makes the third case trivial).  Checkpoint failures are
caught and logged — the paper's §A.6 "file errors should never crash the
simulation" — while training continues.
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
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.distributed import sharding as sh
from repro.launch import specs
from repro.models import init_lm
from repro.optim import adamw
from repro.train.step import make_train_step

log = logging.getLogger("repro.train")


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

    mgr = CheckpointManager(loop.ckpt_dir, keep=loop.ckpt_keep,
                            compressed=loop.ckpt_compressed)
    state, start_step = mgr.restore_or_init(init_fn, like=like)
    if start_step >= 0:
        log.info("resumed from checkpoint at step %d", start_step)
    if "on_start" in hooks:
        hooks["on_start"](start_step, state)
    metrics: Dict[str, Any] = {}
    losses = []
    t0 = time.time()
    for step in range(start_step + 1, loop.total_steps):
        batch = data.sharded_batch(step, mesh)
        params, opt, metrics = step_fn(state["params"], state["opt"], batch)
        state = {"params": params, "opt": opt}
        losses.append(float(metrics["loss"]))
        if step % loop.log_every == 0 or step == loop.total_steps - 1:
            log.info("step %d loss %.4f gnorm %.3f lr %.2e (%.2fs)",
                     step, float(metrics["loss"]),
                     float(metrics["grad_norm"]), float(metrics["lr"]),
                     time.time() - t0)
        if "on_step" in hooks:
            hooks["on_step"](step, state, metrics)
        if loop.ckpt_every and step % loop.ckpt_every == 0 and step > 0:
            try:
                mgr.save(step, state)
            except Exception as e:  # noqa: BLE001 — never crash the job
                log.error("checkpoint save failed (continuing): %s", e)
        if "should_die" in hooks and hooks["should_die"](step):
            # failure-injection hook used by tests/examples
            mgr.wait()
            raise SystemExit(f"injected failure at step {step}")
    try:
        mgr.save(loop.total_steps - 1, state, blocking=True)
    except Exception as e:  # noqa: BLE001
        log.error("final checkpoint failed: %s", e)
    return {"state": state, "metrics": metrics, "losses": losses,
            "start_step": start_step, "manager": mgr}

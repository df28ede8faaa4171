"""Bring-up smoke of the training path and its scda checkpoints on a TPU.

Trains qwen3-1.7b at its published widths, with the depth cut to
``LAYERS``, through ``repro.train.loop.train`` and ``CheckpointManager``:

1. reference: an uninterrupted run of ``STEPS`` steps;
2. die: the same run, checkpointing every ``CKPT_EVERY`` steps, killed
   right after the checkpoint at step ``DIE_AT`` by the loop's own
   ``should_die`` hook;
3. resume: a second ``train()`` on the die run's directory.  It must start
   from step ``DIE_AT`` and end with the reference's state, byte for
   byte, and the reference's losses, bit for bit.

``--four-chips`` runs only the sharded variant: phases 1 and 2 on a
(data=2, model=2) mesh, phase 3 on a (data=4, model=1) mesh.  The restored
state must equal the reference's state at step ``DIE_AT`` byte for byte
and be spread over all four devices; the losses after the resume are
computed under another partition, so they are held to a tolerance.

Earlier lines print bring-up readings, each labelled with the device it
ran on (readings, not benchmark metrics).  The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU, or any failed check, the script exits non-zero and
prints no such line.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips, one host
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

_SRC = Path(__file__).resolve().parent / "src"
if not (_SRC / "repro").is_dir():
    sys.exit(f"chip_smoke: {_SRC / 'repro'} is missing; run from a checkout")
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import trace  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.loop import TrainLoopConfig, abstract_state, train  # noqa: E402

#: qwen3-1.7b cut from 28 layers to LAYERS, at TRAIN_4K's sequence length.
LAYERS, SEQ_LEN = 4, 4096
#: Global batch.  One chip runs batch 1: the step compiled at batch 2
#: needs more than the chip's 16 GiB of HBM.
BATCH_ONE_CHIP, BATCH_FOUR_CHIPS = 1, 4
#: The reference runs STEPS steps; the die run saves every CKPT_EVERY and
#: dies right after the save at DIE_AT.
STEPS, CKPT_EVERY, DIE_AT = 6, 3, 3

#: Largest relative loss difference allowed after a resume under another
#: mesh: the same state, but the reductions run in another order.
CROSS_MESH_LOSS_RTOL = 1e-3


class CompileClock:
    """Seconds JAX reports tracing, lowering and compiling, summed from
    the moment :meth:`install` registers it with ``jax.monitoring``."""

    def __init__(self) -> None:
        self.seconds = 0.0

    def install(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, secs: float, **_: Any) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs


@dataclasses.dataclass
class Phase:
    name: str
    start_step: int
    losses: List[float]
    steps_on_disk: List[int]
    state: Optional[Dict[str, Any]]       # host copy of the final state
    captured: Optional[Dict[str, Any]]    # host copy at ``capture_at``
    device_bytes: Dict[int, int]          # state bytes per device id
    bytes_in_use: Dict[int, Any]          # device memory once started
    marks: Dict[Any, float]               # host clock at start and steps
    compile_s: float
    events: List[Dict[str, Any]]          # repro.core.trace events
    counters: Dict[str, int]


def qwen3_config(layers: int):
    """qwen3-1.7b at its published widths with ``layers`` layers."""
    return dataclasses.replace(get_config("qwen3-1.7b"), n_layers=layers)


def to_host(tree):
    """Host copies of a device tree, taken from device-side copies: a host
    copy of the array itself is cached on it, and would make the loop's
    own snapshot of that array look free."""
    return jax.tree_util.tree_map(lambda x: np.asarray(jnp.copy(x)), tree)


def device_bytes(tree) -> Dict[int, int]:
    out: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for s in leaf.addressable_shards:
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return out


def run_phase(name: str, cfg, ckpt_dir: str, mesh, *, steps: int,
              ckpt_every: int, seq_len: int, batch: int,
              die_at: Optional[int] = None,
              capture_at: Optional[int] = None,
              clock: Optional[CompileClock] = None) -> Phase:
    """One ``train()`` call.  ``capture_at`` keeps a host copy of the state
    at that step: after it ran, or as restored when the run starts there.
    A run that ``die_at`` kills must end in ``SystemExit``."""
    loop = TrainLoopConfig(total_steps=steps, ckpt_every=ckpt_every,
                           ckpt_dir=ckpt_dir, ckpt_keep=1, log_every=steps)
    marks: Dict[Any, float] = {}
    seen: Dict[str, Any] = {"losses": [], "start": None, "captured": None,
                            "device_bytes": {}, "in_use": {}}

    def on_start(start_step, state):
        marks["start"] = time.perf_counter()
        seen["start"] = start_step
        seen["device_bytes"] = device_bytes(state)
        seen["in_use"] = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                          for d in mesh.devices.flat}
        if start_step == capture_at:
            seen["captured"] = to_host(state)

    def on_step(step, state, metrics):
        marks[step] = time.perf_counter()
        seen["losses"].append(float(metrics["loss"]))
        if step == capture_at:
            seen["captured"] = to_host(state)

    hooks = {"on_start": on_start, "on_step": on_step}
    if die_at is not None:
        hooks["should_die"] = lambda step: step == die_at
    clock = clock or CompileClock()
    compile0 = clock.seconds
    out = None
    with trace.scoped(trace.TraceCollector()) as tc:
        try:
            out = train(cfg, loop, AdamWConfig(total_steps=steps),
                        mesh=mesh, seq_len=seq_len, global_batch=batch,
                        hooks=hooks)
        except SystemExit:
            if die_at is None:
                raise
        else:
            if die_at is not None:
                raise RuntimeError(f"{name}: the run did not die at "
                                   f"step {die_at}")
    state = None
    if out is not None:
        # The loop logs and swallows save errors: a lost save shows in
        # the steps on disk, which check() compares.
        out["manager"].wait()
        state = to_host(out["state"])
        on_disk = out["manager"].all_steps()
        del out
    else:
        # This process's lock is shared, so a second manager may list.
        on_disk = CheckpointManager(ckpt_dir).all_steps()
    return Phase(name=name, start_step=seen["start"], losses=seen["losses"],
                 steps_on_disk=on_disk, state=state,
                 captured=seen["captured"],
                 device_bytes=seen["device_bytes"],
                 bytes_in_use=seen["in_use"], marks=marks,
                 compile_s=clock.seconds - compile0,
                 events=tc.chrome()["traceEvents"],
                 counters=tc.metrics.snapshot()["counters"])


def run_die_resume(cfg, root: str, train_mesh, resume_mesh, *,
                   steps: int = STEPS, ckpt_every: int = CKPT_EVERY,
                   die_at: int = DIE_AT,
                   seq_len: int, batch: int,
                   clock: Optional[CompileClock] = None):
    """Phases 1–3 under ``root``; returns their :class:`Phase` records."""
    same_mesh = resume_mesh is train_mesh
    kw = dict(steps=steps, ckpt_every=ckpt_every, seq_len=seq_len,
              batch=batch, clock=clock)
    ref_dir = os.path.join(root, "reference")
    ref = run_phase("reference", cfg, ref_dir, train_mesh,
                    capture_at=None if same_mesh else die_at, **kw)
    shutil.rmtree(ref_dir)  # the next phases need the disk
    run_dir = os.path.join(root, "run")
    died = run_phase("die", cfg, run_dir, train_mesh, die_at=die_at, **kw)
    resumed = run_phase("resume", cfg, run_dir, resume_mesh,
                        capture_at=None if same_mesh else die_at, **kw)
    return ref, died, resumed


def mismatched_leaves(a, b) -> List[str]:
    """Names of the leaves whose bytes differ (or that only one side has)."""
    fa = {jax.tree_util.keystr(k): v
          for k, v in jax.tree_util.tree_leaves_with_path(a)}
    fb = {jax.tree_util.keystr(k): v
          for k, v in jax.tree_util.tree_leaves_with_path(b)}
    bad = sorted(set(fa) ^ set(fb))
    for k in sorted(set(fa) & set(fb)):
        x, y = np.asarray(fa[k]), np.asarray(fb[k])
        if x.dtype != y.dtype or x.shape != y.shape or not np.array_equal(
                x.reshape(-1).view(np.uint8), y.reshape(-1).view(np.uint8)):
            bad.append(k)
    return bad


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32)), initial=0))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def check(ref: Phase, died: Phase, resumed: Phase, *, steps: int,
          die_at: int, same_mesh: bool) -> List[str]:
    """Every failed check, as a message (empty when the run is right)."""
    fail: List[str] = []
    last = steps - 1
    if ref.steps_on_disk != [last]:
        fail.append(f"reference: checkpoints {ref.steps_on_disk}, "
                    f"expected [{last}]")
    if died.steps_on_disk != [die_at]:
        fail.append(f"die: checkpoints {died.steps_on_disk}, "
                    f"expected [{die_at}]")
    if resumed.start_step != die_at:
        fail.append(f"resume: start_step {resumed.start_step}, "
                    f"expected {die_at}")
    if resumed.steps_on_disk != [last]:
        fail.append(f"resume: checkpoints {resumed.steps_on_disk}, "
                    f"expected [{last}]")
    if died.losses != ref.losses[:die_at + 1]:
        fail.append(f"die: losses {died.losses} differ from the "
                    f"reference's {ref.losses[:die_at + 1]}")
    want = ref.losses[die_at + 1:]
    got = resumed.losses
    if len(got) != len(want):
        return fail + [f"resume: {len(got)} losses, expected {len(want)}"]
    diff = max((abs(g - w) for g, w in zip(got, want)), default=0.0)
    if same_mesh:
        if got != want:
            fail.append(f"resume: losses {got} differ from the reference's "
                        f"{want} (max difference {diff!r}); the same "
                        f"program on the same device must give the same "
                        f"bits")
        bad = mismatched_leaves(resumed.state, ref.state)
        if bad:
            fail.append(f"resume: final state differs from the "
                        f"reference's in {len(bad)} leaves: {bad[:5]}")
    else:
        bad = mismatched_leaves(resumed.captured, ref.captured)
        if bad:
            fail.append(f"resume: restored state differs from the "
                        f"reference's at step {die_at} in {len(bad)} "
                        f"leaves: {bad[:5]}")
        if any(abs(g - w) > CROSS_MESH_LOSS_RTOL * abs(w)
               for g, w in zip(got, want)):
            fail.append(f"resume: losses {got} vs the reference's {want} "
                        f"(max difference {diff!r}) exceed rtol "
                        f"{CROSS_MESH_LOSS_RTOL}")
    return fail


# --------------------------------------------------------------------------
# Readings (printed by main; from the host clock around steps that end in a
# loss read, and from repro.core.trace spans and counters)
# --------------------------------------------------------------------------

def _spans(phase: Phase, name: str) -> List[Dict[str, Any]]:
    return [e for e in phase.events
            if e.get("ph") == "X" and e.get("cat") == "ckpt"
            and e.get("name") == name]


def readings(phase: Phase, die_at: int) -> Dict[str, Any]:
    steps = sorted(k for k in phase.marks if k != "start")
    out: Dict[str, Any] = {"compile_s": phase.compile_s}
    if steps and "start" in phase.marks:
        out["first_step_s"] = phase.marks[steps[0]] - phase.marks["start"]
    gaps = [phase.marks[b] - phase.marks[a] for a, b in zip(steps, steps[1:])]
    if gaps:
        out["median_step_s"] = statistics.median(gaps)
    stall = [e for e in _spans(phase, "save_stall")
             if (e.get("args") or {}).get("step") == die_at]
    if stall:
        out[f"save_stall_s@{die_at}"] = stall[0]["dur"] / 1e6
    plan = [e for e in _spans(phase, "plan")
            if (e.get("args") or {}).get("step") == die_at]
    if plan:
        t0 = plan[0]["ts"]
        done = [e["ts"] + e["dur"] for e in _spans(phase, "retention")
                if e["ts"] >= t0]
        if done:
            out[f"background_save_s@{die_at}"] = (min(done) - t0) / 1e6
    out["GB_written"] = (phase.counters.get("io.pwrite.bytes", 0)
                         + phase.counters.get("io.pwritev.bytes", 0)) / 1e9
    rest = _spans(phase, "restore_or_init")
    if rest:
        out["restore_or_init_s"] = rest[0]["dur"] / 1e6
    return out


def _say(label: str, msg: str) -> None:
    print(f"[{label}] {msg}", flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="train on a (2, 2) mesh, resume on a (4, 1) mesh")
    args = ap.parse_args(argv)

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: {need} chips needed, {len(devices)} found",
              file=sys.stderr)
        return 2

    clock = CompileClock().install()
    label = f"{dev.platform} {dev.device_kind} x{need}"
    batch = BATCH_FOUR_CHIPS if args.four_chips else BATCH_ONE_CHIP
    cfg = qwen3_config(LAYERS)
    full = get_config("qwen3-1.7b")
    _say(label, f"qwen3-1.7b widths: d_model {cfg.d_model}, {cfg.n_heads} "
                f"heads x {cfg.head_dim_}, {cfg.n_kv_heads} kv heads, d_ff "
                f"{cfg.d_ff}, qk_norm {cfg.qk_norm}, vocab {cfg.vocab} "
                f"(tied {cfg.tie_embeddings})")
    _say(label, f"layers {cfg.n_layers} (cut from {full.n_layers}: depth "
                f"only), seq_len {SEQ_LEN}, global_batch {batch}")
    state_bytes = sum(a.size * a.dtype.itemsize for a in
                      jax.tree_util.tree_leaves(abstract_state(cfg)))
    _say(label, f"train state (params + AdamW mu, nu; f32): "
                f"{state_bytes} bytes")
    _say(label, f"compile cache: {cache_dir}")

    if args.four_chips:
        train_mesh, resume_mesh = make_host_mesh(2, 2), make_host_mesh(4, 1)
    else:
        train_mesh = resume_mesh = make_host_mesh(1, 1)
    _say(label, f"train mesh {dict(train_mesh.shape)}, resume mesh "
                f"{dict(resume_mesh.shape)}")
    root = tempfile.mkdtemp(prefix="scda-chip-smoke-")
    try:
        _say(label, f"checkpoints under {root}, "
                    f"{shutil.disk_usage(root).free} bytes free")
        phases = run_die_resume(cfg, root, train_mesh, resume_mesh,
                                seq_len=SEQ_LEN, batch=batch, clock=clock)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    for p in phases:
        _say(label, f"{p.name}: start_step {p.start_step}, losses "
                    f"{p.losses}, checkpoints {p.steps_on_disk}")
        for k, v in readings(p, DIE_AT).items():
            _say(label, f"{p.name}: {k} {v!r}")
    ref, _, resumed = phases
    per_dev = resumed.device_bytes
    for d in resume_mesh.devices.flat:
        _say(label, f"resume: device {d.id} holds {per_dev.get(d.id, 0)} "
                    f"state bytes after the restore, bytes_in_use "
                    f"{resumed.bytes_in_use.get(d.id)}")
    fail = check(*phases, steps=STEPS, die_at=DIE_AT,
                 same_mesh=resume_mesh is train_mesh)
    if resume_mesh is not train_mesh:
        _say(label, f"resume: final state max |diff| vs reference "
                    f"{max_abs_diff(resumed.state, ref.state)!r}")
        total = sum(per_dev.values())
        n = resume_mesh.devices.size
        if len(per_dev) != n or max(per_dev.values()) >= total:
            fail.append(f"resume: state bytes per device {per_dev} are not "
                        f"spread over {n} devices")
    if fail:
        for f in fail:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Checkpoint lifecycle management for long-running training jobs.

Fault-tolerance properties (the paper's motivating use case, §A.6: "file
errors should never crash the simulation"):

  * **Async**: the only synchronous work is the device→host snapshot;
    serialization + disk I/O run on a background thread (straggler-safe —
    checkpoint I/O never sits on the training critical path).
  * **Atomic**: writes go to ``<name>.tmp`` and are fsync'd before an
    atomic rename; a crash mid-write never leaves a visible partial
    checkpoint, and ``latest_step`` only ever sees complete files.
  * **Non-fatal**: any ScdaError during a save is recorded and surfaced on
    the *next* call (or ``wait()``), never raised into the training loop
    mid-step unless the caller asks.
  * **Elastic**: ``restore_latest(like=...)`` restores under any mesh; the
    file does not know or care how many hosts wrote it.
  * **Retention**: keep the newest ``keep`` checkpoints (always ≥ 1), so a
    corrupted latest file can fall back to an older one.
  * **Incremental**: with ``delta=True`` (or ``REPRO_SCDA_DELTA=1``) a
    save stores only the leaf chunks whose content changed since the
    newest committed checkpoint; unchanged chunks become by-hash
    references into earlier archives.  Retention is chain-aware — every
    base a retained delta still references (transitively) is protected,
    so dropping old steps never strands a chain.
  * **Journaled**: :meth:`CheckpointManager.journal` streams training
    telemetry (loss/lr/eval scalars) into the newest committed checkpoint
    file via mode-'a' appends; buffered records are flushed right after
    every commit (flush-on-commit ordering), so the archive that holds
    the state also holds the metrics that led to it.
"""
from __future__ import annotations

import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.checkpoint import delta as _delta
from repro.checkpoint import pytree_io
from repro.checkpoint import redundancy as _red
from repro.checkpoint import sharding as _sharding
from repro.checkpoint import manifest as _mf
from repro.checkpoint.snapshot import snapshot_to_host
from repro.core import ScdaError
from repro.core import trace as _trace
from repro.core.comm import Communicator, SerialComm
from repro.core.errors import ScdaErrorCode
from repro.core.index import SIDECAR_SUFFIX, ScdaIndex
from repro.core.io_backend import replace_durable

_CKPT_RE = re.compile(r"^step_(\d{10})\.scda$")

#: Advisory writer lock: O_EXCL-created in the checkpoint directory so
#: two managers on one directory refuse instead of interleaving commits.
LOCK_NAME = ".scda-lock"

#: A foreign-host lock older than this is presumed dead (we cannot
#: signal-probe across hosts); same-host locks are probed by pid.
LOCK_TTL_SECONDS = 3600.0


def _ckpt_name(step: int) -> str:
    return f"step_{step:010d}.scda"


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3,
                 compressed: bool = False,
                 comm: Optional[Communicator] = None,
                 chunk_bytes: int = pytree_io.DEFAULT_CHUNK_BYTES,
                 index_sidecar: bool = True,
                 delta: Optional[bool] = None,
                 delta_chain: Optional[int] = None,
                 shards: Optional[int] = None,
                 parity: Optional[int] = None) -> None:
        self.directory = directory
        self.keep = max(1, keep)
        self.compressed = compressed
        self.comm = comm or SerialComm()
        self.chunk_bytes = chunk_bytes
        self.index_sidecar = index_sidecar
        # Multi-file sharded saves: N independent archives + a manifest
        # file per checkpoint (None defers to REPRO_SCDA_SHARDS; 0 =
        # classic single-file saves).  See repro.checkpoint.sharding.
        self.shards = (_sharding.shards_default()
                       if shards is None else max(0, int(shards)))
        # Erasure coding: m parity shards per set (None defers to
        # REPRO_SCDA_PARITY).  Parity without sharding has nothing to
        # code over, so it collapses to 0 for flat saves.
        self.parity = (_red.parity_default()
                       if parity is None else max(0, int(parity)))
        if not self.shards:
            self.parity = 0
        _red.check_geometry(self.shards, self.parity)
        # Incremental (delta) saves: None defers to REPRO_SCDA_DELTA; the
        # chain depth cap (REPRO_SCDA_DELTA_CHAIN) forces a periodic full
        # save so restore fan-in stays bounded and retention can
        # eventually drop old bases.
        self.delta = (_delta.delta_enabled_default()
                      if delta is None else bool(delta))
        self.delta_chain = (_delta.chain_limit()
                            if delta_chain is None else max(1, delta_chain))
        self._last_doc: Optional[Tuple[Dict[str, Any], str]] = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._journal = None  # lazy ScdaJournal (see journal())
        self._crash_before_commit = False  # test hook: simulated node death
        self._lock_path = os.path.join(directory, LOCK_NAME)
        self._lock_owned = False
        if self.comm.rank == 0:
            os.makedirs(directory, exist_ok=True)
            self._acquire_lock()
        self.comm.barrier()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def close(self) -> None:
        """Join any in-flight save and release the writer lock."""
        try:
            self.wait()
        finally:
            if self._lock_owned and self.comm.rank == 0:
                try:
                    os.remove(self._lock_path)
                except OSError:
                    pass
                self._lock_owned = False

    # -- advisory writer lock ------------------------------------------------
    def _acquire_lock(self) -> None:
        """O_EXCL lockfile (pid/host/timestamp) in the checkpoint dir.

        A live holder refuses loudly; a stale holder (dead pid on this
        host, or a foreign-host lock past LOCK_TTL_SECONDS) is taken
        over with a loud warning.  A lock held by THIS process is
        silently shared — managers and tooling routinely reopen the
        same directory in-process, and the advisory target is two
        *jobs*, not two objects.
        """
        import json
        import socket
        import time
        me = {"pid": os.getpid(), "host": socket.gethostname(),
              "time": time.time()}
        for _ in range(16):  # bounded takeover races
            try:
                fd = os.open(self._lock_path,
                             os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
            except FileExistsError:
                pass
            else:
                with os.fdopen(fd, "w") as f:
                    f.write(json.dumps(me))
                self._lock_owned = True
                return
            try:
                with open(self._lock_path, "r") as f:
                    cur = json.loads(f.read() or "{}")
            except (OSError, ValueError):
                cur = {}
            if not isinstance(cur, dict):
                cur = {}
            if cur.get("host") == me["host"] \
                    and cur.get("pid") == me["pid"]:
                return  # same process — shared advisory lock
            stale = False
            if not cur:
                stale = True  # unreadable/empty lock: crashed mid-write
            elif cur.get("host") == me["host"] \
                    and isinstance(cur.get("pid"), int):
                try:
                    os.kill(cur["pid"], 0)
                except OSError:
                    stale = True  # holder process is gone
            else:
                try:
                    age = time.time() - float(cur.get("time", 0))
                except (TypeError, ValueError):
                    age = LOCK_TTL_SECONDS + 1
                stale = age > LOCK_TTL_SECONDS
            if not stale:
                raise ScdaError(
                    ScdaErrorCode.FS_OPEN,
                    f"checkpoint directory {self.directory!r} is locked "
                    f"by pid {cur.get('pid')} on {cur.get('host')!r} "
                    f"(since {cur.get('time')}); remove "
                    f"{self._lock_path!r} if that writer is gone")
            _trace.warn(
                f"repro: TAKING OVER stale checkpoint lock "
                f"{self._lock_path!r} (holder pid {cur.get('pid')} on "
                f"{cur.get('host')!r} presumed dead)",
                key=("lock-takeover", self._lock_path))
            try:
                os.remove(self._lock_path)
            except OSError:
                pass  # lost a takeover race; retry the O_EXCL create
        raise ScdaError(
            ScdaErrorCode.FS_OPEN,
            f"could not acquire checkpoint lock {self._lock_path!r}")

    # -- inventory -----------------------------------------------------------
    def all_steps(self) -> List[int]:
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        steps = [int(m.group(1)) for n in names
                 if (m := _CKPT_RE.match(n))]
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def path_for(self, step: int) -> str:
        return os.path.join(self.directory, _ckpt_name(step))

    # -- journaling ----------------------------------------------------------
    def journal(self):
        """The run's telemetry journal (:class:`repro.journal.ScdaJournal`).

        ``journal().log(step, scalars)`` buffers records; they are
        appended to the newest *committed* checkpoint file — immediately
        when the auto-flush threshold trips, and in any case right after
        every commit, re-targeted at the fresh file (flush-on-commit:
        telemetry logged before ``save(step)`` is on disk inside
        ``step``'s archive once that save commits).  Before the first
        commit records simply buffer.  Rank 0 only, like the sidecars —
        metrics are replicated, the file needs them once, so every other
        rank gets an inert journal (log is a no-op there) and replicated
        training code may log unconditionally.  Note retention applies:
        journal history lives in the retained checkpoint files.
        """
        if self._journal is None:
            from repro.journal import ScdaJournal
            latest = self.latest_step()
            self._journal = ScdaJournal(
                self.path_for(latest) if latest is not None else None,
                enabled=self.comm.rank == 0)
        return self._journal

    # -- saving ----------------------------------------------------------------
    def save(self, step: int, tree, *, blocking: bool = False,
             aux_extra: Optional[Dict[str, Any]] = None,
             delta: Optional[bool] = None) -> None:
        """Snapshot now; serialize + write in the background.

        ``delta=True`` saves incrementally against the newest committed
        checkpoint: unchanged chunks become by-hash references, save cost
        is proportional to the changed bytes (``None`` defers to the
        manager's / ``REPRO_SCDA_DELTA``'s default).  Falls back to a
        full save when no usable base exists or the chain depth cap is
        reached.

        Raises any error from the *previous* async save (so failures are
        observed, but off the hot path).
        """
        # The stall the training loop pays for this save: the wait for
        # the previous one plus the device→host snapshot.
        with _trace.span("save_stall", "ckpt", step=step):
            with _trace.span("save_wait", "ckpt", step=step):
                self.wait()  # one in-flight save at a time; surfaces errors
            with _trace.span("snapshot", "ckpt", step=step) as sp:
                host_tree = snapshot_to_host(tree)
                sp.add(bytes=sum(getattr(x, "nbytes", 0) for x in
                                 jax.tree_util.tree_leaves(host_tree)))
        use_delta = self.delta if delta is None else bool(delta)

        def _write() -> None:
            try:
                self._write_and_commit(step, host_tree, aux_extra,
                                       use_delta)
            except BaseException as e:  # noqa: BLE001 - stored, not raised
                self._error = e

        if blocking:
            _write()
            self._raise_pending()
        else:
            self._thread = threading.Thread(target=_write, daemon=True,
                                            name=f"ckpt-save-{step}")
            self._thread.start()

    def _delta_base(self, step: int) \
            -> Optional[Tuple[Dict[str, Any], str]]:
        """The ``(manifest_doc, file_name)`` the next delta should
        reference, or ``None`` to force a full save.

        ``None`` when: no prior checkpoint exists, the newest one carries
        no chunk digests (pre-delta archive), re-saving ``step`` would
        make the archive reference itself, or the chain depth cap is
        reached (periodic full save keeps restore fan-in bounded and
        lets retention eventually drop old bases).
        """
        target = _ckpt_name(step)
        cand: Optional[Tuple[Dict[str, Any], str]] = None
        if self._last_doc is not None and self._last_doc[1] != target:
            cand = self._last_doc
        else:
            for s in reversed(self.all_steps()):
                name = _ckpt_name(s)
                if name == target:
                    continue  # never self-reference on a same-step re-save
                try:
                    doc = pytree_io.read_manifest(self.path_for(s))
                    if doc.get("format") == _mf.SHARDED_FORMAT:
                        # A sharded base needs its per-shard docs (the
                        # actual digest tables) — content-id-verified,
                        # so a tampered set falls back to a full save.
                        doc = _sharding.load_set(self.path_for(s))
                except (ScdaError, OSError, ValueError):
                    continue  # unreadable base: fall further back
                cand = (doc, name)
                break
        if cand is None or not _sharding.base_usable_any(cand[0]):
            return None
        if _sharding.chain_depth(cand[0]) + 1 > self.delta_chain:
            return None
        return cand

    def _write_and_commit(self, step: int, host_tree,
                          aux_extra: Optional[Dict[str, Any]],
                          use_delta: bool = False) -> None:
        final = self.path_for(step)
        tmp = final + ".tmp"
        with _trace.span("plan", "ckpt", step=step, delta=use_delta,
                         shards=self.shards, parity=self.parity):
            base = self._delta_base(step) if use_delta else None
        try:
            if self.shards:
                # Sharded save: every file (shards + manifest) is written
                # as <name>.tmp while the manifest records final names;
                # commit_sharded renames shards first, manifest last —
                # the manifest rename is the commit point.
                doc = _sharding.save_sharded(
                    final, host_tree, shards=self.shards, comm=self.comm,
                    step=step, compressed=self.compressed,
                    chunk_bytes=self.chunk_bytes, aux_extra=aux_extra,
                    record_hashes=use_delta or self.delta,
                    delta_base=base, parity=self.parity,
                    tmp_suffix=".tmp")
            else:
                doc = pytree_io.save(tmp, host_tree, comm=self.comm,
                                     step=step,
                                     compressed=self.compressed,
                                     chunk_bytes=self.chunk_bytes,
                                     aux_extra=aux_extra,
                                     record_hashes=use_delta or self.delta,
                                     delta_base=base, shards=0)
        except BaseException:
            # A failed save must not leave its half-written tmp around
            # until the next retention sweep: remove it now (best-effort
            # — the atomic-rename invariant already keeps it invisible)
            # and surface the original error unchanged.
            if self.comm.rank == 0:
                stale = (_sharding.set_paths(final, self.shards, ".tmp",
                                             parity=self.parity)
                         if self.shards else [tmp])
                for p in stale:
                    try:
                        os.remove(p)
                    except OSError:
                        pass
            raise
        if self._crash_before_commit:
            raise RuntimeError("injected crash before commit")
        self.comm.barrier()
        if self.comm.rank == 0:
            with _trace.span("commit", "ckpt", path=final, step=step):
                if self.shards:
                    _sharding.commit_sharded(final, doc, ".tmp")
                    committed = [os.path.join(self.directory, s["file"])
                                 for s in doc["shards"]]
                    committed += [os.path.join(self.directory, p["file"])
                                  for p in (doc.get("parity") or {})
                                  .get("files", [])]
                    committed.append(final)
                else:
                    # Atomic commit: rename + parent-dir fsync.  Without
                    # the directory fsync a power cut can roll the rename
                    # back and lose the commit entirely.
                    replace_durable(tmp, final)
                    committed = [final]
                if self.index_sidecar:
                    # The .scdax sidecars make restore_leaf / lazy
                    # restores seek without a scan.  Best-effort: the
                    # checkpoint is already committed, and readers fall
                    # back to a fresh header scan when a sidecar is
                    # missing or stale.
                    ScdaIndex.write_sidecars(committed)
            c = _trace.collector()
            if c is not None:
                # Metrics sink: counter deltas since the last commit ride
                # into the checkpoint's own journal, so the archive that
                # holds the state also records the I/O it cost.
                rec = c.commit_record()
                if rec:
                    self.journal().log(step, {"trace": rec})
            if self._journal is not None:
                # Flush-on-commit: buffered telemetry follows the newest
                # checkpoint into its file (and refreshes the sidecar it
                # just grew past, atomically).  Best-effort like the
                # sidecar — a failed flush keeps the records buffered
                # for the next commit, never un-commits the checkpoint.
                self._journal.retarget(final)
                try:
                    self._journal.flush()
                except (ScdaError, OSError):
                    pass
            with _trace.span("retention", "ckpt", keep=self.keep):
                self._apply_retention()
        # Cache the exact doc a re-read of the fresh archive would parse —
        # the next delta save references it without touching the disk.
        self._last_doc = (doc, _ckpt_name(step))
        self.comm.barrier()

    def _shard_files(self, name: str) -> List[str]:
        """Shard + parity file names of checkpoint ``name`` (empty for
        flat archives or anything unreadable).  Parity rides along so
        retention treats the whole erasure-coded set as one atomic
        unit — a dropped checkpoint takes its parity with it, a kept
        one keeps its parity restorable."""
        try:
            doc = pytree_io.read_manifest(
                os.path.join(self.directory, name))
        except (ScdaError, OSError, ValueError):
            return []
        if doc.get("format") != _mf.SHARDED_FORMAT:
            return []
        return [s.get("file") for s in doc.get("shards", [])
                if s.get("file")] \
            + [p.get("file")
               for p in (doc.get("parity") or {}).get("files", [])
               if p.get("file")]

    def _referenced_files(self, kept_steps: List[int]) -> set:
        """Transitive closure of delta-base files the kept checkpoints
        still reference — retention must not delete them, or every
        surviving delta becomes unrestorable.  Sharded manifests are
        traversed through their shard archives (whose docs hold the
        actual base references); the bases a sharded delta records are
        shard *files*, so protection lands on those names and the
        retention sweep keeps their whole set."""
        protected: set = set()
        queue = [_ckpt_name(s) for s in kept_steps]
        seen = set(queue)
        while queue:
            name = queue.pop()
            try:
                doc = pytree_io.read_manifest(
                    os.path.join(self.directory, name))
            except (ScdaError, OSError, ValueError):
                continue  # unreadable: nothing to protect through it
            if doc.get("format") == _mf.SHARDED_FORMAT:
                for s in doc.get("shards", []):
                    f = s.get("file")
                    if f and f not in seen:
                        seen.add(f)
                        queue.append(f)  # traverse, don't protect
                continue
            for b in (doc.get("delta") or {}).get("bases", []):
                f = b.get("file")
                if f and f not in seen:
                    seen.add(f)
                    protected.add(f)
                    queue.append(f)
        return protected

    def _apply_retention(self) -> None:
        steps = self.all_steps()
        protected = self._referenced_files(steps[-self.keep:])
        for s in steps[:-self.keep]:
            files = [_ckpt_name(s)] + self._shard_files(_ckpt_name(s))
            if any(f in protected for f in files):
                continue  # an alive delta chain still needs this base
            for f in files:
                p = os.path.join(self.directory, f)
                for path in (p, p + SIDECAR_SUFFIX):
                    try:
                        os.remove(path)
                    except OSError:
                        pass  # retention is best-effort
        # sweep stale tmp files from crashed attempts, orphaned sidecars,
        # and shard files whose manifest is gone (a crashed sharded
        # commit renames shards before the manifest)
        keep_names = set(protected)
        for s in self.all_steps():
            n = _ckpt_name(s)
            keep_names.add(n)
            keep_names.update(self._shard_files(n))
        for n in os.listdir(self.directory):
            stale = (n.endswith(".scda.tmp") or n.endswith(".scdax.tmp")
                     or (n.endswith(".scda" + SIDECAR_SUFFIX)
                         and n[:-len(SIDECAR_SUFFIX)] not in keep_names)
                     or (_sharding.is_shard_name(n) is not None
                         and n not in keep_names)
                     or (_red.is_parity_name(n) is not None
                         and n not in keep_names))
            if stale:
                try:
                    os.remove(os.path.join(self.directory, n))
                except OSError:
                    pass

    def wait(self) -> None:
        """Join any in-flight save and surface its error, if any."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._raise_pending()

    def _raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # -- restoring ---------------------------------------------------------------
    def restore(self, step: int, like=None) -> Tuple[Any, Optional[int]]:
        return pytree_io.restore(self.path_for(step), like, comm=self.comm)

    def restore_leaf(self, step: int, name: str, like=None):
        """Lazily load one tensor of checkpoint ``step`` (index seek)."""
        return pytree_io.restore_leaf(self.path_for(step), name, like,
                                      comm=self.comm)

    def restore_latest(self, like=None) -> Tuple[Any, Optional[int]]:
        """Restore the newest complete checkpoint; fall back on corruption.

        Node-failure recovery: a half-written or corrupted newest file
        (e.g. the job died during a commit on another file system) must not
        brick the restart — older retained checkpoints are tried in order.
        """
        steps = self.all_steps()
        last_err: Optional[BaseException] = None
        for step in reversed(steps):
            try:
                return self.restore(step, like)
            except ScdaError as e:
                last_err = e
                continue
        if last_err is not None:
            raise last_err
        return None, None

    def restore_or_init(self, init_fn, like=None):
        """The standard restart entry point: resume if possible, else init.

        Returns ``(tree, step)`` where step is -1 for a fresh start.
        """
        with _trace.span("restore_or_init", "ckpt"):
            steps = self.all_steps()
            if steps:
                tree, step = self.restore_latest(like)
                if tree is not None:
                    return tree, (step if step is not None else steps[-1])
            return init_fn(), -1

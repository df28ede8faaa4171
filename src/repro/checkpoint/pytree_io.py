"""Sharded-pytree checkpointing on scda — the framework's core feature.

``save`` writes one scda file whose bytes depend only on the *logical*
train state (leaf values in canonical row-major order), never on the mesh,
process count, or sharding — the paper's serial-equivalence, delivered for
JAX pytrees.  ``restore`` rebuilds the state under *any* target sharding /
mesh ("the file can be read on any number of processes that agree on any
partition"), which is what makes restarts elastic.

Both hot paths are overlapped pipelines (:mod:`repro.core.pipeline`).
Restore: the scheduler walks the :class:`ScdaIndex` once, sorts every
wanted leaf's runs by file offset, prefetches the next
``REPRO_SCDA_PREFETCH`` bytes of extents on a background executor, and
inflates compressed chunks on the codec thread pool while the next leaf's
preads are in flight.  Save: the scheduler plans every leaf's extents
from the manifest, snapshots device arrays one leaf ahead, deflates
chunks on the same pool, and drains coalesced ``pwritev`` fragments
through a background queue bounded to ``REPRO_SCDA_WRITE_PIPELINE``
in-flight bytes.  Results are byte-identical to the serial walks;
``REPRO_SCDA_PREFETCH=0`` / ``REPRO_SCDA_WRITE_PIPELINE=0`` (or the
``prefetch_bytes`` / ``write_window`` arguments) disable each engine and
take the exact legacy serial order — the oracles the pipelines are
tested against.

File layout:
    F  header (vendor "repro scda-jax 0.1")
    I  "scda-ckpt status"    — human-readable step number
    B  "scda-ckpt manifest"  — JSON: leaf names/shapes/dtypes/layout + aux
    per array leaf, in manifest order:
        raw:        A("leaf NNNNNN", N = nbytes, E = 1)
        compressed: §3.4 convention (A of U-entries + V of deflate chunks),
                    fixed chunking recorded in the manifest
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.checkpoint import layout, manifest as mf
from repro.checkpoint.snapshot import HostShards, leaf_to_host
from repro.core import ScdaError, ScdaErrorCode, partition
from repro.core import trace as _trace
from repro.core.comm import Communicator, SerialComm
from repro.core.index import ScdaIndex
from repro.core.io_backend import (JOIN_SMALL, prefetch_window,
                                   write_pipeline_window)
from repro.core.pipeline import ReadItem, run_pipeline
from repro.core.reader import ScdaReader, fopen_read
from repro.core.writer import fopen_write

DEFAULT_CHUNK_BYTES = 1 << 20  # 1 MiB deflate chunks for encoded leaves


def _effective_prefetch(prefetch_bytes: Optional[int]) -> int:
    """Resolve the prefetch window: explicit argument wins, else the
    ``REPRO_SCDA_PREFETCH`` environment knob (0 = serial restore)."""
    if prefetch_bytes is None:
        return prefetch_window()
    return max(0, int(prefetch_bytes))


def _effective_write_window(write_window: Optional[int]) -> int:
    """Resolve the save-pipeline window: explicit argument wins, else the
    ``REPRO_SCDA_WRITE_PIPELINE`` environment knob (0 = serial save)."""
    if write_window is None:
        return write_pipeline_window()
    return max(0, int(write_window))


#: ``REPRO_SCDA_VERIFY_RESTORE=1``: CRC-check every restored archive
#: against its checksummed sidecar (as if ``restore(..., verify=True)``).
VERIFY_RESTORE_ENV = "REPRO_SCDA_VERIFY_RESTORE"


def _effective_verify(verify: Optional[bool]) -> bool:
    """Resolve verify-on-restore: explicit argument wins, else the
    ``REPRO_SCDA_VERIFY_RESTORE`` environment knob."""
    if verify is not None:
        return bool(verify)
    return os.environ.get(VERIFY_RESTORE_ENV, "0") not in ("", "0")


def _verify_archive(path: str) -> None:
    """Verify every section payload of ``path`` against its checksummed
    ``.scdax`` sidecar — the ``restore(..., verify=True)`` pass.

    Requires a fresh, fully checksummed sidecar (``scdatool index
    --checksums``); a missing/stale one raises ARG_SEQUENCE rather than
    silently skipping, and a CRC mismatch raises CORRUPT_CHECKSUM with
    the failing section's exact payload byte offset
    (``ScdaError.offset``).  Runs on its own reader so the caller's
    cursor and adopted index are untouched.
    """
    try:
        idx = ScdaIndex.load_sidecar(path)
    except (ScdaError, OSError) as e:
        raise ScdaError(
            ScdaErrorCode.ARG_SEQUENCE,
            f"{path}: restore(verify=True) needs a fresh checksummed "
            f"sidecar — run scdatool index --checksums ({e})") from e
    with _trace.span("verify", "ckpt", path=path):
        with fopen_read(None, path) as vr:
            idx.check_checksums(vr)


# --------------------------------------------------------------------------
# Tree flattening with stable, human-readable names
# --------------------------------------------------------------------------

def _key_name(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    if isinstance(k, jax.tree_util.FlattenedIndexKey):
        return str(k.key)
    return str(k)


def leaf_name(path) -> str:
    return "/".join(_key_name(k) for k in path) or "."


def flatten_named(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    named = [(leaf_name(p), v) for p, v in flat]
    names = [n for n, _ in named]
    if len(set(names)) != len(names):
        raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                        "pytree leaf names are not unique")
    return named, treedef


def _is_array(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray, HostShards)) \
        and np.ndim(x) is not None


# --------------------------------------------------------------------------
# Saving
# --------------------------------------------------------------------------

def _byte_view(host: np.ndarray) -> memoryview:
    """A zero-copy byte view of a contiguous array (bf16/f8-safe — the
    ml_dtypes scalar types have no buffer protocol, uint8 views do)."""
    if host.nbytes == 0:
        return memoryview(b"")
    return memoryview(np.ascontiguousarray(host).reshape(-1).view(np.uint8))


def _short_runs(arr: HostShards) -> bool:
    """Some shard of ``arr`` splits into many runs of at most
    :data:`JOIN_SMALL` bytes (a column-sharded leaf's rows)."""
    for index, host in arr.shards:
        run = layout.run_bytes(arr.shape, index, arr.dtype.itemsize)
        if 0 < run <= JOIN_SMALL and host.nbytes > run:
            return True
    return False


def _owned_windows(arr, nbytes: int) -> List[Tuple[int, memoryview]]:
    """This process's deduplicated (byte_offset, buffer) windows of ``arr``.

    A jax.Array is snapshotted first (:mod:`repro.checkpoint.snapshot`):
    each addressable shard with replica_id == 0 is owned here, and across
    all processes those tile the canonical stream exactly once.  A
    :class:`HostShards` gives one window per contiguous run of each shard
    buffer.  numpy arrays are treated as fully owned (callers pass them
    on rank 0 or rely on identical replicated writes, which are
    byte-identical anyway).

    A 2-D-sharded tensor's shards interleave in the canonical stream;
    ``ScdaWriter.write_array_windows`` sorts the windows and coalesces runs
    that are contiguous *across shards* into single vectored writes.
    """
    if isinstance(arr, jax.Array):
        arr = leaf_to_host(arr)
    if isinstance(arr, HostShards) and arr.complete and _short_runs(arr):
        # The backend copies runs this short into joined buffers anyway
        # (io_backend.JOIN_SMALL): one gather costs no more copying, and
        # spares a window per run.
        arr = np.asarray(arr)
    windows: List[Tuple[int, memoryview]] = []
    if isinstance(arr, HostShards):
        itemsize = arr.dtype.itemsize
        for index, host in arr.shards:
            buf = _byte_view(host)
            for goff, loff, length in layout.shard_runs(arr.shape, index,
                                                        itemsize):
                windows.append((goff, buf[loff:loff + length]))
    else:
        host = np.asarray(arr)
        if host.nbytes:
            windows.append((0, _byte_view(host)))
    return windows


def save(path: str, tree, *, comm: Optional[Communicator] = None,
         step: Optional[int] = None, compressed: bool = False,
         chunk_bytes: int = DEFAULT_CHUNK_BYTES,
         aux_extra: Optional[Dict[str, Any]] = None,
         write_window: Optional[int] = None,
         record_hashes: bool = False,
         delta_base: Optional[Tuple[Dict[str, Any], str]] = None,
         shards: Optional[int] = None,
         parity: Optional[int] = None,
         trace: Optional[Any] = None) \
        -> Dict[str, Any]:
    """Write ``tree`` to ``path`` as a serial-equivalent scda checkpoint.

    Leaf sections go through the overlapped save engine
    (:func:`repro.core.pipeline.run_write_pipeline`): device→host
    snapshots run one leaf ahead, compressed chunks deflate on the codec
    pool, and finished fragments drain through a background ``pwritev``
    queue bounded to ``write_window`` in-flight bytes (default
    ``REPRO_SCDA_WRITE_PIPELINE``, 32 MiB).  ``write_window=0`` saves
    serially, in exactly the pre-pipeline write order — the byte oracle
    the pipeline is fuzzed against.  Either way the file bytes depend
    only on the logical tree: serial equivalence is preserved by
    construction, since both paths plan sections with the same writer
    primitives (:mod:`repro.checkpoint.planner`).

    ``record_hashes`` adds per-chunk content digests (CRC32 + a 128-bit
    SHA-256 prefix)
    to the manifest so the archive can serve as a delta base.
    ``delta_base`` — a ``(base_manifest_doc, base_file_name)`` pair —
    switches to an incremental save: chunks whose digests match the base
    are stored as by-hash references and only changed chunks are
    written (:mod:`repro.checkpoint.delta`).  Both are single-rank.

    Returns the manifest document (what :func:`read_manifest` of the
    fresh file would return).

    ``shards`` splits the save into that many independent scda archives
    plus a manifest file at ``path`` (see
    :mod:`repro.checkpoint.sharding`); ``None`` defers to the
    ``REPRO_SCDA_SHARDS`` knob, 0 writes the classic single file.  A
    sharded save returns the sharded manifest document instead.

    ``parity`` adds that many erasure-code shards to a sharded save
    (``None`` defers to ``REPRO_SCDA_PARITY``; ignored for flat saves —
    there is no shard set to code over).  See
    :mod:`repro.checkpoint.redundancy`.

    ``trace`` activates telemetry for this one save: a
    :class:`repro.core.trace.TraceCollector` (events/metrics accumulate
    there) or a path string (a Chrome ``trace_event`` JSON is exported
    on completion).  ``None`` leaves the process-wide
    ``REPRO_SCDA_TRACE`` behavior in charge.  Purely observational —
    traced saves are byte-identical to untraced ones.
    """
    if trace is not None:
        with _trace.scoped(trace):
            return save(path, tree, comm=comm, step=step,
                        compressed=compressed, chunk_bytes=chunk_bytes,
                        aux_extra=aux_extra, write_window=write_window,
                        record_hashes=record_hashes,
                        delta_base=delta_base, shards=shards,
                        parity=parity)
    comm = comm or SerialComm()
    from repro.checkpoint import redundancy as _red
    from repro.checkpoint import sharding as _sharding
    n_shards = _sharding.shards_default() if shards is None else \
        max(0, int(shards))
    n_parity = _red.parity_default() if parity is None else \
        max(0, int(parity))
    with _trace.span("save", "ckpt", path=path, step=step,
                     shards=n_shards, parity=n_parity,
                     compressed=compressed):
        if n_shards:
            _red.check_geometry(n_shards, n_parity)
            return _sharding.save_sharded(
                path, tree, shards=n_shards, comm=comm, step=step,
                compressed=compressed, chunk_bytes=chunk_bytes,
                aux_extra=aux_extra, write_window=write_window,
                record_hashes=record_hashes, delta_base=delta_base,
                parity=n_parity)
        named, _ = flatten_named(tree)
        leaves: List[mf.LeafSpec] = []
        arrays: List[Any] = []
        aux: Dict[str, Any] = dict(aux_extra or {})
        for name, value in named:
            if _is_array(value):
                leaves.append(mf.LeafSpec.make(
                    name, tuple(np.shape(value)), value.dtype,
                    compressed, chunk_bytes))
                arrays.append(value)
            else:
                aux[name] = _encode_aux(value)
        return _write_checkpoint(
            path, comm=comm, step=step, leaves=leaves, arrays=arrays,
            aux=aux, compressed=compressed, chunk_bytes=chunk_bytes,
            write_window=write_window, record_hashes=record_hashes,
            delta_base=delta_base)


def _write_checkpoint(path: str, *, comm: Optional[Communicator],
                      step: Optional[int], leaves: List[mf.LeafSpec],
                      arrays: List[Any], aux: Dict[str, Any],
                      compressed: bool, chunk_bytes: int,
                      write_window: Optional[int],
                      record_hashes: bool = False,
                      delta_base: Optional[Tuple[Dict[str, Any], str]]
                      = None) -> Dict[str, Any]:
    """The save core shared by :func:`save` and ``scdatool squash``:
    already-flattened leaves → digests → placement plan → archive.

    Splitting "what bytes does this leaf produce" from "where do they
    land" lives here: every layout builds :class:`planner.LeafPlacement`
    objects and one emission loop (:func:`planner.write_placements`)
    drives them through the serial oracle or the overlapped engine.
    Given identical inputs the output bytes are identical regardless of
    the caller — which is what makes a squashed chain byte-equal to a
    direct full save.
    """
    from repro.checkpoint import planner
    comm = comm or SerialComm()
    ww = _effective_write_window(write_window)
    if compressed and comm.size > 1:
        raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                        "compressed checkpoints require chunk-aligned "
                        "partitions; use comm.size == 1 (async snapshot)")
    if (record_hashes or delta_base is not None) and comm.size > 1:
        raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                        "content-hashed / delta checkpoints are "
                        "single-rank; use comm.size == 1 (async snapshot)")

    if record_hashes or delta_base is not None:
        # Digesting touches every byte, so snapshot to host eagerly (the
        # manager pre-snapshots anyway) and reuse the host arrays for
        # the section payloads — one device→host copy, not two.  The
        # delta leg computes the strong hash only; CRC32s are filled in
        # by the planner (computed for stored chunks, inherited from the
        # base for unchanged ones), so save cost tracks changed bytes.
        hosts: List[Any] = []
        for spec_, arr in zip(leaves, arrays):
            host = np.asarray(arr)
            sizes = layout.chunk_sizes(spec_["nbytes"], chunk_bytes)
            view = _byte_view(host)
            if delta_base is not None:
                spec_["chunks"] = {
                    "bytes": int(chunk_bytes),
                    "hash": mf.chunk_strong_hashes(view, sizes)}
            else:
                crcs, hashes = mf.chunk_digests(view, sizes)
                spec_["chunks"] = {"bytes": int(chunk_bytes),
                                   "crc32": crcs, "hash": hashes}
            hosts.append(host)
        arrays = hosts
    delta_table: Optional[Dict[str, Any]] = None
    if delta_base is not None:
        from repro.checkpoint import delta as _delta
        base_doc, base_file = delta_base
        delta_table = _delta.plan_refs(
            leaves, base_doc, base_file,
            views=[_byte_view(h) for h in arrays])

    placements: List[planner.LeafPlacement] = []
    for i, (spec_, arr) in enumerate(zip(leaves, arrays)):
        user = mf.leaf_user_string(i)
        sizes = layout.chunk_sizes(spec_["nbytes"], chunk_bytes)
        if delta_table is not None:
            present = spec_["present"]
            if not present:
                continue  # unchanged leaf: references only, no section

            def snapshot(arr=arr, present=present, sizes=sizes):
                flat = _byte_view(np.asarray(arr))
                return [flat[c * chunk_bytes:c * chunk_bytes + sizes[c]]
                        for c in present]

            placements.append(planner.ChunkPlacement(
                user, [sizes[c] for c in present], snapshot, compressed,
                key=i))
        elif compressed:
            def snapshot(arr=arr, sizes=sizes):
                flat = _byte_view(np.asarray(arr))
                chunks, pos = [], 0
                for s in sizes:
                    chunks.append(flat[pos:pos + s])
                    pos += s
                return chunks

            placements.append(planner.ChunkPlacement(
                user, sizes, snapshot, True, key=i))
        else:
            def snapshot(arr=arr, spec_=spec_):
                return _owned_windows(arr, spec_["nbytes"])

            placements.append(planner.WindowPlacement(
                user, spec_["nbytes"], snapshot, key=i))

    # sync=True: checkpoints must be durable before the manager's atomic
    # rename commits them (every rank fsyncs at close).
    with _trace.span("write_archive", "ckpt", path=path,
                     sections=len(placements)):
        with fopen_write(comm, path, user_string=b"repro checkpoint",
                         sync=True) as f:
            f.write_inline(mf.STATUS_USER_STRING, mf.status_inline(step),
                           root=0)
            f.write_block(
                mf.MANIFEST_USER_STRING,
                mf.build(step, leaves, aux, delta_table)
                if comm.rank == 0 else None,
                E=None, root=0)
            planner.write_placements(f, placements, ww)
    return mf.document(step, leaves, aux, delta_table)


def _encode_aux(value) -> Any:
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, (np.integer,)):
        return int(value)
    if isinstance(value, (np.floating,)):
        return float(value)
    raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                    f"unsupported non-array leaf type {type(value)!r}")


# --------------------------------------------------------------------------
# Restoring
# --------------------------------------------------------------------------

def _read_header_sections(r: ScdaReader) -> Dict[str, Any]:
    """Consume the leading status + manifest sections; returns the doc.

    Accepts both flat checkpoints and sharded-set manifests (told apart
    by the block's user string) — callers check ``doc["format"]`` and
    delegate sharded docs to :mod:`repro.checkpoint.sharding`.
    """
    hdr = r.read_section_header()
    if hdr.type != "I" or hdr.user_string != mf.STATUS_USER_STRING:
        raise ScdaError(ScdaErrorCode.CORRUPT_ENCODING,
                        "not a repro checkpoint: missing status inline")
    step = mf.parse_status_inline(r.read_inline_data())
    hdr = r.read_section_header()
    if hdr.type != "B":
        raise ScdaError(ScdaErrorCode.CORRUPT_ENCODING,
                        "not a repro checkpoint: missing manifest block")
    if hdr.user_string == mf.MANIFEST_USER_STRING:
        doc = mf.parse(r.read_block_data())
    elif hdr.user_string == mf.SHARDS_MANIFEST_USER_STRING:
        doc = mf.parse_sharded(r.read_block_data())
    else:
        raise ScdaError(ScdaErrorCode.CORRUPT_ENCODING,
                        "not a repro checkpoint: missing manifest block")
    if doc.get("step") is None:
        doc["step"] = step
    return doc


def _resolve_index(r: ScdaReader) -> "ScdaIndex":
    """The reader's index, salvaging a valid prefix on a torn tail.

    A checkpoint that was *committed* and then grew a torn post-commit
    append (a power cut mid journal-flush) is still a perfectly good
    checkpoint: every leaf the manifest names lives in the valid prefix.
    A full index build would raise CORRUPT_* on the torn tail and demote
    the whole file; instead, adopt the longest-valid-prefix index.  Safe
    by construction — every seek re-verifies the on-disk section header,
    and a leaf genuinely missing from the prefix still fails the restore
    (which then falls back to an older checkpoint, as before).
    """
    try:
        return r.index()
    except ScdaError as e:
        if e.group != 1:
            raise
        idx = ScdaIndex.build_prefix(r)
        # Keep the corruption error: if a *required* leaf turns out to be
        # missing from the prefix, the file was truncated mid-checkpoint
        # (not torn post-commit) and that original error is the truth.
        idx._salvage_error = e
        r.set_index(idx)
        return idx


def _adopt_sidecar(r: ScdaReader) -> None:
    """Give the reader a ``.scdax`` index if a fresh sidecar exists.

    Purely an optimization: without one, the reader's first seek builds
    the index with a single header-only scan; a stale or unreadable
    sidecar is ignored (and every seek re-checks the on-disk header, so
    even adopting a wrong-but-same-size sidecar cannot corrupt a restore).
    """
    try:
        r.set_index(ScdaIndex.load_sidecar(r.path))
    except (ScdaError, OSError):
        pass


def read_manifest(path: str, comm: Optional[Communicator] = None) \
        -> Dict[str, Any]:
    """Read just the status + manifest (cheap metadata probe)."""
    with fopen_read(comm, path) as r:
        return _read_header_sections(r)


def restore(path: str, like=None, *, comm: Optional[Communicator] = None,
            prefetch_bytes: Optional[int] = None,
            verify: Optional[bool] = None):
    """Restore a checkpoint.

    ``like``: an abstract pytree of ``jax.ShapeDtypeStruct`` (with optional
    ``.sharding``) or concrete arrays defining the target structure and
    placement.  With ``like=None`` a nested dict of numpy arrays is
    rebuilt from the manifest names.

    With ``like`` given the restore is *lazy*: each wanted leaf's section
    is reached by an index seek (``.scdax`` sidecar when fresh, one
    header-only scan otherwise) and unwanted leaves are never touched —
    restoring one tensor of a terabyte archive reads that tensor, the
    manifest, and nothing else.

    Reads run through the overlapped restore engine: all wanted leaf runs
    are sorted by file offset, prefetched ``prefetch_bytes`` ahead
    (default ``REPRO_SCDA_PREFETCH``, 4 MiB) on a background executor,
    and compressed chunks inflate on the codec pool while later preads
    are in flight.  ``prefetch_bytes=0`` restores serially (the byte
    oracle).  Returns ``(tree, step)``.

    ``verify=True`` (or ``REPRO_SCDA_VERIFY_RESTORE=1``) CRC-checks
    every section payload of each opened archive against its
    checksummed ``.scdax`` sidecar before any tensor is returned —
    mismatches raise CORRUPT_CHECKSUM with the exact failing byte
    offset.  Delta-chain *bases* are not re-verified per restore (cover
    them with ``scdatool verify --chain``).
    """
    comm = comm or SerialComm()
    pf = _effective_prefetch(prefetch_bytes)
    vfy = _effective_verify(verify)
    with _trace.span("restore", "ckpt", path=path):
        if vfy:
            _verify_archive(path)
        with fopen_read(comm, path) as r:
            doc = _read_header_sections(r)
            if doc.get("format") != mf.SHARDED_FORMAT:
                return _restore_from_reader(r, doc, like, pf)
        # Sharded set: the manifest file holds no payloads — close it and
        # resolve the per-shard archives (deterministic collective opens).
        from repro.checkpoint import sharding as _sharding
        return _sharding.restore_sharded(path, doc, like, comm=comm,
                                         prefetch_bytes=prefetch_bytes,
                                         verify=vfy)


def _restore_from_reader(r: ScdaReader, doc: Dict[str, Any], like,
                         pf: int):
    """The flat-checkpoint restore body (reader already past the
    manifest) — what :func:`restore` runs once the doc turned out not to
    be a sharded-set manifest."""
    step = doc.get("step")
    chained = bool(doc.get("delta"))
    if chained:
        from repro.checkpoint import delta as _delta
    by_name: Dict[str, Any] = {}
    for i, spec_ in enumerate(doc["leaves"]):
        by_name[spec_["name"]] = (i, spec_)

    if like is None:
        out: Dict[str, Any] = {}
        if chained:
            # Incremental checkpoint: every leaf resolves through the
            # manifest chain (prefetch engine per archive; pf<=0 is
            # the serial oracle inside the resolver too).
            _adopt_sidecar(r)
            wanted = [(spec_["name"], i, spec_, None)
                      for i, spec_ in enumerate(doc["leaves"])]
            out = (_delta.restore_chained(r, doc, wanted, pf)
                   if wanted else {})
        elif pf > 0 and doc["leaves"]:
            _adopt_sidecar(r)
            wanted = [(spec_["name"], i, spec_, None)
                      for i, spec_ in enumerate(doc["leaves"])]
            out = _restore_pipelined(r, wanted, pf)
        else:
            # Serial oracle: the forward walk touches every byte in
            # file order, one section at a time.
            for spec_ in doc["leaves"]:
                hdr = r.read_section_header()
                _check_leaf_header(hdr, spec_)
                out[spec_["name"]] = _read_leaf_full(r, hdr, spec_)
        for name, value in doc["aux"].items():
            out[name] = value
        return _unflatten_names(out), step

    named, treedef = flatten_named(like)
    targets = {n: v for n, v in named}
    missing = [n for n in targets
               if n not in by_name and n not in doc["aux"]]
    if missing:
        raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                        f"leaves missing from checkpoint: {missing[:5]}"
                        f"{'…' if len(missing) > 5 else ''}")
    _adopt_sidecar(r)
    if chained:
        wanted = [(name,) + by_name[name] + (targets[name],)
                  for name in targets if name in by_name]
        values = (_delta.restore_chained(r, doc, wanted, pf)
                  if wanted else {})
    elif pf > 0:
        wanted = [(name,) + by_name[name] + (targets[name],)
                  for name in targets if name in by_name]
        values = _restore_pipelined(r, wanted, pf)
    else:
        values = {}
        for name in targets:
            if name not in by_name:
                continue  # aux leaf
            i, spec_ = by_name[name]
            hdr = r.open_section(mf.leaf_user_string(i))
            _check_leaf_header(hdr, spec_)
            values[name] = _read_leaf_to_target(r, hdr, spec_,
                                                targets[name])
    for name in targets:
        if name in doc["aux"]:
            values[name] = doc["aux"][name]
    leaves_out = [values[n] for n, _ in named]
    return jax.tree_util.tree_unflatten(treedef, leaves_out), step


def restore_leaf(path: str, name: str, like=None, *,
                 comm: Optional[Communicator] = None,
                 prefetch_bytes: Optional[int] = None,
                 verify: Optional[bool] = None):
    """Load ONE leaf from a checkpoint without touching the rest.

    The lazy-restore workload §1 motivates: seek straight to the leaf's
    section (sidecar index or one header scan), read only its bytes —
    for compressed leaves only the chunks overlapping the target shards,
    inflated on the codec pool while later chunk preads are in flight
    (``prefetch_bytes`` as in :func:`restore`).
    ``like`` optionally gives a target (``jax.ShapeDtypeStruct`` with
    ``.sharding`` or a concrete array) to place the leaf onto; with
    ``like=None`` a numpy array is returned.  Aux (non-array) leaves are
    returned from the manifest directly.
    """
    comm = comm or SerialComm()
    pf = _effective_prefetch(prefetch_bytes)
    vfy = _effective_verify(verify)
    with _trace.span("restore_leaf", "ckpt", path=path, leaf=name):
        if vfy:
            _verify_archive(path)
        with fopen_read(comm, path) as r:
            doc = _read_header_sections(r)
            if doc.get("format") == mf.SHARDED_FORMAT:
                sharded = doc
            else:
                return _restore_leaf_from_reader(r, doc, name, like, pf)
        from repro.checkpoint import sharding as _sharding
        return _sharding.restore_leaf_sharded(path, sharded, name, like,
                                              comm=comm,
                                              prefetch_bytes=prefetch_bytes,
                                              verify=vfy)


def _restore_leaf_from_reader(r: ScdaReader, doc: Dict[str, Any],
                              name: str, like, pf: int):
    for i, spec_ in enumerate(doc["leaves"]):
        if spec_["name"] != name:
            continue
        _adopt_sidecar(r)
        if doc.get("delta"):
            from repro.checkpoint import delta as _delta
            return _delta.restore_chained(
                r, doc, [(name, i, spec_, like)], pf)[name]
        if pf > 0:
            return _restore_pipelined(
                r, [(name, i, spec_, like)], pf)[name]
        hdr = r.open_section(mf.leaf_user_string(i))
        _check_leaf_header(hdr, spec_)
        if like is None:
            return _read_leaf_full(r, hdr, spec_)
        return _read_leaf_to_target(r, hdr, spec_, like)
    if name in doc["aux"]:
        return doc["aux"][name]
    raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                    f"leaf {name!r} not in checkpoint")


def _check_leaf_header(hdr, spec_) -> None:
    if spec_.get("store") == "delta":
        # Delta-stored leaves hold only their present chunk subset and
        # are resolved by the chain resolver, never by the flat readers.
        raise ScdaError(ScdaErrorCode.CORRUPT_ENCODING,
                        f"leaf {spec_['name']}: delta-stored leaf outside "
                        f"the chain resolver")
    if spec_["compressed"]:
        if hdr.type != "V" or hdr.N != len(layout.chunk_sizes(
                spec_["nbytes"], spec_["chunk_bytes"])):
            raise ScdaError(ScdaErrorCode.CORRUPT_ENCODING,
                            f"leaf {spec_['name']}: bad compressed section")
    else:
        if hdr.type != "A" or hdr.N != spec_["nbytes"] or hdr.E != 1:
            raise ScdaError(ScdaErrorCode.CORRUPT_ENCODING,
                            f"leaf {spec_['name']}: bad array section "
                            f"({hdr.type} N={hdr.N} E={hdr.E})")


# --------------------------------------------------------------------------
# The overlapped restore engine's checkpoint scheduler
# --------------------------------------------------------------------------

class _Unit:
    """One assembly unit of a leaf: a distinct shard extent (or the whole
    leaf) with its contiguous runs and destination host buffer.

    The buffer is uninitialized (``np.empty``): every byte is covered by
    a run (raw leaves) or a chunk span (compressed leaves), and a 64 MiB
    ``bytearray`` would pay a pure-overhead memset on the hot path.
    """

    __slots__ = ("runs", "shard_shape", "arr", "buf")

    def __init__(self, runs, shard_shape, nbytes: int) -> None:
        self.runs = runs
        self.shard_shape = shard_shape
        self.arr = np.empty(nbytes, np.uint8)
        self.buf = memoryview(self.arr)


def _shard_shape(index, shape) -> Tuple[int, ...]:
    return tuple(sl.indices(dim)[1] - sl.indices(dim)[0]
                 for sl, dim in zip(index, shape)) if shape else ()


def _leaf_layout(name: str, spec_, target) -> Dict[str, Any]:
    """Target-side layout of one leaf: dtype/shape/sharding plus the
    assembly units (distinct shard extents, or the whole leaf) with
    their run decompositions and host buffers.

    Shared by the flat restore scheduler and the delta chain resolver —
    the *destination* of a leaf is the same regardless of which
    archive(s) its bytes come from.
    """
    dtype = mf.dtype_from_name(spec_["dtype"])
    shape = tuple(spec_["shape"])
    sharding = None
    if target is not None:
        t_shape = tuple(getattr(target, "shape", np.shape(target)))
        if t_shape != shape:
            raise ScdaError(
                ScdaErrorCode.ARG_SEQUENCE,
                f"leaf {spec_['name']}: target shape {t_shape} != "
                f"checkpoint shape {shape}")
        sharding = getattr(target, "sharding", None)
    units: List[_Unit] = []
    per_device: List[Tuple[Any, Any]] = []
    whole = True
    if sharding is not None:
        itemsize = np.dtype(dtype).itemsize
        device_map = sharding.addressable_devices_indices_map(shape)
        extents: Dict[Tuple, Any] = {}
        for index in device_map.values():
            extents.setdefault(_index_key(index, shape), index)
        held = sum(_nbytes(_shard_shape(i, shape), itemsize)
                   for i in extents.values())
        # Where this process holds every shard (one host), the leaf is
        # read in one sweep and each device's shard sliced out of it: a
        # column-sharded leaf would otherwise take one read per row.
        whole = held == spec_["nbytes"]
        if whole:
            per_device = list(device_map.items())
        else:
            by_extent: Dict[Tuple, int] = {}
            for device, index in device_map.items():
                key = _index_key(index, shape)
                if key not in by_extent:
                    sshape = _shard_shape(index, shape)
                    by_extent[key] = len(units)
                    units.append(_Unit(
                        layout.shard_runs(shape, index, itemsize), sshape,
                        _nbytes(sshape, itemsize)))
                per_device.append((device, by_extent[key]))
    if whole:
        runs = [(0, 0, spec_["nbytes"])] if spec_["nbytes"] else []
        units.append(_Unit(runs, shape, spec_["nbytes"]))
    return {"name": name, "spec": spec_, "target": target,
            "dtype": dtype, "shape": shape, "sharding": sharding,
            "whole": whole, "units": units, "per_device": per_device,
            "pending": 0}


def _nbytes(shard_shape, itemsize: int) -> int:
    return (int(np.prod(shard_shape, dtype=np.int64)) * itemsize
            if shard_shape else itemsize)


def _restore_pipelined(r: ScdaReader, wanted, prefetch_bytes: int) \
        -> Dict[str, Any]:
    """Restore ``wanted`` leaves through the overlapped engine.

    ``wanted``: list of ``(name, manifest_index, spec, target)`` with
    ``target`` a ShapeDtypeStruct/array (placement honored) or None
    (plain numpy out).  One index walk plans every leaf: raw leaves read
    straight into their shard buffers (zero-copy scatter reads),
    compressed leaves read only the chunks overlapping their shards and
    inflate them on the codec pool.  All plans are sorted by file offset
    so consumption sweeps the archive front to back while prefetch runs
    ``prefetch_bytes`` ahead; fully consumed extents are released
    (``DONTNEED``).  Byte-identical to the serial walk by construction —
    only the schedule changes, never the bytes.
    """
    idx = _resolve_index(r)
    backend = r._backend
    leaves: List[Dict[str, Any]] = []
    items: List[ReadItem] = []
    for leaf_pos, (name, i, spec_, target) in enumerate(wanted):
        user = mf.leaf_user_string(i)
        sec = idx.find(user)
        if sec < 0:
            salvage = getattr(idx, "_salvage_error", None)
            if salvage is not None:
                raise salvage
            raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                            f"no section with user string {user!r} "
                            f"(occurrence 0)")
        e = idx.entries[sec]
        r.verify_index_entry(sec, e)
        _check_leaf_header(e.header(), spec_)
        leaf = _leaf_layout(name, spec_, target)
        units = leaf["units"]
        if spec_["compressed"]:
            chunk = spec_["chunk_bytes"]
            csizes = r._parse_entries(e.v_entries_start, 0, e.N, b"E")
            usizes = r._parse_entries(e.entries_start, 0, e.N, b"U")
            offs = partition.offsets(csizes)
            for ui, unit in enumerate(units):
                needed = layout.chunks_for_runs(unit.runs, chunk)
                if not needed:
                    continue
                items.append(ReadItem(
                    (leaf_pos, ui, needed),
                    [(e.v_data_start + offs[c], csizes[c]) for c in needed],
                    inflate=True,
                    expected_sizes=[usizes[c] for c in needed]))
                leaf["pending"] += 1
        else:
            for ui, unit in enumerate(units):
                if not unit.runs:
                    continue
                view = memoryview(unit.buf)
                items.append(ReadItem(
                    (leaf_pos, ui, None),
                    [(e.data_start + g, n) for g, _, n in unit.runs],
                    dst=[view[loc:loc + n] for _, loc, n in unit.runs]))
                leaf["pending"] += 1
        leaves.append(leaf)

    items.sort(key=lambda it: it.start())
    values: Dict[str, Any] = {}
    for leaf in leaves:  # zero-byte leaves have nothing in flight
        if leaf["pending"] == 0:
            values[leaf["name"]] = _finalize_leaf(leaf)
    for key, res in run_pipeline(backend, items, prefetch_bytes):
        leaf_pos, ui, needed = key
        leaf = leaves[leaf_pos]
        unit = leaf["units"][ui]
        if needed is not None:  # compressed: scatter chunks into the unit
            if leaf["sharding"] is None:
                # Whole-leaf unit: mirror the serial _read_leaf_full
                # exactly — chunks concatenate in element order and the
                # total must equal the manifest size, with no boundary
                # assumption (a foreign archive whose chunk sizes stray
                # from the layout geometry still joins to the same
                # bytes, or fails with the same error, as the oracle).
                _fill_joined(res, unit.arr, leaf["spec"])
            else:
                _scatter_chunks_np(unit.runs, dict(zip(needed, res)),
                                   leaf["spec"]["chunk_bytes"], unit.arr)
        leaf["pending"] -= 1
        if leaf["pending"] == 0:
            values[leaf["name"]] = _finalize_leaf(leaf)
    return values


def _finalize_leaf(leaf: Dict[str, Any]):
    """Assemble a completed leaf from its unit buffers (host → device)."""
    dtype, shape = leaf["dtype"], leaf["shape"]
    if leaf["whole"]:
        full = leaf["units"][0].arr.view(dtype).reshape(shape)
        if leaf["sharding"] is None:
            return full
        return _place(leaf["name"], shape, leaf["sharding"],
                      ((full[index], device)
                       for device, index in leaf["per_device"]))
    units = leaf["units"]
    return _place(leaf["name"], shape, leaf["sharding"],
                  ((units[ui].arr.view(dtype).reshape(units[ui].shard_shape),
                    device) for device, ui in leaf["per_device"]))


def _place(name: str, shape, sharding, pieces):
    """Host → device: put each ``(host array, device)`` piece on its
    device and assemble the global array — one ``ckpt.place`` span with
    the bytes sent to the devices."""
    c = _trace.collector()
    t0 = 0 if c is None else c.now()
    arrays, nbytes = [], 0
    for arr, device in pieces:
        arrays.append(jax.device_put(arr, device))
        nbytes += arr.nbytes
    out = jax.make_array_from_single_device_arrays(shape, sharding, arrays)
    if c is not None:
        c.end("place", "ckpt", t0, {"leaf": name, "bytes": nbytes})
    return out


def _fill_joined(chunks: List[bytes], arr: np.ndarray, spec_) -> None:
    """Serial-oracle assembly for a whole-leaf unit: the inflated chunks
    are concatenated in element order and the total checked against the
    manifest — :func:`_read_leaf_full`'s ``b"".join`` + size check,
    without materializing the join."""
    total = sum(map(len, chunks))
    if total != spec_["nbytes"]:
        raise ScdaError(ScdaErrorCode.CORRUPT_CHECKSUM,
                        f"leaf {spec_['name']}: {total} bytes, "
                        f"manifest says {spec_['nbytes']}")
    pos = 0
    for c in chunks:
        if len(c):
            arr[pos:pos + len(c)] = np.frombuffer(c, np.uint8)
            pos += len(c)


def _short_chunk(ci: int, have: int, want: int) -> ScdaError:
    return ScdaError(
        ScdaErrorCode.CORRUPT_CHECKSUM,
        f"chunk {ci} holds {have} bytes, layout needs {want} — inflated "
        f"size disagrees with the manifest chunk geometry")


def _scatter_chunks(runs, chunks: Dict[int, bytes], chunk_bytes: int,
                    buf) -> None:
    """Copy the overlapping spans of inflated ``chunks`` into ``buf``
    (any mutable byte buffer: bytearray or a uint8 memoryview).

    A chunk shorter than the manifest geometry implies (a corrupt or
    foreign archive whose U-entries disagree with ``chunk_bytes``) is a
    CORRUPT_CHECKSUM :class:`ScdaError`, never a silent short copy.
    One implementation serves both paths — ``np.frombuffer`` wraps any
    writable buffer — so the serial and pipelined scatters cannot
    diverge.
    """
    _scatter_chunks_np(runs, chunks, chunk_bytes,
                       np.frombuffer(buf, np.uint8))


def _scatter_chunks_np(runs, chunks: Dict[int, bytes], chunk_bytes: int,
                       arr: np.ndarray) -> None:
    """:func:`_scatter_chunks` for a uint8 ndarray destination: big spans
    copy through numpy (which drops the GIL), so the engine's assembly
    does not stall the codec pool's decode slices."""
    for goff, loff, n in runs:
        pos = 0
        while pos < n:
            ci, off = divmod(goff + pos, chunk_bytes)
            take = min(n - pos, chunk_bytes - off)
            data = chunks[ci]
            if len(data) < off + take:
                raise _short_chunk(ci, len(data), off + take)
            arr[loff + pos:loff + pos + take] = \
                np.frombuffer(data, np.uint8, take, off)
            pos += take


def _read_leaf_full(r: ScdaReader, hdr, spec_) -> np.ndarray:
    dtype = mf.dtype_from_name(spec_["dtype"])
    if spec_["compressed"]:
        sizes = layout.chunk_sizes(spec_["nbytes"], spec_["chunk_bytes"])
        n = len(sizes)
        raw = b"".join(r.read_varray_elements(list(range(n))))
        r.skip_data()
    else:
        raw = b"".join(r.read_array_windows([(0, spec_["nbytes"])], 1))
        r.skip_data()
    if len(raw) != spec_["nbytes"]:
        raise ScdaError(ScdaErrorCode.CORRUPT_CHECKSUM,
                        f"leaf {spec_['name']}: {len(raw)} bytes, "
                        f"manifest says {spec_['nbytes']}")
    arr = np.frombuffer(raw, dtype=dtype).reshape(spec_["shape"])
    return arr.copy()


def _read_leaf_to_target(r: ScdaReader, hdr, spec_, target):
    """Assemble the leaf under the target's sharding (any mesh)."""
    dtype = mf.dtype_from_name(spec_["dtype"])
    shape = tuple(spec_["shape"])
    t_shape = tuple(getattr(target, "shape", np.shape(target)))
    if tuple(t_shape) != shape:
        raise ScdaError(ScdaErrorCode.ARG_SEQUENCE,
                        f"leaf {spec_['name']}: target shape {t_shape} != "
                        f"checkpoint shape {shape}")
    sharding = getattr(target, "sharding", None)
    if sharding is None:
        return _read_leaf_full(r, hdr, spec_)

    # One host buffer per *distinct* addressable shard extent.
    device_map = sharding.addressable_devices_indices_map(shape)
    shard_arrays: Dict[Tuple, np.ndarray] = {}
    per_device = []
    for device, index in device_map.items():
        key = _index_key(index, shape)
        if key not in shard_arrays:
            shard_arrays[key] = _read_shard(r, spec_, index, shape, dtype)
        per_device.append((device, shard_arrays[key]))
    r.skip_data()
    return _place(spec_["name"], shape, sharding,
                  ((arr, device) for device, arr in per_device))


def _index_key(index, shape) -> Tuple:
    out = []
    for sl, dim in zip(index, shape):
        start, stop, _ = sl.indices(dim)
        out.append((start, stop))
    return tuple(out)


def _read_shard(r: ScdaReader, spec_, index, shape, dtype) -> np.ndarray:
    itemsize = np.dtype(dtype).itemsize
    runs = layout.shard_runs(shape, index, itemsize)
    shard_shape = _shard_shape(index, shape)
    buf = bytearray(int(np.prod(shard_shape, dtype=np.int64)) * itemsize
                    if shard_shape else itemsize)
    if spec_["compressed"]:
        _fill_from_chunks(r, spec_, runs, buf)
    else:
        if runs:
            got = r.read_array_windows([(g, n) for g, _, n in runs], 1)
            for (g, loff, n), raw in zip(runs, got):
                buf[loff:loff + n] = raw
    arr = np.frombuffer(bytes(buf), dtype=dtype)
    return arr.reshape(shard_shape)


def _fill_from_chunks(r: ScdaReader, spec_, runs, buf: bytearray) -> None:
    """Selective chunk reads: only chunks overlapping this shard's runs."""
    chunk = spec_["chunk_bytes"]
    needed = layout.chunks_for_runs(runs, chunk)
    if not needed:
        return
    chunks = dict(zip(needed, r.read_varray_elements(needed)))
    _scatter_chunks(runs, chunks, chunk, buf)


def _unflatten_names(flat: Dict[str, Any]):
    """Rebuild a nested dict from 'a/b/c' names (like=None restores)."""
    root: Dict[str, Any] = {}
    for name, value in flat.items():
        parts = name.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return root

"""The overlapped restore engine (PR 3): scatter reads, prefetch,
read_batch, and the pipelined checkpoint restore scheduler.

Core invariant: the pipeline changes WHEN bytes are read and WHERE they
inflate, never WHAT is returned — every pipelined result must be
byte-identical to the serial forward-walk oracle (REPRO_SCDA_PREFETCH=0),
at every reading partition, and every failure must raise the same
ScdaError the serial path raises (no hangs, no leaked futures).
"""
import os
import types

import numpy as np
import pytest

from repro.checkpoint import pytree_io
from repro.core import (ScdaError, ThreadComm, fopen_read, fopen_write,
                        partition, run_ranks)
from repro.core.errors import ScdaErrorCode
from repro.core.io_backend import FileBackend, prefetch_window
from repro.core.pipeline import ReadItem, run_pipeline

PF = 1 << 20  # pipelined prefetch window used throughout
V_SIZES = [5, 0, 17, 3, 64, 1]


def write_all_kinds(path):
    rng = __import__("random").Random(7)
    elems = [bytes(rng.randrange(256) for _ in range(s)) for s in V_SIZES]
    blk = b"0123456789abcdef" * 40
    arr = bytes(range(256)) * 2
    with fopen_write(None, path, user_string=b"pipeline test") as f:
        f.write_inline(b"inl", b"#" * 32)
        f.write_block(b"blk", blk)
        f.write_array(b"arr", arr, [64], 8)
        f.write_varray(b"var", elems, [len(elems)], V_SIZES)
        f.write_block(b"zblk", blk, encode=True)
        f.write_array(b"zarr", arr, [128], 4, encode=True)
        f.write_varray(b"zvar", elems, [len(elems)], V_SIZES, encode=True)
    return blk, arr, elems


# --------------------------------------------------------------------------
# FileBackend: read_scatter / preadv / prefetch / readahead refit
# --------------------------------------------------------------------------

class TestReadScatter:
    @pytest.fixture
    def datafile(self, tmp_path):
        path = str(tmp_path / "d.bin")
        data = bytes(np.random.default_rng(0).integers(
            0, 256, 1 << 20, dtype=np.uint8))
        with open(path, "wb") as fh:
            fh.write(data)
        return path, data

    def test_adjacent_and_gapped_fragments(self, datafile):
        path, data = datafile
        b = FileBackend(path, "r", create=False)
        bufs = [bytearray(100), bytearray(50), bytearray(200),
                bytearray(0), bytearray(7)]
        b.read_scatter([(0, bufs[0]), (100, bufs[1]), (500, bufs[2]),
                        (700, bufs[3]), (700, bufs[4])])
        assert bytes(bufs[0]) == data[:100]
        assert bytes(bufs[1]) == data[100:150]
        assert bytes(bufs[2]) == data[500:700]
        assert bytes(bufs[4]) == data[700:707]
        b.close()

    def test_truncation_raises_like_pread(self, datafile):
        path, data = datafile
        b = FileBackend(path, "r", create=False)
        with pytest.raises(ScdaError) as ei:
            b.read_scatter([(len(data) - 10, bytearray(100))])
        assert ei.value.code == ScdaErrorCode.CORRUPT_TRUNCATED
        b.close()

    def test_prefetch_serves_reads_and_release_advises(self, datafile):
        path, data = datafile
        b = FileBackend(path, "r", create=False)
        accepted = b.prefetch([(1000, 4096), (5096, 4096), (20000, 512)],
                              window=1 << 20)
        assert accepted == 3
        out = bytearray(8192)
        b.read_scatter([(1000, out)])  # served from the prefetch cache
        assert bytes(out) == data[1000:9192]
        assert b.pread(20000, 100) == data[20000:20100]
        b.release(10000)
        assert b.pending_prefetch() == 1  # the 20000 extent survives
        b.release(1 << 30)
        assert b.pending_prefetch() == 0
        b.close()

    def test_prefetch_window_bounds_buffering(self, datafile):
        path, _ = datafile
        b = FileBackend(path, "r", create=False)
        # 16 KiB window cannot accept 1 MiB of extents up front.
        extents = [(i * 4096, 4096) for i in range(256)]
        accepted = b.prefetch(extents, window=16 << 10)
        assert 0 < accepted < len(extents)
        b.close()
        assert b.pending_prefetch() == 0  # close drains everything

    def test_prefetch_noop_on_write_mode_and_zero_window(self, tmp_path):
        path = str(tmp_path / "w.bin")
        b = FileBackend(path, "w", create=True)
        assert b.prefetch([(0, 10)], window=1 << 20) == 0
        b.close()
        datapath = str(tmp_path / "r.bin")
        with open(datapath, "wb") as fh:
            fh.write(b"x" * 100)
        b = FileBackend(datapath, "r", create=False)
        assert b.prefetch([(0, 10)], window=0) == 0
        assert b.pending_prefetch() == 0
        b.close()

    def test_refit_readahead_on_jump(self, datafile):
        path, data = datafile
        b = FileBackend(path, "r", create=False, readahead=4096)
        b.pread(0, 32)  # window at 0
        assert b._cache_off == 0
        b.refit_readahead(300000)  # jump outside → drop and refit
        assert b._cache_off == 300000 and len(b._cache) > 0
        assert b.pread(300010, 20) == data[300010:300030]
        b.refit_readahead(300100)  # inside the window → untouched
        assert b._cache_off == 300000
        b.close()

    def test_run_pipeline_serial_equals_pipelined(self, datafile):
        path, data = datafile
        items = [ReadItem(i, [(i * 1000, 500), ((i + 1) * 1000, 250)])
                 for i in range(20)]
        results = {}
        for pf in (0, PF):
            b = FileBackend(path, "r", create=False)
            results[pf] = {k: [bytes(x) for x in res]
                           for k, res in run_pipeline(b, items, pf)}
            b.close()
        assert results[0] == results[PF]
        assert results[0][3][0] == data[3000:3500]


# --------------------------------------------------------------------------
# read_batch: byte-identity against the forward walk at P∈{1,2,4,8}
# --------------------------------------------------------------------------

@pytest.mark.parametrize("P", [1, 2, 4, 8])
@pytest.mark.parametrize("pf", [0, PF])
def test_read_batch_matches_forward_walk(tmp_path, P, pf):
    path = str(tmp_path / "a.scda")
    blk, arr, elems = write_all_kinds(path)
    # serial oracle: full payloads via the forward walk
    oracle = {}
    with fopen_read(None, path) as r:
        i = 0
        while not r.at_eof:
            hdr = r.read_section_header()
            if hdr.type == "I":
                oracle[i] = r.read_inline_data()
            elif hdr.type == "B":
                oracle[i] = r.read_block_data()
            elif hdr.type == "A":
                oracle[i] = b"".join(r.read_array_data([hdr.N]))
            else:
                sizes = r.read_varray_sizes([hdr.N])
                oracle[i] = b"".join(r.read_varray_data([hdr.N], sizes))
            i += 1

    batchable = {2: 64, 3: len(V_SIZES), 5: 128, 6: len(V_SIZES)}

    def workload(comm):
        out = {}
        with fopen_read(comm, path) as r:
            reqs = []
            for sec, N in batchable.items():
                counts = partition.uniform(N, comm.size)
                offs = partition.offsets(counts)
                lo, n = offs[comm.rank], counts[comm.rank]
                reqs.append((sec, [(lo, n)] if n else []))
            for pos, res in r.read_batch(reqs, prefetch_bytes=pf):
                out[list(batchable)[pos]] = b"".join(res)
        return out

    per_rank = run_ranks(ThreadComm.group(P), workload)
    for sec in batchable:
        joined = b"".join(rank[sec] for rank in per_rank)
        assert joined == oracle[sec], f"section {sec} differs under P={P}"


def test_read_batch_window_validation(tmp_path):
    path = str(tmp_path / "a.scda")
    write_all_kinds(path)
    with fopen_read(None, path) as r:
        with pytest.raises(ScdaError):
            list(r.read_batch([(2, [(60, 10)])]))  # beyond N=64
        with pytest.raises(ScdaError):
            list(r.read_batch([(0, [(0, 1)])]))  # inline not batchable
        with pytest.raises(ScdaError):
            list(r.read_batch([(99, [(0, 1)])]))


# --------------------------------------------------------------------------
# Checkpoint restore: pipelined == serial oracle, raw + compressed
# --------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((64, 48)).astype(np.float32),
        "b": np.arange(1 << 15, dtype=np.float64),  # compressible
        "m": rng.integers(0, 255, (3, 5, 7), dtype=np.uint8),
        "empty": np.zeros((0, 4), np.int32),
        "scalar": np.float32(3.25),
        "lr": 0.125,
    }


@pytest.mark.parametrize("compressed", [False, True])
def test_restore_pipelined_equals_serial(tmp_path, compressed):
    path = str(tmp_path / "ck.scda")
    tree = _tree()
    pytree_io.save(path, tree, step=11, compressed=compressed,
                   chunk_bytes=1 << 12)
    serial, st0 = pytree_io.restore(path, prefetch_bytes=0)
    piped, st1 = pytree_io.restore(path, prefetch_bytes=PF)
    assert st0 == st1 == 11
    for k in ("w", "b", "m", "empty", "scalar"):
        np.testing.assert_array_equal(serial[k], piped[k])
        np.testing.assert_array_equal(piped[k], tree[k])
    assert piped["lr"] == tree["lr"]


@pytest.mark.parametrize("compressed", [False, True])
def test_restore_leaf_pipelined_equals_serial(tmp_path, compressed):
    path = str(tmp_path / "ck.scda")
    tree = _tree(1)
    pytree_io.save(path, tree, compressed=compressed, chunk_bytes=1 << 12)
    for name in ("w", "b", "m"):
        serial = pytree_io.restore_leaf(path, name, prefetch_bytes=0)
        piped = pytree_io.restore_leaf(path, name, prefetch_bytes=PF)
        np.testing.assert_array_equal(serial, piped)
    assert pytree_io.restore_leaf(path, "lr", prefetch_bytes=PF) == 0.125


@pytest.mark.parametrize("P", [1, 2, 4, 8])
def test_restore_identity_under_thread_ranks(tmp_path, P):
    """Every rank's pipelined restore equals the serial oracle — prefetch
    on and off, raw and compressed, concurrently at P ranks."""
    raw = str(tmp_path / "raw.scda")
    comp = str(tmp_path / "comp.scda")
    tree = _tree(2)
    pytree_io.save(raw, tree)
    pytree_io.save(comp, tree, compressed=True, chunk_bytes=1 << 12)
    oracle = {p: pytree_io.restore(p, prefetch_bytes=0)[0]
              for p in (raw, comp)}

    def workload(comm):
        # rank-local pipelined restores against one shared file
        out = {}
        for p in (raw, comp):
            out[p], _ = pytree_io.restore(p, prefetch_bytes=PF)
        return out

    for rank_out in run_ranks(ThreadComm.group(P), workload):
        for p in (raw, comp):
            for k in ("w", "b", "m", "empty", "scalar"):
                np.testing.assert_array_equal(rank_out[p][k], oracle[p][k])


def test_restore_like_pipelined_equals_serial(tmp_path):
    jax = pytest.importorskip("jax")
    path = str(tmp_path / "ck.scda")
    tree = _tree(3)
    pytree_io.save(path, tree, step=5)
    like = {"w": jax.ShapeDtypeStruct((64, 48), np.float32),
            "b": jax.ShapeDtypeStruct((1 << 15,), np.float64),
            "lr": 0.0}
    serial, _ = pytree_io.restore(path, like, prefetch_bytes=0)
    piped, _ = pytree_io.restore(path, like, prefetch_bytes=PF)
    np.testing.assert_array_equal(serial["w"], piped["w"])
    np.testing.assert_array_equal(serial["b"], piped["b"])
    assert piped["lr"] == 0.125

    bad = {"w": jax.ShapeDtypeStruct((4, 4), np.float32)}
    with pytest.raises(ScdaError) as ei:
        pytree_io.restore(path, bad, prefetch_bytes=PF)
    assert ei.value.code == ScdaErrorCode.ARG_SEQUENCE


def test_prefetch_env_knob(tmp_path, monkeypatch):
    path = str(tmp_path / "ck.scda")
    tree = _tree(4)
    pytree_io.save(path, tree)
    monkeypatch.setenv("REPRO_SCDA_PREFETCH", "0")
    assert prefetch_window() == 0
    s0, _ = pytree_io.restore(path)
    monkeypatch.setenv("REPRO_SCDA_PREFETCH", str(PF))
    assert prefetch_window() == PF
    s1, _ = pytree_io.restore(path)
    for k in ("w", "b", "m"):
        np.testing.assert_array_equal(s0[k], s1[k])


# --------------------------------------------------------------------------
# Failure behavior: same errors as serial, no hangs, no leaked futures
# --------------------------------------------------------------------------

def _leaf_payload_extent(path):
    """(data_start, end) of the compressed leaf's carrier V payload."""
    from repro.core import ScdaIndex
    idx = ScdaIndex.build(path)
    for e in idx:
        if e.kind == "zV":
            return e.v_data_start, e.end
    raise AssertionError("no compressed leaf found")


@pytest.fixture
def corrupt_compressed_ckpt(tmp_path):
    path = str(tmp_path / "ck.scda")
    pytree_io.save(path, _tree(5), compressed=True, chunk_bytes=1 << 12)
    data_start, end = _leaf_payload_extent(path)
    with open(path, "r+b") as fh:  # clobber a chunk mid-payload
        fh.seek(data_start + (end - data_start) // 2)
        fh.write(b"\x00" * 16)
    return path


def test_corrupt_chunk_same_error_serial_vs_pipelined(
        corrupt_compressed_ckpt):
    path = corrupt_compressed_ckpt
    errors = {}
    for pf in (0, PF):
        with pytest.raises(ScdaError) as ei:
            pytree_io.restore(path, prefetch_bytes=pf)
        errors[pf] = ei.value.code
    assert errors[0] == errors[PF]
    assert errors[0] in (ScdaErrorCode.CORRUPT_ENCODING,
                         ScdaErrorCode.CORRUPT_CHECKSUM)


@pytest.mark.parametrize("sizes,want", [
    ([3000, 5000, 2000], "ok"),       # re-chunked, total preserved
    ([4096, 4096, 1900], "error"),    # total disagrees with the manifest
])
def test_foreign_chunking_parity(tmp_path, sizes, want):
    """A foreign archive whose chunk sizes stray from the manifest layout
    (chunk count intact, U-entries self-consistent): the serial oracle
    joins chunks boundary-blind and checks only the total, so the
    pipelined whole-leaf path must do exactly the same — same bytes when
    the total matches, same CORRUPT_CHECKSUM when it doesn't."""
    from repro.checkpoint import manifest as mf
    orig = str(tmp_path / "orig.scda")
    data = np.arange(2500, dtype=np.float32)  # 10000 bytes, 3 chunks @4096
    pytree_io.save(orig, {"w": data}, compressed=True, chunk_bytes=4096)
    with fopen_read(None, orig) as r:
        r.read_section_header()
        status = r.read_inline_data()
        r.read_section_header()
        man = r.read_block_data()
    path = str(tmp_path / "foreign.scda")
    flat, chunks, pos = data.tobytes(), [], 0
    for s in sizes:
        c = flat[pos:pos + s]
        chunks.append(c + b"\0" * (s - len(c)))
        pos += s
    with fopen_write(None, path, user_string=b"repro checkpoint") as w:
        w.write_inline(mf.STATUS_USER_STRING, status)
        w.write_block(mf.MANIFEST_USER_STRING, man, E=None)
        w.write_varray(mf.leaf_user_string(0), chunks, [len(sizes)],
                       [len(c) for c in chunks], encode=True)
    outcomes = []
    for pf in (0, PF):
        try:
            out, _ = pytree_io.restore(path, prefetch_bytes=pf)
            outcomes.append(("ok", out["w"].tobytes()))
        except ScdaError as e:
            outcomes.append(("error", e.code))
    assert outcomes[0] == outcomes[1]
    assert outcomes[0][0] == want
    if want == "ok":
        assert outcomes[0][1] == flat
    else:
        assert outcomes[0][1] == ScdaErrorCode.CORRUPT_CHECKSUM


def test_corrupt_chunk_no_leaked_futures(corrupt_compressed_ckpt):
    path = corrupt_compressed_ckpt
    # reader-level: batch every chunk of the corrupt leaf
    with fopen_read(None, path) as r:
        idx = r.index()
        sec = next(i for i, e in enumerate(idx.entries) if e.kind == "zV")
        N = idx.entries[sec].N
        with pytest.raises(ScdaError) as ei:
            for _ in r.read_batch([(sec, [(0, N)])], prefetch_bytes=PF):
                pass
        assert ei.value.code in (ScdaErrorCode.CORRUPT_ENCODING,
                                 ScdaErrorCode.CORRUPT_CHECKSUM)
        backend = r._backend
    # close() ran inside the context manager: everything drained
    assert backend.pending_prefetch() == 0
    assert backend._pf_pool is None


def test_truncated_archive_same_error_serial_vs_pipelined(tmp_path):
    path = str(tmp_path / "ck.scda")
    pytree_io.save(path, _tree(6))
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(size - 200)  # cut into the last leaf's payload
    errors = {}
    for pf in (0, PF):
        with pytest.raises(ScdaError) as ei:
            pytree_io.restore(path, prefetch_bytes=pf)
        errors[pf] = ei.value.code
    assert errors[0] == errors[PF] == ScdaErrorCode.CORRUPT_TRUNCATED


def test_short_chunk_raises_scda_error_not_valueerror():
    """A chunk shorter than the manifest geometry implies (corrupt or
    foreign U-entries) must raise CORRUPT_CHECKSUM from both scatter
    implementations, never a bare ValueError."""
    runs = [(0, 0, 2048)]
    chunks = {0: b"x" * 1024, 1: b"y" * 100}  # chunk 1 short of 1024
    with pytest.raises(ScdaError) as ei:
        pytree_io._scatter_chunks(runs, chunks, 1024, bytearray(2048))
    assert ei.value.code == ScdaErrorCode.CORRUPT_CHECKSUM
    with pytest.raises(ScdaError) as ei:
        pytree_io._scatter_chunks_np(runs, chunks, 1024,
                                     np.empty(2048, np.uint8))
    assert ei.value.code == ScdaErrorCode.CORRUPT_CHECKSUM


class _HalfRows:
    """A sharding of which this process holds only the first half of the
    rows, as one host of two would."""

    def __init__(self, device):
        self.device = device

    def addressable_devices_indices_map(self, shape):
        return {self.device: (slice(0, shape[0] // 2), slice(0, shape[1]))}


def test_leaf_layout_reads_whole_leaf_when_every_shard_is_held():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.layout import shard_runs
    from repro.launch.mesh import make_host_mesh
    spec_ = {"name": "w", "dtype": "float32", "shape": [8, 6],
             "nbytes": 8 * 6 * 4}
    held = jax.ShapeDtypeStruct((8, 6), np.float32, sharding=NamedSharding(
        make_host_mesh(1, 1), P("data", "model")))
    leaf = pytree_io._leaf_layout("w", spec_, held)
    assert leaf["whole"] and len(leaf["units"]) == 1
    assert leaf["units"][0].runs == [(0, 0, spec_["nbytes"])]
    # Another host holds the rest: read only this host's rows.
    dev = jax.devices()[0]
    part = types.SimpleNamespace(shape=(8, 6), sharding=_HalfRows(dev))
    leaf = pytree_io._leaf_layout("w", spec_, part)
    assert not leaf["whole"] and leaf["per_device"] == [(dev, 0)]
    assert leaf["units"][0].runs == shard_runs(
        (8, 6), (slice(0, 4), slice(0, 6)), 4)

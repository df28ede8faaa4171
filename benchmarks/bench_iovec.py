"""Micro-benchmark for the scatter-gather write core: one varray-shaped
section (header + count entries + many element payloads + padding) written

  * per-fragment  — one ``pwrite`` syscall per fragment (the seed path),
  * joined        — ``b"".join`` then one ``pwrite`` (copies the payload),
  * coalesced     — one ``pwritev`` via ``FileBackend.write_gather``
                    (zero-copy, the current fast path).

Shows where buffer coalescing around a positioned-write core wins (cf.
Lemon, arXiv:1106.4177)."""
import os
import tempfile
import time

from repro.core.io_backend import FileBackend


def _fragments(n_frag, frag_bytes):
    header = os.urandom(64)
    entries = [os.urandom(32) for _ in range(n_frag)]
    payload = [os.urandom(frag_bytes) for _ in range(n_frag)]
    frags = [header] + entries + payload + [os.urandom(32)]
    offs, pos = [], 0
    for f in frags:
        offs.append(pos)
        pos += len(f)
    return list(zip(offs, frags)), pos


def _time(fn, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def run(quick=False):
    rows = []
    n_frag = 256 if quick else 1024
    # Above io_backend.JOIN_SMALL so the coalesced strategy actually
    # exercises the zero-copy multi-iovec pwritev branch (small fragments
    # would be user-space pre-joined and measure a plain pwrite).
    frag_bytes = 16384
    frags, total = _fragments(n_frag, frag_bytes)
    reps = 10 if quick else 30
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "iovec.bin")
        be = FileBackend(path, "w", create=True)

        def per_fragment():
            for off, buf in frags:
                be.pwrite(off, buf)

        def joined():
            be.pwrite(0, b"".join(f for _, f in frags))

        def coalesced():
            be.write_gather(frags)

        t_frag = _time(per_fragment, reps)
        t_join = _time(joined, reps)
        t_vec = _time(coalesced, reps)
        be.close()
        mb = total / (1 << 20)
        rows.append((f"iovec.per_fragment_{n_frag}", t_frag,
                     f"{mb / (t_frag / 1e6):.0f}MB/s"))
        rows.append((f"iovec.joined_{n_frag}", t_join,
                     f"{mb / (t_join / 1e6):.0f}MB/s"))
        rows.append((f"iovec.coalesced_{n_frag}", t_vec,
                     f"{mb / (t_vec / 1e6):.0f}MB/s;"
                     f"speedup={t_frag / t_vec:.1f}x"))
    return rows

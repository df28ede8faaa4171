"""End-to-end telemetry (repro.core.trace): metrics aggregation, Chrome
trace_event export and schema, byte-parity of traced vs untraced saves,
the warn() channel, per-commit journal records, error op-context, and
the scdatool stats / --timing surfaces."""
import json
import os
import time

import numpy as np
import pytest

from repro.checkpoint import pytree_io, sharding
from repro.checkpoint.manager import CheckpointManager
from repro.core import (ScdaError, ScdaErrorCode, ThreadComm, run_ranks,
                        trace)
from repro.core.io_backend import FileBackend
from repro.journal import iter_records
from repro.tools import cli

WW = 1 << 16  # write window enabling the background writeback path


@pytest.fixture(autouse=True)
def _trace_isolation(monkeypatch):
    monkeypatch.delenv(trace.TRACE_ENV, raising=False)
    trace.uninstall()
    trace.reset_warn_limits()
    yield
    trace.uninstall()
    trace.reset_warn_limits()


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((64, 33)).astype(np.float32),
        "b": np.arange(257, dtype=np.int64),
        "bytes": np.frombuffer(b"scda trace " * 300,
                               dtype=np.uint8).copy(),
    }


def _assert_tree_equal(got, want):
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(v))


# ------------------------------------------------------------ metrics ----

def test_metrics_counters_and_histograms():
    m = trace.Metrics()
    m.count("io.pwrite.calls")
    m.count("io.pwrite.calls", 2)
    m.count("io.pwrite.bytes", 4096)
    for us in (1.0, 10.0, 100.0, 1000.0):
        m.observe("io.pwrite.us", us)
    snap = m.snapshot()
    assert snap["counters"]["io.pwrite.calls"] == 3
    assert snap["counters"]["io.pwrite.bytes"] == 4096
    h = snap["histograms"]["io.pwrite.us"]
    assert h == {"count": 4, "total_us": pytest.approx(1111.0)}
    assert json.dumps(snap)  # plain-dict, JSON-able as-is


def test_commit_record_returns_deltas():
    c = trace.TraceCollector()
    c.metrics.count("io.pwrite.calls", 5)
    first = c.commit_record()
    assert first == {"io.pwrite.calls": 5}
    assert c.commit_record() == {}  # nothing new since
    c.metrics.count("io.pwrite.calls", 2)
    c.metrics.count("io.fsync.calls")
    assert c.commit_record() == {"io.pwrite.calls": 2,
                                 "io.fsync.calls": 1}


# ----------------------------------------------------------- activation ----

def test_quiet_by_default_and_env_activation(tmp_path, monkeypatch):
    assert trace.collector() is None
    monkeypatch.setenv(trace.TRACE_ENV, "mem")
    c = trace.collector()
    assert c is not None and c.path is None
    assert trace.collector() is c  # installed, not re-created
    trace.uninstall()
    target = str(tmp_path / "t.json")
    monkeypatch.setenv(trace.TRACE_ENV, target)
    c = trace.collector()
    assert c is not None and c.path == target
    c.event("hello", "ckpt")
    assert trace.flush() == target
    assert trace.load_chrome(target)


def test_quiet_path_is_cheap():
    # The disabled guard is one global load + one environ lookup; a
    # generous absolute bound catches an accidental allocation or I/O
    # on the quiet path without being timing-flaky.
    from repro.train import loop
    assert trace.collector() is None
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        trace.collector()
    per_call_us = (time.perf_counter() - t0) * 1e6 / n
    assert per_call_us < 25.0
    # The train loop's per-step calls on the quiet path: one collector
    # lookup, the hook calls, and the compile listener's bail-out.
    hooks = {"on_step": lambda step, state, metrics: None,
             "should_die": lambda step: False}
    t0 = time.perf_counter()
    for step in range(n):
        tc = trace.collector()
        loop._hook(hooks, "on_step", tc, step, None, None)
        loop._hook(hooks, "should_die", tc, step)
        loop._hook(hooks, "on_start", tc, step, None)
        loop._record_compile(loop.COMPILE_EVENTS + "x", 1e-3)
    per_step_us = (time.perf_counter() - t0) * 1e6 / n
    assert per_step_us < 25.0
    assert trace.collector() is None


def test_scoped_installs_and_restores(tmp_path):
    outer = trace.install(trace.TraceCollector())
    inner = trace.TraceCollector()
    with trace.scoped(inner) as got:
        assert got is inner
        assert trace.collector() is inner
    assert trace.collector() is outer
    # a path scope exports on exit
    target = str(tmp_path / "scoped.json")
    with trace.scoped(target) as c:
        c.event("x", "ckpt")
    assert os.path.exists(target)


# ------------------------------------------------------- chrome schema ----

def _spans_nest(events):
    """Complete events on one tid must nest (contain or be disjoint)."""
    by_tid = {}
    for ev in events:
        if ev["ph"] == "X":
            by_tid.setdefault(ev["tid"], []).append(
                (ev["ts"], ev["ts"] + ev.get("dur", 0.0)))
    for spans in by_tid.values():
        spans.sort()
        for i, (s0, e0) in enumerate(spans):
            for s1, e1 in spans[i + 1:]:
                if s1 >= e0:
                    break  # disjoint, and sorted: all later ones too
                assert e1 <= e0 + 1e-6, \
                    f"partial overlap: [{s0},{e0}] vs [{s1},{e1}]"


def test_traced_sharded_parity_save_restore_chrome_trace(tmp_path):
    """The acceptance path: a traced sharded+parity save/restore yields
    a loadable Chrome trace with pid/tid/ts/dur spans that nest, real
    io events, and a non-empty per-stage summary."""
    path = str(tmp_path / "ck.scda")
    tree = _tree()
    target = str(tmp_path / "trace.json")
    tc = trace.install(trace.TraceCollector(path=target))
    try:
        pytree_io.save(path, tree, step=9, shards=2, parity=1,
                       compressed=True)
        out, step = pytree_io.restore(path)
    finally:
        trace.uninstall()
    assert step == 9
    _assert_tree_equal(out, tree)
    tc.export()
    events = trace.load_chrome(target)
    assert events
    cats = set()
    for ev in events:
        assert set(ev) >= {"name", "cat", "ph", "pid", "tid", "ts"}
        if ev["ph"] == "X":
            assert "dur" in ev and ev["dur"] >= 0
        cats.add(ev["cat"])
    assert {"io", "ckpt"} <= cats
    _spans_nest(events)
    names = {ev["name"] for ev in events}
    assert {"save", "restore", "parity_encode",
            "shard_placement"} <= names
    assert any(ev["cat"] == "io" and ev["name"] in ("pwrite", "pwritev")
               for ev in events)
    summary = trace.summarize_chrome(events)
    assert summary["wall_us"] > 0
    assert summary["io_calls"] > 0 and summary["io_bytes"] > 0
    assert any(k.startswith("ckpt.save") for k in summary["stages"])
    lines = list(trace.format_summary(summary))
    assert lines and lines[0].startswith("wall ")


# ----------------------------------------------------------- byte parity ----

@pytest.mark.parametrize("P", [1, 2, 4, 8])
def test_traced_saves_byte_identical(tmp_path, P):
    """Tracing must never perturb bytes: traced saves are byte-identical
    to untraced ones — raw, compressed (serial: compressed parallel
    saves need chunk-aligned partitions), and sharded+parity."""
    configs = [dict(shards=0, parity=0, compressed=False),
               dict(shards=3, parity=1, compressed=False)]
    if P == 1:
        configs.append(dict(shards=0, parity=0, compressed=True))
        configs.append(dict(shards=2, parity=1, compressed=True))
    for i, cfg in enumerate(configs):
        tree = _tree(seed=100 + i)

        def run(tag, traced):
            d = tmp_path / f"{tag}{i}"
            os.makedirs(d)
            path = str(d / "ck.scda")

            def workload(comm):
                pytree_io.save(path, tree, comm=comm, step=i, **cfg)
            tc = trace.install(trace.TraceCollector()) if traced else None
            try:
                if P == 1:
                    pytree_io.save(path, tree, step=i, **cfg)
                else:
                    run_ranks(ThreadComm.group(P), workload)
                out, _ = pytree_io.restore(path)
            finally:
                if traced:
                    trace.uninstall()
            _assert_tree_equal(out, tree)
            if traced:
                assert tc.metrics.get("io.pwrite.calls") \
                    + tc.metrics.get("io.pwritev.calls") > 0
            return {n: (d / n).read_bytes()
                    for n in sorted(os.listdir(d))
                    if not n.endswith(".scdax")}
        assert run("plain", False) == run("traced", True), \
            f"P={P} cfg={cfg}: tracing changed bytes"


# ------------------------------------------------------------- warn() ----

def test_warn_logs_and_rate_limits(caplog):
    c = trace.install(trace.TraceCollector())
    with caplog.at_level("WARNING", logger="repro.scda"):
        assert trace.warn("shard s0 lost", key="k1")
        assert not trace.warn("shard s0 lost", key="k1")  # suppressed
        assert trace.warn("other problem", key="k2")
        assert trace.warn("always", interval=0)
        assert trace.warn("always", interval=0)
    assert caplog.text.count("shard s0 lost") == 1
    assert "other problem" in caplog.text
    snap = c.metrics.snapshot()["counters"]
    assert snap["warn.emitted"] == 4
    assert snap["warn.suppressed"] == 1
    trace.reset_warn_limits()
    with caplog.at_level("WARNING", logger="repro.scda"):
        assert trace.warn("shard s0 lost", key="k1")  # limit forgotten


def test_degraded_read_warns_once_per_set(tmp_path, caplog):
    path = str(tmp_path / "ck.scda")
    tree = _tree(seed=7)
    pytree_io.save(path, tree, step=1, shards=2, parity=1)
    os.remove(sharding.shard_file(path, 1, 2))
    with caplog.at_level("WARNING", logger="repro.scda"):
        out, _ = pytree_io.restore(path)
    _assert_tree_equal(out, tree)
    assert "DEGRADED READ" in caplog.text


# ----------------------------------------------- journal metrics sink ----

def test_manager_journals_commit_record(tmp_path):
    d = str(tmp_path / "ck")
    tc = trace.install(trace.TraceCollector())
    try:
        with CheckpointManager(d, keep=3, shards=0) as mgr:
            mgr.save(1, _tree(), blocking=True)
            mgr.save(2, _tree(seed=1), blocking=True)
    finally:
        trace.uninstall()
    newest = os.path.join(d, "step_0000000002.scda")
    recs = [rec for _, rec in iter_records(newest)]
    traced = [r for r in recs if any(k.startswith("trace/")
                                     for k in r["data"])]
    assert traced, f"no trace record in journal: {recs}"
    data = traced[-1]["data"]
    assert any(k.startswith("trace/io.") for k in data)
    assert all(isinstance(v, int) for v in data.values())


# ---------------------------------------------------- error op-context ----

def test_writeback_error_carries_op_context(tmp_path, monkeypatch):
    b = FileBackend(str(tmp_path / "w.bin"), "w", create=True)

    def boom(fd, bufs, off):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(os, "pwritev", boom)
    b.submit_write_gather([(0, b"z" * 100)], window=WW)
    monkeypatch.undo()
    with pytest.raises(ScdaError) as ei:
        b.drain_writes()
    err = ei.value
    assert err.code == ScdaErrorCode.FS_WRITE
    assert err.stage == "writeback"
    assert err.op_context["offset"] == 0
    assert err.op_context["bytes"] == 100
    assert err.op_context["path"].endswith("w.bin")
    b.close()


# -------------------------------------------------- CLI: stats/--timing ----

def test_cli_stats_table_and_json(tmp_path, capsys):
    path = str(tmp_path / "a.scda")
    pytree_io.save(path, _tree(), step=1, compressed=True)
    assert cli.main(["stats", path]) == 0
    out = capsys.readouterr().out
    assert "sections" in out and "ratio" in out
    assert cli.main(["stats", "--json", path]) == 0
    doc = json.loads(capsys.readouterr().out)
    f = doc["files"][0]
    assert f["stored_bytes"] > 0
    assert f["logical_bytes"] >= f["stored_bytes"]  # §3 compresses
    kinds = {row["kind"] for row in f["sections"]}
    assert any(k.startswith("z") for k in kinds)


def test_cli_stats_expands_sharded_set(tmp_path, capsys):
    path = str(tmp_path / "ck.scda")
    pytree_io.save(path, _tree(), step=1, shards=2)
    assert cli.main(["stats", "--json", path]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["files"]) == 3  # manifest + 2 shards


def test_cli_stats_trace_summary(tmp_path, capsys):
    path = str(tmp_path / "ck.scda")
    target = str(tmp_path / "trace.json")
    with trace.scoped(target):
        pytree_io.save(path, _tree(), step=1, shards=2, parity=1)
    assert cli.main(["stats", "--trace", target]) == 0
    out = capsys.readouterr().out
    assert "wall " in out and "io." in out
    assert cli.main(["stats", "--trace", target, "--json", path]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["trace"]["io_calls"] > 0
    assert doc["trace"]["stages"]
    # no args at all is a usage error
    assert cli.main(["stats"]) == 2


def test_cli_verify_and_fsck_timing(tmp_path, capsys):
    path = str(tmp_path / "a.scda")
    pytree_io.save(path, _tree(), step=1)
    assert cli.main(["index", "--checksums", path]) == 0
    capsys.readouterr()
    assert cli.main(["verify", "--timing", path]) == 0
    out = capsys.readouterr().out
    assert "verified" in out
    assert "# verify timing:" in out and "bytes scanned" in out
    assert cli.main(["fsck", "--timing", path]) == 0
    out = capsys.readouterr().out
    assert "clean" in out and "# fsck timing:" in out


# ----------------------------------------------------- save(trace=...) ----

def test_save_trace_kwarg_exports(tmp_path):
    path = str(tmp_path / "ck.scda")
    target = str(tmp_path / "save-trace.json")
    pytree_io.save(path, _tree(), step=4, trace=target)
    assert trace.collector() is None  # scope restored
    events = trace.load_chrome(target)
    assert any(ev["name"] == "save" and ev["cat"] == "ckpt"
               for ev in events)
    tc = trace.TraceCollector()
    pytree_io.save(path, _tree(), step=5, trace=tc)
    assert tc.metrics.get("ckpt.save.calls") == 1


# ------------------------------------------------ one clock with jax ----

def _xplane(d):
    import glob
    return glob.glob(os.path.join(str(d), "**", "*.xplane.pb"),
                     recursive=True)[0]


def test_spans_land_on_the_profiler_clock(tmp_path, monkeypatch):
    """A program span and a TraceAnnotation around the same region map
    onto each other within 1 ms through the collector's clock anchors,
    on a real (CPU) profiler trace."""
    import jax
    monkeypatch.setattr(trace, "ANCHOR_EVERY_NS", 20_000_000)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tc = trace.install(trace.TraceCollector())
        for i in range(4):
            with jax.profiler.TraceAnnotation(f"region{i}"):
                with tc.span(f"region{i}", "test"):
                    time.sleep(0.03)
    finally:
        trace.uninstall()
        jax.profiler.stop_trace()
    path = _xplane(tmp_path)
    anchors = trace.clock_anchors(path)
    assert len(anchors) >= 3  # install, then from end() as time passes
    assert all(a[0] < b[0] and a[1] < b[1]
               for a, b in zip(anchors, anchors[1:]))
    from jax.profiler import ProfileData
    marks = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events
             if e.name.startswith("region")}
    # The Chrome document's route: ts (µs) from the collector's epoch.
    doc = tc.chrome()
    epoch = doc["otherData"]["epoch_ns"]
    spans = {ev["name"]: (epoch + ev["ts"] * 1000,
                          epoch + (ev["ts"] + ev["dur"]) * 1000)
             for ev in doc["traceEvents"] if ev["cat"] == "test"}
    assert set(spans) == set(marks) == {f"region{i}" for i in range(4)}
    for name, (t0, t1) in spans.items():
        a, b = marks[name]
        assert abs(trace.to_trace_clock(t0, anchors) - a) < 1e6
        assert abs(trace.to_trace_clock(t1, anchors) - b) < 1e6


def test_clock_mapping_interpolates_between_anchors():
    anchors = [(1000, 10.0), (2000, 1010.5), (4000, 3010.5)]
    assert trace.to_trace_clock(1000, anchors) == 10.0
    assert trace.to_trace_clock(1500, anchors) == 510.25
    assert trace.to_trace_clock(3000, anchors) == 2010.5
    # Outside the anchors: the nearest anchor's offset.
    assert trace.to_trace_clock(500, anchors) == -490.0
    assert trace.to_trace_clock(5000, anchors) == 4010.5
    back = [(t, p) for p, t in anchors]
    for ts in (700, 1000, 1234, 2500, 4000, 4500):
        assert trace.to_trace_clock(
            trace.to_trace_clock(ts, anchors), back) == pytest.approx(ts)
    with pytest.raises(ValueError):
        trace.to_trace_clock(1, [])


def test_no_anchor_without_jax_imported(tmp_path, monkeypatch):
    """``repro.core.trace`` never imports JAX, and with JAX absent from
    ``sys.modules`` an active collector emits no anchor."""
    import subprocess
    import sys
    import jax
    code = ("import sys; from repro.core import trace; "
            "c = trace.install(); c.end('s', 'test', c.now()); "
            "c.io_op('pwrite', 'f', 0, 1, c.now()); trace.uninstall(); "
            "assert 'jax' not in sys.modules, 'core imported jax'")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
    monkeypatch.setattr(trace, "ANCHOR_EVERY_NS", 0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with monkeypatch.context() as m:
            m.delitem(sys.modules, "jax")
            tc = trace.install(trace.TraceCollector())
            for _ in range(3):
                with tc.span("s", "test"):
                    pass
            trace.uninstall()
    finally:
        jax.profiler.stop_trace()
    assert trace.clock_anchors(_xplane(tmp_path)) == []


# --------------------------------------------- spans of the train loop ----

def test_train_loop_spans(tmp_path):
    """Under a collector, ``train()`` records one batch/step/loss_read
    span per step and one hook span per hook call, spans nest per
    thread, and JAX's compile stages fall in the first step only."""
    from repro.configs.base import ModelConfig
    from repro.train.loop import TrainLoopConfig, train
    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=32,
                      vocab=64, n_heads=2, n_kv_heads=1, head_dim=16,
                      d_ff=64, mlp_type="swiglu")
    loop_cfg = TrainLoopConfig(total_steps=4, ckpt_every=2,
                               ckpt_dir=str(tmp_path / "ck"), ckpt_keep=1,
                               log_every=1 << 30)
    hooks = {"on_start": lambda s, st: None,
             "on_step": lambda s, st, m: None,
             "should_die": lambda s: False}
    tc = trace.TraceCollector()
    with trace.scoped(tc):
        out = train(cfg, loop_cfg, seq_len=8, global_batch=2, hooks=hooks)
    assert len(out["losses"]) == 4
    events = tc.chrome()["traceEvents"]
    _spans_nest(events)
    loop_spans = [e for e in events if e["ph"] == "X" and e["cat"] == "train"]
    for name in ("batch", "step", "loss_read"):
        got = [e["args"]["step"] for e in loop_spans if e["name"] == name]
        assert got == [0, 1, 2, 3], name
    hooks_seen = [e["args"]["hook"] for e in loop_spans if e["name"] == "hook"]
    assert hooks_seen == ["on_start"] + ["on_step", "should_die"] * 4
    steps = sorted((e["ts"], e["ts"] + e["dur"], e["args"]["step"])
                   for e in loop_spans if e["name"] == "step")
    compiles = [e for e in loop_spans if e["name"] == "compile"]
    assert all(e["args"]["event"].startswith("/jax/core/compile/")
               for e in compiles)
    inside = {s for a, b, s in steps for e in compiles
              if a <= e["ts"] and e["ts"] + e["dur"] <= b}
    assert inside == {0}
    main = {e["tid"] for e in loop_spans if e["name"] == "step"}
    assert len(main) == 1
    assert {e["tid"] for e in compiles} <= main


def test_save_stall_holds_wait_and_snapshot(tmp_path):
    """``save`` records ``ckpt.save_wait`` and ``ckpt.snapshot`` inside
    ``ckpt.save_stall``; the snapshot's bytes are the tree's."""
    tree = _tree()
    tc = trace.TraceCollector()
    with trace.scoped(tc):
        with CheckpointManager(str(tmp_path / "ck"), keep=2) as mgr:
            mgr.save(1, tree)
            mgr.save(2, _tree(seed=1))
            mgr.wait()
    events = tc.chrome()["traceEvents"]
    _spans_nest(events)
    ck = [e for e in events if e["ph"] == "X" and e["cat"] == "ckpt"]
    stalls = [e for e in ck if e["name"] == "save_stall"]
    assert [e["args"]["step"] for e in stalls] == [1, 2]
    for stall in stalls:
        a, b = stall["ts"], stall["ts"] + stall["dur"]
        tid = stall["tid"]
        parts = [e["name"] for e in ck if e["tid"] == tid
                 and e["name"] in ("save_wait", "snapshot")
                 and a <= e["ts"] and e["ts"] + e["dur"] <= b
                 and e["args"]["step"] == stall["args"]["step"]]
        assert parts == ["save_wait", "snapshot"]
    nbytes = sum(v.nbytes for v in tree.values())
    snaps = [e["args"]["bytes"] for e in ck if e["name"] == "snapshot"]
    assert snaps[0] == nbytes
    assert tc.metrics.get("ckpt.snapshot.bytes") == sum(snaps)
    assert tc.metrics.get("ckpt.save_wait.calls") == 2

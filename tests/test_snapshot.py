"""The save snapshot, shard by shard (``repro.checkpoint.snapshot``).

The four-device cases run once in a child process
(``helpers/snapshot_x4.py``: the device count is fixed when JAX starts);
each test below asserts one part of its findings.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import pytree_io
from repro.checkpoint.manager import CheckpointManager
from repro.checkpoint.snapshot import HostShards, snapshot_to_host
from repro.core import trace

HELPER = os.path.join(os.path.dirname(__file__), "helpers", "snapshot_x4.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def x4(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(SRC))
    out = subprocess.run(
        [sys.executable, HELPER, str(tmp_path_factory.mktemp("x4"))],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["devices"] == 4
    return r


@pytest.mark.parametrize("layout", ["raw", "compressed", "hashes"])
def test_sharded_save_is_a_serial_save(x4, layout):
    """Row-, column-, 2-D-sharded and replicated leaves, f32 and bf16:
    the committed file is byte-identical to a serial save of the
    gathered tree.  The raw save writes the shard buffers as they are;
    the compressed and hashed saves need whole leaves and gather them,
    in the background, never in the stall."""
    r = x4["saves"][layout]
    assert r["identical"]
    assert r["shards"] == x4["stall"]["distinct"] == 19
    if layout == "raw":
        assert r["gathered_bytes"] == 0
    else:
        assert r["gathered_bytes"] > 0


def test_the_stall_gathers_nothing(x4):
    s = x4["stall"]
    assert s["shards"] == s["distinct"]
    assert s["gathered_bytes"] == 0
    assert s["kinds"] == ["HostShards", "ndarray"]


@pytest.mark.parametrize("leaf", ["row", "col", "grid",
                                  "row_bf16", "col_bf16", "grid_bf16"])
def test_host_shards_match_the_device_array(x4, leaf):
    r = x4["leaves"][leaf]
    assert r["array_equal"]
    assert r["windows_equal"]


def test_short_runs_are_gathered_off_the_stall(x4):
    """Rows of 4 KiB per shard are gathered on the background thread
    (the backend would join runs that short anyway), bytes unchanged."""
    r = x4["short_runs"]
    assert r["identical"]
    assert r["gathered_bytes"] == r["bytes"]


def test_sharded_set_of_host_shards_restores(x4):
    assert x4["sharded_set"]


def test_donation_cannot_reach_the_snapshot_x4(x4):
    assert x4["donation"]


def test_donation_cannot_reach_the_snapshot(tmp_path):
    state = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64) / 7,
             "b": jnp.ones((64,), jnp.bfloat16),
             "count": jnp.int32(5)}
    want = jax.tree_util.tree_map(np.array, state)
    update = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a + 1, t),
                     donate_argnums=0)
    with CheckpointManager(str(tmp_path), keep=1) as m:
        m.save(3, state)
        state = update(state)
        jax.block_until_ready(state)
        m.wait()
        got, _ = pytree_io.restore(m.path_for(3))
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_one_device_leaves_come_back_as_numpy():
    state = {"w": jnp.ones((8, 8), jnp.float32), "n": 3,
             "h": np.zeros(4, np.int8)}
    c = trace.install(trace.TraceCollector())
    try:
        host = snapshot_to_host(state)
    finally:
        trace.uninstall()
    assert type(host["w"]) is np.ndarray
    np.testing.assert_array_equal(host["w"], np.ones((8, 8), np.float32))
    assert host["n"] == 3 and host["h"] is state["h"]
    counters = c.metrics.snapshot()["counters"]
    assert counters["ckpt.snapshot.shards"] == 1
    assert "ckpt.snapshot.gathered_bytes" not in counters


def test_host_shards_gather_and_refuse_a_partial_leaf():
    full = np.arange(24, dtype=np.float32).reshape(4, 6)
    parts = [((slice(0, 4), slice(0, 3)), full[:, :3].copy()),
             ((slice(0, 4), slice(3, 6)), full[:, 3:].copy())]
    h = HostShards(full.shape, full.dtype, parts)
    assert h.complete and h.nbytes == full.nbytes and h.ndim == 2
    np.testing.assert_array_equal(np.asarray(h), full)
    part = HostShards(full.shape, full.dtype, parts[:1])
    assert not part.complete
    with pytest.raises(ValueError):
        np.asarray(part)
    # A partial leaf still gives this host's windows: its rows.
    wins = pytree_io._owned_windows(part, part.nbytes)
    assert [g for g, _ in wins] == [0, 24, 48, 72]


@pytest.mark.parametrize("budget, order", [
    (0, ["issue a", "collect a", "issue b", "collect b", "issue c",
         "collect c"]),
    (1 << 40, ["issue a", "issue b", "issue c", "collect a", "collect b",
               "collect c"])])
def test_copies_in_flight_are_bounded(monkeypatch, budget, order):
    from repro.checkpoint import snapshot
    state = {k: jnp.full((256,), i, jnp.float32)
             for i, k in enumerate("abc")}
    names = {id(x): k for k, x in state.items()}
    log = []
    real_issue, real_collect = snapshot._issue, snapshot._collect

    def issue(shards):
        log.append("issue " + names[id(owner[id(shards[0])])])
        return real_issue(shards)

    def collect(x, shards):
        log.append("collect " + names[id(x)])
        return real_collect(x, shards)

    owner = {}
    real_distinct = snapshot._distinct_shards

    def distinct(x):
        shards = real_distinct(x)
        owner[id(shards[0])] = x
        return shards

    monkeypatch.setattr(snapshot, "IN_FLIGHT_BYTES", budget)
    monkeypatch.setattr(snapshot, "_issue", issue)
    monkeypatch.setattr(snapshot, "_collect", collect)
    monkeypatch.setattr(snapshot, "_distinct_shards", distinct)
    host = snapshot.snapshot_to_host(state)
    assert log == order
    for i, k in enumerate("abc"):
        np.testing.assert_array_equal(host[k], np.full((256,), i))

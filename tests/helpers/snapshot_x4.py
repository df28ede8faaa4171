"""Subprocess helper: the shard-by-shard save snapshot on 4 devices.

Run with the device count set before JAX starts (this file sets
``--xla_force_host_platform_device_count=4``).  Saves a (2, 2)-sharded
state through ``CheckpointManager`` and prints one JSON line of findings
for ``tests/test_snapshot.py`` to assert on:

* ``saves``: for a raw, a compressed and a hashed save, whether the
  committed file is byte-identical to a serial save of the gathered
  numpy tree, and the snapshot counters of that save;
* ``stall``: the counters of ``snapshot_to_host`` alone;
* ``leaves``: per sharded leaf, whether ``np.asarray`` of its host shards
  equals the device array and whether its windows equal those of the
  ``jax.Array`` it came from, window for window;
* ``short_runs``: a leaf whose rows split into runs of at most
  ``JOIN_SMALL`` bytes: its bytes, its gathered bytes, byte identity;
* ``sharded_set``: whether a sharded set with parity (``shards=2,
  parity=1``) of the state restores to its values;
* ``donation``: whether the committed bytes are the pre-update state's
  after a donating update ran right behind ``save()``.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint import pytree_io  # noqa: E402
from repro.checkpoint.manager import CheckpointManager  # noqa: E402
from repro.checkpoint.snapshot import HostShards, snapshot_to_host  # noqa: E402
from repro.core import trace  # noqa: E402

#: name -> (shape, dtype, spec); every sharded leaf's runs are 16 KiB,
#: longer than JOIN_SMALL, so the raw save writes them as windows.
LEAVES = {
    "row": ((16, 4096), jnp.float32, P("data", None)),
    "col": ((8, 8192), jnp.float32, P(None, "model")),
    "grid": ((16, 8192), jnp.float32, P("data", "model")),
    "rep": ((32, 32), jnp.float32, P()),
    "row_bf16": ((16, 8192), jnp.bfloat16, P("data", None)),
    "col_bf16": ((8, 16384), jnp.bfloat16, P(None, "model")),
    "grid_bf16": ((16, 16384), jnp.bfloat16, P("data", "model")),
    "rep_bf16": ((32, 32), jnp.bfloat16, P()),
}


def make_state(mesh, leaves):
    state = {}
    for i, (name, (shape, dtype, spec)) in enumerate(sorted(leaves.items())):
        x = jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
        state[name] = jax.device_put(x.astype(dtype), NamedSharding(mesh, spec))
    state["count"] = jax.device_put(jnp.int32(5), NamedSharding(mesh, P()))
    return state


def distinct_shards(state):
    return sum(sum(s.replica_id == 0 for s in x.addressable_shards)
               for x in jax.tree_util.tree_leaves(state))


def gathered(state):
    return jax.tree_util.tree_map(lambda x: np.array(x), state)


def read(path):
    with open(path, "rb") as f:
        return f.read()


def manager_save(d, state, traced, **kw):
    c = trace.install(trace.TraceCollector()) if traced else None
    try:
        with CheckpointManager(d, keep=1, index_sidecar=False, **kw) as m:
            m.save(3, state, blocking=True)
            path = m.path_for(3)
    finally:
        if traced:
            trace.uninstall()
    counters = c.metrics.snapshot()["counters"] if traced else {}
    return path, {k: counters.get(f"ckpt.snapshot.{k}", 0)
                  for k in ("shards", "gathered_bytes")}


def save_layouts(tmp, mesh):
    state = make_state(mesh, LEAVES)
    host = gathered(state)
    out = {}
    for layout, kw, skw in [("raw", {}, {}),
                            ("compressed", {"compressed": True},
                             {"compressed": True}),
                            ("hashes", {"delta": True},
                             {"record_hashes": True})]:
        d = os.path.join(tmp, layout)
        path, _ = manager_save(d, state, False, **kw)
        _, counters = manager_save(d + "_traced", state, True, **kw)
        serial = os.path.join(tmp, layout + "_serial.scda")
        pytree_io.save(serial, host, step=3, write_window=0, **skw)
        out[layout] = {"identical": read(path) == read(serial), **counters}
    return state, out


def stall_counters(state):
    c = trace.install(trace.TraceCollector())
    try:
        host = snapshot_to_host(state)
    finally:
        trace.uninstall()
    counters = c.metrics.snapshot()["counters"]
    kinds = sorted({type(v).__name__ for v in jax.tree_util.tree_leaves(host)})
    return host, {"shards": counters.get("ckpt.snapshot.shards", 0),
                  "gathered_bytes":
                      counters.get("ckpt.snapshot.gathered_bytes", 0),
                  "distinct": distinct_shards(state), "kinds": kinds}


def windows(x):
    return [(g, bytes(b)) for g, b in pytree_io._owned_windows(x, x.nbytes)]


def leaf_checks(state, host):
    out = {}
    for name, x in state.items():
        h = host[name]
        if not isinstance(h, HostShards):
            continue
        out[name] = {"array_equal": bool(np.array_equal(
                         np.asarray(h), np.asarray(x))),
                     "windows_equal": windows(h) == windows(x),
                     "windows": len(windows(h))}
    return out


def short_runs(tmp, mesh):
    # 4 KiB rows per shard, as a column-sharded embedding's on 4 chips.
    state = make_state(mesh, {"emb": ((64, 2048), jnp.float32,
                                      P("model", "data"))})
    path, _ = manager_save(os.path.join(tmp, "short"), state, False)
    _, counters = manager_save(os.path.join(tmp, "short_traced"), state,
                               True)
    serial = os.path.join(tmp, "short_serial.scda")
    pytree_io.save(serial, gathered(state), step=3, write_window=0)
    return {"identical": read(path) == read(serial),
            "bytes": int(state["emb"].nbytes), **counters}


def sharded_set(tmp, state):
    want = gathered(state)
    with CheckpointManager(os.path.join(tmp, "set"), keep=1, shards=2,
                           parity=1) as m:
        m.save(3, state, blocking=True)
        got, _ = m.restore(3)
    return all(np.array_equal(np.asarray(got[k]), want[k]) for k in want)


def donation(tmp, mesh):
    state = make_state(mesh, LEAVES)
    want = gathered(state)
    update = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a + 1, t),
                     donate_argnums=0)
    with CheckpointManager(os.path.join(tmp, "donate"), keep=1) as m:
        m.save(3, state)
        state = update(state)
        jax.block_until_ready(state)
        m.wait()
        got, _ = pytree_io.restore(m.path_for(3))
    return all(np.array_equal(np.asarray(got[k]), want[k]) for k in want)


def main(tmp):
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    state, saves = save_layouts(tmp, mesh)
    host, stall = stall_counters(state)
    print(json.dumps({"devices": len(jax.devices()), "saves": saves,
                      "stall": stall, "leaves": leaf_checks(state, host),
                      "short_runs": short_runs(tmp, mesh),
                      "sharded_set": sharded_set(tmp, state),
                      "donation": donation(tmp, mesh)}))


if __name__ == "__main__":
    main(sys.argv[1])

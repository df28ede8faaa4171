"""Checkpoint/restart on the scda format — the paper's technique as a
first-class framework feature.

    from repro.checkpoint import CheckpointManager, save, restore

    mgr = CheckpointManager("/ckpts/run7", keep=3)
    state, start = mgr.restore_or_init(init_fn, like=abstract_state)
    for step in range(start + 1, total):
        state = train_step(state, batch)
        if step % 500 == 0:
            mgr.save(step, state)          # async, atomic, serial-equivalent
"""
from repro.checkpoint.delta import (verify_chain, squash, checkpoint_diff)
from repro.checkpoint.layout import (shard_runs, chunk_sizes,
                                     chunks_for_runs, runs_cover_exactly)
from repro.checkpoint.manifest import (MANIFEST_USER_STRING,
                                       STATUS_USER_STRING,
                                       SHARDS_FILE_USER_STRING, content_id)
from repro.checkpoint.sharding import (save_sharded, read_sharded_manifest,
                                       verify_set, assign_shards,
                                       shard_file, is_shard_name)
from repro.checkpoint.pytree_io import (save, restore, restore_leaf,
                                        read_manifest, flatten_named,
                                        leaf_name, DEFAULT_CHUNK_BYTES)
from repro.checkpoint.manager import CheckpointManager
from repro.checkpoint.snapshot import snapshot_to_host

__all__ = [
    "shard_runs", "chunk_sizes", "chunks_for_runs", "runs_cover_exactly",
    "MANIFEST_USER_STRING", "STATUS_USER_STRING", "SHARDS_FILE_USER_STRING",
    "content_id", "save", "restore", "restore_leaf", "read_manifest",
    "flatten_named", "leaf_name", "DEFAULT_CHUNK_BYTES", "CheckpointManager",
    "snapshot_to_host", "verify_chain", "squash", "checkpoint_diff",
    "save_sharded", "read_sharded_manifest", "verify_set", "assign_shards",
    "shard_file", "is_shard_name",
]

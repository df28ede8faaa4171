"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program for the duration of one tiny run,
which goes through the harness as a chip run does (only its look for a
chip is skipped): a step that returns its state unchanged; half of the
batch left out, the mean taken over the rest; an answer altered where it
is produced (a word of the saved state, or of the restored one).  The
exchange between chips does not exist in these one-chip cells."""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

TRAIN = "qwen3-1.7b-l4.train_ckpt"
RESUME = "qwen3-1.7b-l4.resume"


def _step_fault(monkeypatch, kind):
    import repro.train.loop as loop_mod
    real = loop_mod.make_train_step

    def make(cfg, opt, **kw):
        step = real(cfg, opt, **kw)

        def broken(params, opt_state, batch):
            if kind == "half_batch":
                B, S = batch["tokens"].shape
                cut = (slice(0, B // 2), slice(None)) if B >= 2 else \
                    (slice(None), slice(0, S // 2))
                return step(params, opt_state,
                            {k: v[cut] for k, v in batch.items()})
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return broken
    monkeypatch.setattr(loop_mod, "make_train_step", make)


@pytest.mark.parametrize("cell", [TRAIN, RESUME])
@pytest.mark.parametrize("kind", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, cell, kind):
    _step_fault(monkeypatch, kind)
    r = tiny.run(cell)
    assert r["correct"] is False
    over = [k for k, (v, lim) in r["checks"].items() if not v <= lim]
    assert over, r["checks"]


def test_altered_save_is_not_correct(monkeypatch):
    import repro.checkpoint.manager as mgr
    real = mgr.snapshot_to_host

    def altered(tree):
        host = real(tree)
        leaf = host["params"]["final_norm"]
        leaf.reshape(-1)[0] += np.float32(1e-3)
        return host
    monkeypatch.setattr(mgr, "snapshot_to_host", altered)
    r = tiny.run(TRAIN)
    assert r["correct"] is False
    assert r["checks"]["ckpt_mismatch"][0] >= 1


def test_altered_restore_is_not_correct(monkeypatch):
    import repro.checkpoint.pytree_io as pio
    real = pio.restore

    def altered(path, like=None, **kw):
        tree, step = real(path, like, **kw)
        if like is not None:
            tree["params"]["final_norm"] = \
                tree["params"]["final_norm"] + jnp.float32(1e-3)
        return tree, step
    monkeypatch.setattr(pio, "restore", altered)
    r = tiny.run(RESUME)
    assert r["correct"] is False
    assert r["checks"]["restore_mismatch"][0] >= 1

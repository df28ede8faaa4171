"""chip_smoke.py's phases at smoke size on the CPU: reference, die at step
3, resume — byte-equal final state and equal losses.  The TPU check lives
in ``chip_smoke.main``, which this file never calls."""
import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.launch.mesh import make_host_mesh

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def phases(chip_smoke, tmp_path_factory):
    mesh = make_host_mesh(1, 1)
    return chip_smoke.run_die_resume(
        smoke(get_config("qwen3-1.7b")), str(tmp_path_factory.mktemp("cs")),
        mesh, mesh, steps=6, ckpt_every=3, die_at=3, seq_len=32, batch=2)


def test_die_resume_matches_reference(chip_smoke, phases):
    ref, died, resumed = phases
    assert chip_smoke.check(ref, died, resumed, steps=6, die_at=3,
                            same_mesh=True) == []
    assert died.steps_on_disk == [3] and resumed.start_step == 3
    assert resumed.losses == ref.losses[4:]
    for a, b in zip(jax.tree_util.tree_leaves(resumed.state),
                    jax.tree_util.tree_leaves(ref.state)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_readings_come_from_trace_spans(chip_smoke, phases):
    ref, died, resumed = phases
    r = chip_smoke.readings(died, 3)
    assert r["save_stall_s@3"] > 0 and r["background_save_s@3"] > 0
    assert r["GB_written"] > 0
    assert chip_smoke.readings(resumed, 3)["restore_or_init_s"] > 0
    assert "median_step_s" in chip_smoke.readings(ref, 3)


def test_check_reports_a_changed_leaf(chip_smoke, phases):
    ref, died, resumed = phases
    bad = jax.tree_util.tree_map(np.copy, resumed.state)
    bad["params"]["final_norm"][0] += 1
    tampered = type(resumed)(**{**vars(resumed), "state": bad})
    fail = chip_smoke.check(ref, died, tampered, steps=6, die_at=3,
                            same_mesh=True)
    assert len(fail) == 1 and "final_norm" in fail[0]

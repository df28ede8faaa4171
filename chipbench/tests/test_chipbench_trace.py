"""trace_reduce on a small trace recorded on a TPU v5 lite by
``record_trace.py``: three runs of a jitted step, one host pause of
50 ms annotated as a save between the second and the third."""
from pathlib import Path

import pytest

from chipbench import trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE))


def test_executable_runs_and_time(reduced):
    assert reduced.devices == 1
    assert reduced.module_runs == {"jit_step": 3}
    # The three runs, as the trace's XLA Modules line gives them (ns).
    assert reduced.module_s["jit_step"] == pytest.approx(
        (47388 + 50491 + 50513) / 1e9, rel=1e-9)


def test_busy_is_the_union_of_operations(reduced):
    # Each run is four ~11.6 us fusions plus a copy; busy lies within the
    # runs, and the ops overlap only where async copies do.
    assert 0.9 * reduced.module_s["jit_step"] < reduced.busy_s \
        <= reduced.module_s["jit_step"]
    assert reduced.idle_share == pytest.approx(
        1 - reduced.busy_s / reduced.window_s)


def test_idle_is_labelled_by_the_host_annotation(reduced):
    name, seconds = reduced.idle_gaps[0]
    assert name == "chipbench.save"
    assert 0.050 <= seconds < 0.060
    assert reduced.window_s > 0.050
    assert sum(s for _, s in reduced.idle_gaps) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-6)


def test_top_ops_are_named_by_their_hlo_name(reduced):
    names = [n for n, _ in reduced.top_ops]
    assert {"%fusion", "%fusion.1", "%fusion.2", "%fusion.3"} <= set(names)
    assert all(" = " not in n for n in names)


def test_a_trace_without_a_tpu_reduces_to_nothing(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    assert trace_reduce.reduce(path) is None

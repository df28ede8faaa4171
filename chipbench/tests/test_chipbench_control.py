"""The comparison separates the program from its control: the reference
in fp8 (the precision below the configurations' bfloat16) and the
reference with half of the batch left out each fail a cell's limits, at a
size the CPU holds.  The reference draws the program's weights from the
seed without taking them from the program, bit for bit as the
program's jitted initialiser (the one train() runs) draws them, each
through the modules the configuration names."""
import jax
import numpy as np
import pytest

from chipbench import control, harness
import tiny

CELLS = [c["name"] for c in tiny.TinyBench().doc["workloads"]]


def _bench(cell):
    return tiny.TinyBench()


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_half_batch_are_caught(cell):
    """Half of the batch left out fails the cell's limits, by the
    control's own verdict against them.  The fp8
    control's gaps shrink with the sizes (on the chip, at the cell's
    widths, they read as PERF.md gives them): here it has to read at
    least three times what a sound run of the program reads, on one
    number at least."""
    bench = _bench(cell)
    limits = bench.limits(bench.cell(cell))
    readings = control.readings(bench, cell, 2**31 + 5)
    assert readings.pop("negligible_leaves") == []
    verdicts = {}
    for v, gaps in readings.items():
        verdicts[v] = gaps.pop("verdict")
        for k in ("loss_gap_per_step", "grad_gap_worst", "update_gap_worst"):
            gaps.pop(k)
    half = readings["half_batch"]
    over = sorted(k for k, v in half.items() if not v <= limits[k])
    assert over and verdicts["half_batch"] == {"correct": False,
                                               "over": over}, half
    sound = tiny.run(cell, seed=2**31 + 5,
                     **({"batch": 1, "seq_len": 64} if "granite" in cell
                        else {}))["checks"]
    fp8 = readings["fp8"]
    assert any(fp8[k] >= 3 * sound[k][0] for k in fp8 if k in sound), \
        (fp8, sound)


@pytest.mark.parametrize("name", ["qwen3-1.7b-l4", "granite-moe-3b-a800m-l4"])
def test_reference_weights_equal_the_programs(name):
    import json
    from repro.models import init_lm
    config = json.loads((tiny.ROOT / "chipbench" / "configs" /
                         f"{name}.json").read_text())
    config.update(tiny.TINY_SIZES)
    if config.get("num_local_experts"):
        config.update(num_local_experts=4, num_experts_per_tok=2)
    seed = 2**31 + 9
    bench = harness.Bench(tiny.ROOT)
    ref, program = bench.reference(config), bench.program(config)
    mine = ref.init_params(ref.Arch.from_config(config), seed)
    theirs = jax.jit(lambda: init_lm(program.model_config(config),
                                     jax.random.PRNGKey(seed)))()
    a = jax.tree_util.tree_leaves_with_path(mine)
    b = jax.tree_util.tree_leaves_with_path(theirs)
    assert [k for k, _ in a] == [k for k, _ in b]
    for (k, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=str(k))

"""FLOPs per token from shapes, against a count written out by hand.

``compiled.cost_analysis()`` is not the source: XLA counts the body of a
``lax.scan`` once, so the program's scanned layers and chunked loss are
undercounted, and it counts recomputation (remat) and MoE capacity
padding, which are not model work."""
import json

import pytest

from chipbench import flops
from tiny import ROOT


def _cfg(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def test_qwen3_l4_by_hand():
    # per layer: q 2048*16*128, k and v 2048*8*128 each, o 16*128*2048
    proj = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048        # 12,582,912
    attn = 2 * 16 * 128 * 4097 / 2                            # 8,390,656
    mlp = 3 * 2048 * 6144                                      # 37,748,736
    head = 2048 * 151936                                       # 311,164,928
    fwd = 2 * (4 * (proj + attn + mlp) + head)                 # 1.0928e9
    assert flops.forward_flops_per_token(_cfg("qwen3-1.7b-l4"), 4096) == \
        pytest.approx(fwd, rel=1e-12)
    assert flops.train_flops_per_token(_cfg("qwen3-1.7b-l4"), 4096) == \
        3 * fwd == 3_276_324_864


def test_granite_l4_by_hand():
    proj = 1536 * 64 * (24 + 2 * 8) + 24 * 64 * 1536           # 6,291,456
    attn = 2 * 24 * 64 * 4097 / 2                             # 6,292,992
    moe = 8 * 3 * 1536 * 512 + 1536 * 40                      # 18,935,808
    head = 1536 * 49155                                        # 75,502,080
    fwd = 2 * (4 * (proj + attn + moe) + head)
    assert flops.forward_flops_per_token(
        _cfg("granite-moe-3b-a800m-l4"), 4096) == pytest.approx(fwd, rel=1e-12)
    assert flops.train_flops_per_token(
        _cfg("granite-moe-3b-a800m-l4"), 4096) == 3 * fwd == 1_209_498_624

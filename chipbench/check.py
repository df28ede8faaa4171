"""The comparison that decides ``correct``.

Readings of the program and of the reference are reduced to a few
numbers, each held to its limit (``chipbench/limits/<cell>.json``):

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over the compared
  steps (and, in a resume cell, over the first step of every resume);
* ``grad_gap``: the first step's gradient as the optimizer got it, by the
  worst leaf: ``|norm - ref norm| / max(ref norm, median leaf's ref norm)``;
* ``update_gap``: the parameters' change over the compared steps, by the
  worst leaf, measured the same way, leaving out leaves whose reference
  gradient is under a thousandth of the median leaf's (their change is
  rounding: Adam normalises it to full size);
* ``ckpt_mismatch`` / ``restore_mismatch``: leaves whose checksums,
  read back from the checkpoint (or restored by a resume), differ from
  those of the device state that was saved (limit 0).
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: A leaf whose reference gradient is below this share of the median
#: leaf's moves under Adam by rounding alone: its change is not compared.
NEGLIGIBLE_GRAD = 1e-3


def leaf_gaps(got: Dict[str, float], ref: Dict[str, float],
              leave_out=()) -> Dict[str, float]:
    """Per leaf, ``|got - ref| / max(ref, median leaf's ref)``; a leaf
    that only one side has reads inf."""
    floor = statistics.median(ref.values())
    out = {k: abs(got[k] - ref[k]) / max(ref[k], floor, 1e-30)
           for k in ref if k in got and k not in leave_out}
    out.update({k: float("inf") for k in set(ref) ^ set(got)})
    return out


def _worst_leaf_gap(got, ref, leave_out=()) -> float:
    return max(leaf_gaps(got, ref, leave_out).values(), default=0.0)


def gaps(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """``got`` and ``ref`` hold ``losses`` (a list), ``grad_norms`` and
    ``update_norms`` (per-leaf dicts), as ``reference.train`` returns."""
    n = len(ref["losses"])
    if len(got["losses"]) != n:
        loss_gap = float("inf")
    else:
        loss_gap = max(abs(g - r) / abs(r)
                       for g, r in zip(got["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    floor = statistics.median(g_ref.values())
    negligible = {k for k, v in g_ref.items() if v < NEGLIGIBLE_GRAD * floor}
    grad = leaf_gaps(got["grad_norms"], g_ref)
    return {"loss_gap": loss_gap,
            "grad_gap": max(grad.values(), default=0.0),
            "update_gap": _worst_leaf_gap(got["update_norms"],
                                          ref["update_norms"], negligible)}


def details(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Readings behind the numbers (printed, not compared): the loss gap
    of each step, and the median and the three worst leaves of each
    per-leaf gap."""
    out: Dict[str, Any] = {"loss_gap_per_step": [
        abs(g - r) / abs(r) for g, r in zip(got["losses"], ref["losses"])]}
    for key in ("grad_norms", "update_norms"):
        lg = leaf_gaps(got[key], ref[key])
        worst = sorted(lg.items(), key=lambda kv: -kv[1])[:3]
        out[key.replace("norms", "gap")] = {
            "median": statistics.median(lg.values()) if lg else None,
            "worst": worst}
    return out


def loss_gap(losses: List[float], ref: float) -> float:
    return max((abs(x - ref) / abs(ref) for x in losses), default=0.0)


# ------------------------------------------------------------- exactness --

def _words(x):
    """The bits of a leaf as uint32 words (float32 / int32 leaves)."""
    if x.dtype.itemsize != 4:
        raise TypeError(f"checksums take 4-byte leaves, not {x.dtype}")
    return jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)


@jax.jit
def checksums(tree):
    """Per leaf, two uint32 sums of its words: plain, and weighted by
    position (so that moved words show too).  Equal trees give equal
    sums; a changed word changes them."""
    def leaf(x):
        u = _words(x)
        i = jnp.arange(u.size, dtype=jnp.uint32) * jnp.uint32(2) \
            + jnp.uint32(1)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * i, dtype=jnp.uint32)])
    return jax.tree_util.tree_map(leaf, tree)


def checksum_host(tree) -> Dict[str, tuple]:
    return {jax.tree_util.keystr(k): tuple(int(v) for v in np.asarray(c))
            for k, c in jax.tree_util.tree_leaves_with_path(tree)}


def count_mismatch(got: Dict[str, tuple], want: Dict[str, tuple]) -> int:
    keys = set(got) | set(want)
    return sum(got.get(k) != want.get(k) for k in keys)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Optional[Dict[str, List[float]]]:
    """``{name: [value, limit]}`` for every number, or None when a number
    has no limit."""
    if set(numbers) - set(limits):
        return None
    return {k: [v, limits[k]] for k, v in numbers.items()}


def passes(checks: Dict[str, List[float]]) -> bool:
    return all(v <= lim for v, lim in checks.values())

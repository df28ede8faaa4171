"""The ``ckpt.restore_or_init`` span of each resume in the window,
averaged: reading the checkpoint and placing it on the devices."""
from chipbench.metrics import spans


def read(m):
    rs = [s for s in spans.named(m, "restore_or_init")
          if spans.in_window(m, s)]
    if not rs or not m.records.resumes:
        return None
    return sum(s["t1"] - s["t0"] for s in rs) / len(rs)

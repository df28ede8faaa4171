"""The background part of each save in the window: from its
``ckpt.plan`` span's start to the end of the ``ckpt.retention`` span
that follows, averaged over saves."""
from chipbench.metrics import spans


def read(m):
    bg = [iv for iv in spans.background(m)
          if m.records.t_window is not None and iv[0] >= m.records.t_window]
    if not bg:
        return None
    return sum(b - a for a, b in bg) / len(bg)

"""The device-to-host snapshot of each save in the window: its
``ckpt.save_stall`` span less the part that the previous save's
background interval (``ckpt.plan`` start to ``ckpt.retention`` end)
covers, which is the wait for that save.  Averaged over saves."""
from chipbench.metrics import spans


def read(m):
    stalls = [s for s in spans.named(m, "save_stall") if spans.in_window(m, s)]
    if not stalls:
        return None
    bg = spans.background(m)
    out = []
    for s in stalls:
        prev = [iv for iv in bg if iv[0] < s["t0"]]
        cover = 0.0
        if prev:
            a, b = max(prev)
            cover = max(0.0, min(b, s["t1"]) - max(a, s["t0"]))
        out.append(s["t1"] - s["t0"] - cover)
    return sum(out) / len(out)

"""Device-idle seconds of the traced window while a save's background
part is open (``ckpt.plan`` start to ``ckpt.retention`` end) and no
``ckpt.save_stall`` is: what the write costs the steps beside it.
Summed over the window's fixed 90 steps; on the program's clock through
its clock anchors (``idle.py``)."""
from chipbench.metrics import idle, spans


def read(m):
    gaps = idle.intervals(m)
    if gaps is None:
        return None
    stalls = [(s["t0"], s["t1"]) for s in spans.named(m, "save_stall")]
    return idle.overlap(gaps, idle.minus(spans.background(m), stalls))

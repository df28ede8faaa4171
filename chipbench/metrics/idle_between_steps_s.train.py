"""Device-idle seconds of the traced window outside every save's stall
(``ckpt.save_stall``) and background part (``ckpt.plan`` start to
``ckpt.retention`` end): the loop's own gaps between steps.  Summed over
the window's fixed 90 steps; on the program's clock through its clock
anchors (``idle.py``)."""
from chipbench.metrics import idle, spans


def read(m):
    gaps = idle.intervals(m)
    if gaps is None:
        return None
    saves = [(s["t0"], s["t1"]) for s in spans.named(m, "save_stall")]
    return idle.total(gaps) - idle.overlap(gaps, saves + spans.background(m))

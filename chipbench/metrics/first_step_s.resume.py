"""From the end of each resume's ``ckpt.restore_or_init`` span to its
first loss read: the loop's retrace and compile-cache lookup, placement
finishing late, and the step.  Averaged over resumes."""
from chipbench.metrics import spans


def read(m):
    rs = spans.named(m, "restore_or_init")
    out = []
    for r in m.records.resumes:
        ends = [s["t1"] for s in rs if r.t_call <= s["t1"] <= r.t_first_loss]
        if ends:
            out.append(r.t_first_loss - max(ends))
    return sum(out) / len(out) if out else None

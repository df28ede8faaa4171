"""Per resume, the first ``train.step`` span after the ``train()`` call
less the ``train.compile`` time inside it: the transfer of the step's
arguments and the enqueue.  Averaged over resumes."""
from chipbench.metrics import resumes


def read(m):
    got = resumes.first_steps(m)
    if not got:
        return None
    return sum(s["t1"] - s["t0"] - c for s, c in got) / len(got)

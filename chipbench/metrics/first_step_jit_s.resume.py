"""Per resume, the seconds of ``train.compile`` spans (JAX's tracing,
lowering, and compile or compile-cache load) inside the first
``train.step`` span after the ``train()`` call.  Averaged over resumes."""
from chipbench.metrics import resumes


def read(m):
    got = resumes.first_steps(m)
    return sum(c for _, c in got) / len(got) if got else None

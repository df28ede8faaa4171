"""Model FLOP utilisation of the step program while it runs: the model
FLOPs of the step runs in the device trace (``flops.py``, from shapes)
over chips x bf16 peak x the summed device time of those runs.  Idle time
between steps is not in the denominator: ``device_idle_share.train``
reports it."""
STEP_MODULE = "jit_step"


def read(m):
    d = m.device
    if d is None:
        return None
    t, n = d.module_s.get(STEP_MODULE), d.module_runs.get(STEP_MODULE)
    if not t or not n:
        return None
    flops = n * m.tokens_per_step * m.flops_per_token
    return 100.0 * flops / (m.chips * m.peak["bf16_flops_per_s"] * t)

"""Helper of the resume readers: each resume's first step, from the
program's ``train`` spans of a traced run."""
from chipbench.metrics import idle


def first_steps(m):
    """Per resume, its first ``train.step`` span after the ``train()``
    call and the seconds of ``train.compile`` spans inside it (their
    union: JAX's stages nest where one program traces another)."""
    steps = [s for s in m.spans if s["cat"] == "train" and s["name"] == "step"]
    comp = [(s["t0"], s["t1"]) for s in m.spans
            if s["cat"] == "train" and s["name"] == "compile"]
    out = []
    for r in m.records.resumes:
        mine = [s for s in steps if r.t_call <= s["t0"] <= r.t_first_loss]
        if mine:
            s = min(mine, key=lambda x: x["t0"])
            out.append((s, idle.overlap(comp, [(s["t0"], s["t1"])])))
    return out

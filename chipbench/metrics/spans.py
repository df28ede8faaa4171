"""Helpers the metric readers share: the program's ``ckpt`` spans of a
traced run, on the host clock."""


def named(m, name):
    return [s for s in m.spans if s["cat"] == "ckpt" and s["name"] == name]


def in_window(m, s) -> bool:
    r = m.records
    return r.t_window is not None and s["t0"] >= r.t_window and \
        (r.t_close is None or s["t0"] <= r.t_close)


def background(m):
    """(start, end) of each save's background part: ``plan`` start to the
    end of the first ``retention`` span after it."""
    ret = sorted(s["t1"] for s in named(m, "retention"))
    out = []
    for p in named(m, "plan"):
        ends = [t for t in ret if t >= p["t0"]]
        if ends:
            out.append((p["t0"], ends[0]))
    return sorted(out)

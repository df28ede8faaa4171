"""The readers of the device's idle time by program span and of each
resume's first step: exact seconds on hand-built inputs, the mapping of
a trace onto the program's clock through its anchors, and nothing to
read from a program or trace without them."""
import types
from pathlib import Path

import pytest

from chipbench import drive, harness, trace_reduce
from chipbench.metrics import idle

ROOT = Path(__file__).resolve().parents[2]
TRACE = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"
P0 = 5_000_000_000_000   # a program timestamp (ns) at trace time 0


def _xspace(ops, marks, anchors):
    """A serialized XSpace: device ops and host annotations as
    ``(start_ns, end_ns)``, anchors as ``(trace_ns, program_ns)``."""
    from jax.profiler import ProfileData

    def events(ivs, meta, stat=None):
        out = []
        for i, (a, b) in enumerate(ivs):
            s = (f" stats {{ metadata_id: 1 int64_value: {stat[i]} }}"
                 if stat else "")
            out.append(f"events {{ metadata_id: {meta} offset_ps: {a * 1000}"
                       f" duration_ps: {(b - a) * 1000}{s} }}")
        return " ".join(out)
    txt = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events(ops, 1)} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "%fusion = f32[] add()" }} }}
    }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 0
        {events(marks, 1)}
        {events([(t, t + 1) for t, _ in anchors], 2,
                [p for _, p in anchors])} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "chipbench.train" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "scda.clock" }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "t_ns" }} }}
    }}"""
    return ProfileData.text_proto_to_serialized_xspace(txt)


OPS = [(1000, 3000), (2500, 4000), (6000, 7000), (10000, 11000)]
MARK = [(500, 11500)]
GAPS = [(500, 1000), (4000, 6000), (7000, 10000), (11000, 11500)]


@pytest.mark.parametrize("slope", [1, 2])
def test_idle_stretches_map_onto_the_program_clock(tmp_path, slope):
    d = tmp_path / "trace" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    anchors = [(0, P0), (20000, P0 + slope * 20000)]
    path.write_bytes(_xspace(OPS, MARK, anchors))
    dt = trace_reduce.reduce(str(path))
    m = types.SimpleNamespace(trace_dir=str(tmp_path / "trace"), device=dt)
    got = idle.intervals(m)
    want = [((P0 + slope * a) / 1e9, (P0 + slope * b) / 1e9) for a, b in GAPS]
    assert got == pytest.approx(want, rel=0, abs=1e-12)
    assert m.device_idle is got   # read once
    assert idle.total(got) == pytest.approx(
        slope * (dt.window_s - dt.busy_s), rel=1e-9)


def test_a_trace_without_anchors_gives_nothing(tmp_path):
    # The recorded v5e trace predates the anchors, as does a program
    # that emits none: every new reader reads nothing and raises nothing.
    assert idle.read(str(TRACE)) is None
    (tmp_path / "t").mkdir()
    (tmp_path / "t" / "v5e.xplane.pb").write_bytes(TRACE.read_bytes())
    rec = drive.Records(t_window=0.0, t_close=9.0)
    rec.resumes.append(drive.Resume(t_call=1.0, t_first_loss=2.0))
    m = types.SimpleNamespace(trace_dir=str(tmp_path / "t"), records=rec,
                              device=trace_reduce.reduce(str(TRACE)),
                              spans=[_span("ckpt", "save_stall", 1, 2)])
    for name in ("idle_beside_write_s.train", "idle_between_steps_s.train",
                 "first_step_jit_s.resume", "first_dispatch_s.resume"):
        assert harness.reader(name, ROOT).read(m) is None, name


def test_only_the_runs_own_trace_is_read(tmp_path):
    # The trace in the directory is read only when its window is the one
    # the run's reduced trace reports; a run with no device trace (none
    # on the CPU) reads no file at all.
    d = tmp_path / "trace"
    d.mkdir()
    (d / "host.xplane.pb").write_bytes(
        _xspace(OPS, MARK, [(0, P0), (20000, P0 + 20000)]))
    dt = trace_reduce.reduce(str(d / "host.xplane.pb"))
    other = trace_reduce.DeviceTrace(**dict(vars(dt), window_s=2 * dt.window_s))
    for device, found in ((dt, True), (other, False), (None, False)):
        m = types.SimpleNamespace(trace_dir=str(d), device=device)
        assert (idle.intervals(m) is not None) is found
    assert idle.trace_dir(types.SimpleNamespace()) == str(
        ROOT / harness.WORKDIR_NAME / "trace")


def _span(cat, name, t0, t1):
    return {"cat": cat, "name": name, "args": {}, "t0": t0, "t1": t1}


def _train_input():
    spans = [_span("ckpt", "save_stall", 10.0, 12.0),
             _span("ckpt", "plan", 12.1, 12.6),
             _span("ckpt", "retention", 17.5, 18.0)]
    gaps = [(9.5, 10.5), (11.0, 11.5), (11.9, 12.3), (15.0, 15.25),
            (17.9, 18.2), (20.0, 20.05)]
    return types.SimpleNamespace(spans=spans, device_idle=gaps,
                                 records=drive.Records(t_window=9.0))


def test_train_idle_readers_and_their_identity():
    m = _train_input()
    beside = harness.reader("idle_beside_write_s.train", ROOT).read(m)
    between = harness.reader("idle_between_steps_s.train", ROOT).read(m)
    assert beside == pytest.approx(0.2 + 0.25 + 0.1)
    assert between == pytest.approx(0.5 + 0.1 + 0.2 + 0.05)
    stall = idle.overlap(m.device_idle, [(10.0, 12.0)])
    assert stall == pytest.approx(0.5 + 0.5 + 0.1)
    assert stall + beside + between == pytest.approx(
        idle.total(m.device_idle), rel=1e-12)


def test_resume_first_step_readers():
    rec = drive.Records()
    rec.resumes += [drive.Resume(t_call=100.0, t_first_loss=105.0),
                    drive.Resume(t_call=200.0, t_first_loss=204.0),
                    drive.Resume(t_call=300.0)]   # failed: no loss read
    spans = [_span("train", "compile", 101.0, 101.5),   # before the step
             _span("train", "step", 102.0, 104.0),
             _span("train", "compile", 102.5, 103.0),
             _span("train", "compile", 102.6, 102.8),   # nested stage
             _span("train", "compile", 103.2, 103.5),
             _span("train", "step", 104.5, 104.6),      # not the first
             _span("train", "compile", 200.9, 201.2),
             _span("train", "step", 201.0, 201.5),
             _span("train", "step", 300.5, 301.0)]
    m = types.SimpleNamespace(spans=spans, records=rec)
    jit = harness.reader("first_step_jit_s.resume", ROOT).read(m)
    dispatch = harness.reader("first_dispatch_s.resume", ROOT).read(m)
    assert jit == pytest.approx((0.8 + 0.2) / 2)
    assert dispatch == pytest.approx((1.2 + 0.3) / 2)


def test_interval_arithmetic():
    assert idle.union([(3, 4), (1, 2), (1.5, 3)]) == [(1, 4)]
    assert idle.minus([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert idle.minus([(0, 1)], [(-1, 2)]) == []
    assert idle.overlap([(0, 4), (6, 8)], [(3, 7), (3.5, 6.5)]) == 2.0

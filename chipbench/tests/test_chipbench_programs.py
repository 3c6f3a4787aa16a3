"""The readers of the program's own instrumentation (chipbench/lib/
programs.py), on hand-built spans and modules, and on a recorded CPU
trace."""
import dataclasses
import threading
from types import SimpleNamespace

import pytest

from chipbench.lib import harness, programs, spans as spans_mod, trace as T
from repro.core.telemetry import HostSpanRecord

W = (1000.0, 2000.0)
MAIN = threading.get_ident()


def _rec(name, t0, t1, parent=None, thread=MAIN, **attrs):
    return HostSpanRecord(name, t0, t1, parent, thread, attrs)


def _ctx(loops, frames=4, **kw):
    """What a reader gets: the benchmark's span-part spans (its ``loop``
    spans bound the part) and the rest of a ``TraceContext``."""
    base = dict(spans=[spans_mod.Span("loop", a, b, None) for a, b in loops],
                missing=set(), wrapped=set(), frames=frames, trace=None,
                window_ns=None, rounds=2, plain_frames=0, plain_seconds=0.0,
                shape=None, traffic=SimpleNamespace(n_ues=2, buckets=(1, 2)),
                quant_block=8192, peaks={})
    base.update(kw)
    return harness.TraceContext(**base)


@pytest.fixture
def recorded(monkeypatch):
    rec = SimpleNamespace(spans=[])
    monkeypatch.setattr(programs, "_RECORDER", rec)
    return rec.spans


def test_module_events_are_named_by_their_program():
    assert programs.program_name("jit_swin_head(4417)") == "swin_head"
    assert programs.program_name("jit_swin_tail") == "swin_tail"
    assert programs.program_name("jit_swin_full.3") == "swin_full"
    assert programs.program_name("swin_full") == "swin_full"
    assert programs.program_name("jit_encode(12)") == "encode"


def test_a_count_that_does_not_hold_reads_nothing():
    mods = [T.Op("swin_head", 1100.0, 1200.0), T.Op("swin_head", 1300.0, 1350.0),
            T.Op("swin_tail", 1400.0, 1600.0), T.Op("encode", 1600.0, 1700.0),
            T.Op("swin_head", 2100.0, 2200.0)]        # after the window
    # two heads in the window, for two frames: 150 ns -> 75e-6 ms a frame
    assert programs.program_device_ms(mods, ("swin_head",), W, 2, 2) == \
        pytest.approx(75e-6)
    assert programs.program_device_ms(mods, ("swin_head",), W, 3, 2) is None
    assert programs.program_device_ms(
        mods, ("swin_tail", "swin_full"), W, 1, 2) == pytest.approx(100e-6)
    assert programs.program_device_ms(mods, ("swin_full",), W, 1, 2) is None


def test_outermost_copies_are_summed_once_when_nested(recorded):
    other = MAIN + 1
    recorded += [
        _rec("setup", 0.0, 0.5),                           # before the part
        _rec("copy.frame_h2d", 0.1, 0.2, bytes=1),
        _rec("tail", 1.0, 2.0),
        _rec("tail.stack", 1.0, 1.5, "tail"),
        _rec("copy.frame_h2d", 1.1, 1.4, "tail.stack", bytes=8),
        _rec("copy.inner", 1.2, 1.3, "copy.frame_h2d"),    # nested: once
        _rec("copy.codec_d2h", 2.5, 2.75),
        _rec("copy.codec_d2h", 2.6, 2.7, thread=other),    # its own thread
    ]
    ctx = _ctx([(1.0, 2.0), (2.0, 3.0)], frames=4)
    got = programs.span_ms_per_frame(ctx, lambda n: n.startswith("copy."))
    assert got == pytest.approx(1e3 * (0.3 + 0.25 + 0.1) / 4)
    assert programs.span_ms_per_frame(ctx, lambda n: n == "tail") == \
        pytest.approx(1e3 * 1.0 / 4)


def test_no_span_part_or_no_program_spans_reads_nothing(recorded,
                                                        monkeypatch):
    recorded.append(_rec("tail", 1.0, 2.0))
    assert programs.span_ms_per_frame(_ctx([]), lambda n: True) is None
    assert programs.span_ms_per_frame(_ctx([(0.0, 3.0)], frames=0),
                                      lambda n: True) is None
    monkeypatch.setattr(programs, "_RECORDER", None)
    monkeypatch.setattr(programs, "recorder", lambda: None)
    assert programs.span_ms_per_frame(_ctx([(0.0, 3.0)]),
                                      lambda n: True) is None


def test_mac_us_per_tti(recorded):
    reader = harness.load_reader("mac_us_per_tti")
    recorded += [_rec("mac.advance", 1.0, 1.3, ttis=200),
                 _rec("mac.advance", 1.5, 1.6, ttis=0),        # idle call
                 _rec("mac.advance", 2.0, 2.4, ttis=300),
                 _rec("mac.advance", 5.0, 6.0, ttis=999)]      # outside
    assert reader.read(_ctx([(1.0, 3.0)])) == pytest.approx(
        1e6 * 0.8 / 500)
    recorded[:] = [_rec("mac.advance", 1.0, 1.3, ttis=0)]
    assert reader.read(_ctx([(1.0, 3.0)])) is None


def test_an_idle_gap_under_a_program_span_takes_its_name():
    """Program spans, put on the trace's clock beside the benchmark's, name
    the gaps they hold: the innermost span open at a gap's midpoint."""
    ops = [T.Op("fusion", 1000.0, 1100.0), T.Op("fusion", 1900.0, 2000.0)]
    host = [T.Op("loop", 1000.0, 2000.0),                    # benchmark's
            T.Op("codec", 1050.0, 1950.0),                   # benchmark wrap
            T.Op("codec.encode", 1060.0, 1940.0),            # program's
            T.Op("codec.zlib", 1150.0, 1850.0)]
    (name, secs), = T.labelled_gaps(ops, host, W)
    assert name.split(" @")[0] == "codec.zlib"
    assert secs == pytest.approx(800e-9)


def test_a_recorded_cpu_trace_loads_and_has_no_device_programs():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    programs.note_profile_dirs()
    programs.note_profile_dirs()                      # wraps once
    tracer = harness.Tracer([], spans=False)
    try:
        tracer.begin("device")
        f(x).block_until_ready()
        tracer.end("device")
        assert programs._DIRS[-1] == tracer.dir       # noted at start
        tr = T.load(tracer.dir)
        assert programs.load_modules(tracer.dir) == []
        ctx = _ctx([], trace=tr, window_ns=(0.0, 5e9))
        assert programs.device_modules(ctx) == []
        for name in ("head_device_ms_per_frame", "tail_device_ms_per_frame"):
            assert harness.load_reader(name).read(ctx) is None
    finally:
        tracer.close()
    assert tr.start_ns is not None


def test_readers_of_a_program_without_host_spans_read_nothing(monkeypatch):
    monkeypatch.setattr(programs, "_RECORDER", None)
    import repro.core.telemetry as telemetry
    monkeypatch.delattr(telemetry, "HostRecorder")
    assert programs.recorder() is None
    ctx = _ctx([(0.0, 1.0)])
    for name in ("tail_host_ms_per_frame", "copy_ms_per_frame",
                 "mac_us_per_tti"):
        assert harness.load_reader(name).read(ctx) is None
    assert programs.device_ms_per_frame(
        dataclasses.replace(ctx, trace=T.Trace([], 0), window_ns=W),
        ("swin_head",), 2) is None


def test_a_traced_run_reads_the_program_spans(monkeypatch):
    """A whole traced run of the small ue8 cell on the CPU: the readers of
    the program's spans read in the span part; the device readers find no
    TPU and say nothing."""
    import time

    from chipbench.tests.test_chipbench_faults import PEAKS, _tiny

    monkeypatch.setattr(harness, "MAX_DEPARTURE", 1e9)   # the CPU's pace
    spec = _tiny("swin_t.split2.ue8.edf")                 # swings widely
    spec = dataclasses.replace(
        spec, traffic=dataclasses.replace(spec.traffic, trace_seconds=0.3))
    res = harness.run(spec, 2 ** 31 + 29, 0.9, True,
                      t_start=time.perf_counter(), require_tpu=False,
                      peaks=PEAKS)
    m = res["metrics"]
    for k in ("tail_host_ms_per_frame", "copy_ms_per_frame"):
        assert m[k]["value"] > 0 and m[k]["unit"] == "ms/frame", k
    assert m["mac_us_per_tti"]["value"] > 0
    assert m["mac_us_per_tti"]["unit"] == "us/TTI"
    for k in ("head_device_ms_per_frame", "tail_device_ms_per_frame"):
        assert k not in m
    assert res["correct"]

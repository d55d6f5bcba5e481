"""``bench/reduce_trace.py``: by hand on a made-up trace, and on a small
trace recorded on a TPU v5e (``record_trace.py``, gzipped)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import reduce_trace as rt

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb.gz"


def _ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def _line(name, events):
    return NS(name=name, events=events)


def _profile():
    """One chip, a 100 us window from t=1000 ns: two program runs and
    three operations (two overlapping), and a host thread that is in
    ``dispatch`` during the first idle gap and ``sync`` during the last."""
    us = 1000
    device = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [_ev("jit_decode(7)", 1000 + 10 * us, 30 * us),
                              _ev("jit_flush(8)", 1000 + 60 * us, 20 * us),
                              _ev("jit_decode(7)", 0, 500)]),
        _line("XLA Ops", [_ev("fusion.3", 1000 + 10 * us, 20 * us),
                          _ev("paged_wave_attention.1", 1000 + 20 * us,
                              20 * us),
                          _ev("fusion.12", 1000 + 60 * us, 20 * us)])])
    host = NS(name="/host:CPU", lines=[
        _line("python", [_ev(rt.WINDOW_SPAN, 1000, 100 * us),
                         _ev("dispatch", 1000, 10 * us),
                         _ev("sync", 1000 + 80 * us, 20 * us)])])
    return NS(planes=[host, device])


def test_reduce_by_hand():
    red = rt.reduce_profile(_profile())
    assert red["window_s"] == pytest.approx(100e-6)
    # busy: [10, 40) and [60, 80) us
    assert red["busy_s"] == pytest.approx(50e-6)
    assert red["modules"]["decode"] == [1, pytest.approx(30e-6)]
    assert red["modules"]["flush"] == [1, pytest.approx(20e-6)]
    assert red["ops"]["paged_wave_attention"] == [1, pytest.approx(20e-6)]
    assert red["ops"]["fusion"] == [2, pytest.approx(40e-6)]
    gaps = dict(red["idle_gaps"])
    # idle: [0, 10) in dispatch, [40, 60) under no event, [80, 100) in sync
    assert gaps == {"dispatch": pytest.approx(10e-6),
                    "host: no event": pytest.approx(20e-6),
                    "sync": pytest.approx(20e-6)}
    # operations named after the program run they lie in
    assert dict(red["device_ops"]) == {
        "decode/fusion": pytest.approx(20e-6),
        "decode/paged_wave_attention": pytest.approx(20e-6),
        "flush/fusion": pytest.approx(20e-6)}


def test_names_are_keyed_without_suffixes():
    assert rt.module_key("jit_chunk_pe(12)") == "chunk_pe"
    assert rt.module_key("jit_fin") == "fin"
    assert rt.op_key("paged_wave_attention.6") == "paged_wave_attention"
    assert rt.op_key("fusion.1.2") == "fusion"
    # a TPU names an operation's event by its whole HLO instruction
    assert rt.op_key("%paged_wave_attention.6 = f32[4,2,128]{2,1,0} "
                     "custom-call(s32[4,4]{1,0} %bitcast.357)") \
        == "paged_wave_attention"


def test_trace_without_window_or_device_is_refused():
    pd = _profile()
    with pytest.raises(ValueError):
        rt.reduce_profile(NS(planes=[pd.planes[0]]))
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        rt.reduce_profile(pd)


@pytest.fixture(scope="module")
def recorded():
    return rt.reduce_file(DATA)


def test_recorded_trace_names_the_engine_programs(recorded):
    for name in ("decode", "chunk", "fin", "flush", "argmax_ids"):
        assert recorded["modules"][name][0] >= 1, name
    calls, secs = recorded["ops"]["paged_wave_attention"]
    assert calls > 0 and 0 < secs < recorded["modules"]["decode"][1]
    assert recorded["chips"] == 1


def test_recorded_trace_busy_within_window(recorded):
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    idle = sum(s for _, s in recorded["idle_gaps"])
    assert idle <= recorded["window_s"] - recorded["busy_s"] + 1e-9
    assert 1 <= len(recorded["device_ops"]) <= 10
    assert all("/" in name and " " not in name
               for name, _ in recorded["device_ops"])
    total = sum(s for _, s in recorded["modules"].values())
    assert total <= recorded["window_s"] * recorded["chips"] + 1e-9


def _traced_spans(path):
    from jax.profiler import ProfileData
    return [(e.start_ns, e.duration_ns * 1e-9)
            for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name == rt.WINDOW_SPAN]


@pytest.mark.parametrize("span_s, work_s, share, start_s", [
    (0.5, 1.5, 0.0, 0.0), (5.0, 0.5, 0.0, 0.0), (5.0, 2.0, 0.6, 0.5)],
    ids=["span-ends-first", "window-ends-first", "starts-at-share"])
def test_window_trace_spans_the_traced_part(tmp_path, span_s, work_s, share,
                                            start_s):
    """The harness's traced window: the span ``bench.window`` opens once
    the requests hold ``share`` of the tokens they ask for, and lasts
    ``span_s`` or until the window closes, whichever comes first. Here
    the window serves its tokens evenly over ``work_s`` (the share is
    reached at ``start_s``)."""
    import time

    import jax.numpy as jnp

    from bench.run import WindowTrace
    reqs = [NS(max_new_tokens=100, out_tokens=[]) for _ in range(2)]
    t0 = time.perf_counter()
    tracer = WindowTrace(str(tmp_path), span_s, reqs, share)
    x = jnp.ones((128, 128))
    while (now := time.perf_counter() - t0) < work_s:
        x = (x @ x) / 128.0
        for r in reqs:
            r.out_tokens.extend([0] * (int(100 * min(1.2 * now, 1.0))
                                       - len(r.out_tokens)))
    x.block_until_ready()
    tracer.close()
    (path,) = tmp_path.rglob("*.xplane.pb")
    (span,) = _traced_spans(path)
    assert span[1] == pytest.approx(min(span_s, work_s - start_s), abs=0.3)


def test_window_trace_records_nothing_when_the_share_is_never_reached(
        tmp_path):
    from bench.run import WindowTrace
    reqs = [NS(max_new_tokens=10, out_tokens=[1, 2])]
    tracer = WindowTrace(str(tmp_path), 5.0, reqs, 0.5)
    tracer.close()
    assert not list(tmp_path.rglob("*.xplane.pb"))

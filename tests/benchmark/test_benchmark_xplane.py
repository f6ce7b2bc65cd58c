"""The reduction from a profiler trace to numbers: on a trace written down
by hand, where every answer can be worked out on paper, and on two small
recorded ones (``fixtures/*.xplane.pb.gz``: the char-RNN cell on one v5e and
the ParallelWrapper cell on four, cut to a few steps; PR 22)."""
import gzip
import os
import shutil

import pytest

from benchmark import xplane
from benchmark.xplane import Device, Trace, event

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e3                                 # the hand-written trace counts in µs

FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
KERNEL = ('%jvp__.2 = f32[8]{0} custom-call(f32[8]{0} %p), '
          'custom_call_target="tpu_custom_call"')
WHILE = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
AR_START = "%all-reduce-start.4 = f32[8]{0} all-reduce-start(f32[8]{0} %g)"
AR_DONE = "%all-reduce-done.4 = f32[8]{0} all-reduce-done(f32[8]{0} %s)"
AR_SYNC = "%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %g), to_apply=%add"


def _hand_written():
    """One device. A while from 0 to 40 holding a fusion (0-10) and a kernel
    (10-30); idle 40-60; a fusion 60-70; an asynchronous all-reduce in
    flight 65-90 whose done op waits 70-90; a synchronous all-reduce
    90-100. Host: fit 0-100, the iterator's next 35-62 with its barrier
    35-45 inside, JAX's DevicePut 45-58 on the fit thread."""
    ops = [event(WHILE, 0, 40 * US), event(FUSION, 0, 10 * US),
           event(KERNEL, 10 * US, 30 * US), event(FUSION, 60 * US, 70 * US),
           event(AR_START, 65 * US, 66 * US), event(AR_DONE, 70 * US, 90 * US),
           event(AR_SYNC, 90 * US, 100 * US)]
    ops.sort(key=lambda ev: (ev.start, -ev.end))
    flying = [event(AR_START, 65 * US, 90 * US)]
    host = [("python3", [event("bench/fit", 0, 100 * US),
                         event("DevicePut", 45 * US, 58 * US)]),
            ("input-prefetch-0",
             [event("bench/input_next", 35 * US, 62 * US),
              event("bench/run_ahead_barrier", 35 * US, 45 * US)])]
    return Trace([Device(0, ops, flying, [event("jit_step(1)", 0, 100 * US)])],
                 host)


def test_names_and_opcodes_come_from_the_hlo_text():
    ev = event(KERNEL, 0, 1)
    assert (ev.name, ev.opcode) == ("jvp__.2", "custom-call")
    assert xplane.is_pallas(ev) and not xplane.is_collective(ev)
    assert event(AR_START, 0, 1).opcode == "all-reduce-start"
    assert xplane.is_collective(event(AR_SYNC, 0, 1))
    assert event("bench/fit", 0, 1).opcode == ""


def test_busy_and_idle_of_the_hand_written_trace():
    trace = _hand_written()
    t0, t1 = xplane.window(trace)
    assert (t0, t1) == (0, 100 * US)
    # busy 0-40 and 60-100: the while counts once, the idle gap not at all
    assert xplane.busy_seconds(trace) == pytest.approx(80e-6)
    self_s = xplane.self_seconds(trace.devices[0])
    assert self_s["while.3"] == pytest.approx(10e-6)     # 40 less 10 and 20
    assert self_s["jvp__.2"] == pytest.approx(20e-6)
    assert xplane.top_ops(trace, n=2)[0] == [
        "jvp__.2 custom-call (pallas)", pytest.approx(20e-6)]
    assert xplane.pallas_seconds(trace) == (pytest.approx(20e-6), 1)
    assert xplane.module_seconds(trace) == {
        "jit_step": (1, pytest.approx(100e-6))}


def test_collective_time_and_its_exposed_part():
    flight, exposed = xplane.collective_seconds(_hand_written())
    # in flight 65-90 and 90-100; compute (the fusion) covers 65-70 of it
    assert flight == pytest.approx(35e-6)
    assert exposed == pytest.approx(30e-6)


def test_idle_gap_goes_to_what_the_host_was_doing():
    gaps = dict(xplane.idle_gaps(_hand_written()))
    # the gap 40-60: the barrier held 40-45, next() went on to 60; the rest
    # of next() outranks JAX's DevicePut, which overlaps it
    assert gaps == {"bench/run_ahead_barrier": pytest.approx(5e-6),
                    "bench/input_next": pytest.approx(15e-6)}
    trace = _hand_written()
    trace.host[1] = ("input-prefetch-0", [])             # no span of ours
    assert dict(xplane.idle_gaps(trace)) == {
        "bench/fit > DevicePut": pytest.approx(20e-6)}


def test_interval_arithmetic():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert xplane.measure(merged) == 6
    assert xplane.overlap(merged, 2, 6) == 2
    assert xplane.gaps(merged, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (8, 10)]


def _recorded(name, tmp_path):
    path = tmp_path / name
    with gzip.open(os.path.join(HERE, "fixtures", name + ".gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.load(str(path))


def test_recorded_one_chip_trace(tmp_path):
    """Three ``fit`` calls of the char-RNN (64 x 200, 4 segments each) on one
    v5e, monitor off: 16 Mosaic calls per fit, the device idle between
    programs while the host dispatches."""
    trace = _recorded("charrnn_v5e_3fits.xplane.pb", tmp_path)
    assert [d.ordinal for d in trace.devices] == [0]
    t0, t1 = xplane.window(trace)
    assert (t1 - t0) * 1e-6 == pytest.approx(15.746, abs=1e-3)       # ms
    assert xplane.busy_seconds(trace) * 1e3 == pytest.approx(8.967, abs=1e-3)
    seconds, calls = xplane.pallas_seconds(trace)
    assert calls == 3 * 16
    assert seconds * 1e3 == pytest.approx(2.451, abs=1e-3)
    runs, mean = xplane.module_seconds(trace)["jit_scanned"]
    assert runs == 3 and mean * 1e3 == pytest.approx(2.903, abs=1e-3)
    top = xplane.top_ops(trace, n=4)
    assert all(label.endswith("custom-call (pallas)") for label, _ in top)
    assert xplane.collective_seconds(trace) == (0.0, 0.0)
    gaps = dict(xplane.idle_gaps(trace))
    # the idle time is all found, and all of it inside fit
    assert sum(gaps.values()) * 1e3 == pytest.approx(15.746 - 8.967, abs=2e-3)
    assert all(label.startswith("bench/fit") for label in gaps)
    assert xplane.h2d_bytes(trace) == 7680   # iteration counters and keys


def test_recorded_four_chip_trace(tmp_path):
    """One ResNet50 step of the ParallelWrapper cell on four v5e chips (HLO
    texts shortened to name and opcode): a hundred small synchronous
    all-reduces per device, none of them hidden behind compute."""
    trace = _recorded("resnet50_pw4_v5e_1step.xplane.pb", tmp_path)
    assert [d.ordinal for d in trace.devices] == [0, 1, 2, 3]
    assert sum(1 for ev in trace.devices[0].ops
               if xplane.is_collective(ev)) == 100
    flight, exposed = xplane.collective_seconds(trace)
    assert flight * 1e3 == pytest.approx(1.281, abs=1e-3)            # ms
    assert exposed == pytest.approx(flight)
    # the copies of the asynchronous line are no collectives
    assert trace.devices[0].async_ops
    assert not any(xplane.is_collective(ev)
                   for ev in trace.devices[0].async_ops)
    t0, t1 = xplane.window(trace)
    assert xplane.busy_seconds(trace) / ((t1 - t0) * 1e-9) > 0.999
    assert xplane.pallas_seconds(trace) == (0.0, 0)
    runs, mean = xplane.module_seconds(trace)["jit_step"]
    assert runs == 1 and mean * 1e3 == pytest.approx(100.889, abs=1e-3)

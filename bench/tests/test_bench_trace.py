"""The trace reduction: against a small trace recorded on a TPU v5e (a
paged decode kernel and a matmul program, three times each, inside
``bench.traced`` with ``bench.step``/``bench.wait``/``bench.drain``
spans), and against hand-made planes whose union and gaps are known."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace  # noqa: E402

SMALL = os.path.join(HERE, "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace.reduce_file(SMALL)


def test_recorded_trace_planes(small):
    assert small.n_devices == 1
    assert 0 < small.busy_s < small.window_s
    # busy and idle partition the window
    idle = sum(small.idle_by_span.values())
    assert small.busy_s + idle == pytest.approx(small.window_s, rel=1e-9)


def test_recorded_trace_names(small):
    s, n = small.ops_matching("flash_decode_paged")
    assert n >= 2 and s > 0
    assert s <= small.busy_s
    s_mod, n_mod = small.modules_matching("jit__lambda")
    assert n_mod >= 4 and s_mod >= s
    # the host slept between the programs: that is where the idle time is
    assert max(small.idle_by_span, key=small.idle_by_span.get) == \
        "bench.wait"
    b = small.breakdown()
    assert b["device_ops"][0][0] == "flash_decode_paged"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_name():
    assert trace.op_name("%flash_decode_paged.12 = (f32[2]) custom-call(x)"
                         ) == "flash_decode_paged"
    assert trace.op_name("%copy-start = (bf16[4]) copy-start(%x)") == \
        "copy-start"
    assert trace.op_name("fusion") == "fusion"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes(ops, spans, mods=()):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev(*o) for o in ops]),
        NS(name="XLA Modules", events=[ev(*m) for m in mods])])
    host = NS(name="/host:CPU", lines=[NS(name="python3",
                                          events=[ev(*s) for s in spans])])
    return [host, dev, NS(name="/host:metadata", lines=[])]


def test_union_of_overlapping_ops_and_idle_gaps():
    ops = [("%a.1 = x", 10, 20),      # [10, 30)
           ("%b.2 = x", 25, 10),      # [25, 35) overlaps a
           ("%a.3 = x", 60, 10),      # [60, 70)
           ("%c = x", 95, 20)]        # [95, 115) crosses the window's end
    spans = [("bench.traced", 0, 100), ("bench.step", 0, 40),
             ("bench.wait", 40, 15), ("bench.drain", 55, 45)]
    r = trace.reduce_planes(planes(ops, spans, [("jit__f(1)", 10, 25)]))
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((35 - 10 + 10 + 5) * 1e-9)
    assert r.op_s["a"] == pytest.approx(30e-9) and r.op_n["a"] == 2
    assert r.op_n["c"] == 1
    assert r.module_n == {"jit__f(1)": 1}
    # gaps: [0,10) in step, [35,60) mostly wait, [70,95) in drain
    assert r.idle_by_span["bench.step"] == pytest.approx(10e-9)
    assert r.idle_by_span["bench.wait"] == pytest.approx(25e-9)
    assert r.idle_by_span["bench.drain"] == pytest.approx(25e-9)


def test_window_defaults_to_the_device_ops():
    r = trace.reduce_planes(planes([("%a = x", 10, 10), ("%a = x", 30, 10)],
                                   []))
    assert r.window_s == pytest.approx(30e-9)
    assert r.busy_s == pytest.approx(20e-9)
    assert r.idle_by_span == {"outside harness spans": pytest.approx(10e-9)}

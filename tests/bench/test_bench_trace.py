"""The reduction from a profiler trace to the benchmark's numbers, on
small made-up traces (no chip, no libtpu)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace  # noqa: E402
from bench.trace import Event  # noqa: E402


def ev(name, start, end):
    return Event(name, float(start), float(end))


def test_busy_is_the_union_of_overlapping_ops():
    ops = [ev("a", 0, 10), ev("b", 5, 15), ev("c", 20, 30), ev("d", 22, 25)]
    assert trace.merge(ops) == [(0, 15), (20, 30)]
    assert trace.busy_ns(ops) == 25


def test_idle_share_and_per_chip_mean():
    tr = trace.Trace(ops={0: [ev("a", 0, 40)], 1: [ev("a", 0, 80)]},
                     window=(0.0, 100.0))
    assert trace.busy_s(tr) == pytest.approx(60e-9)
    assert trace.idle_share(tr) == pytest.approx(0.4)


def test_no_device_ops_reads_nothing():
    tr = trace.Trace(ops={0: []}, window=(0.0, 100.0))
    assert trace.busy_s(tr) is None
    assert trace.idle_share(tr) is None


def test_clip_gaps_and_events_by_name():
    ops = [ev("vfl_matmul.1", -5, 10), ev("fusion.3", 30, 50),
           ev("vfl_matmul.2", 60, 120)]
    clipped = trace.clip(ops, 0, 100)
    assert [(e.start, e.end) for e in clipped] == [(0, 10), (30, 50),
                                                  (60, 100)]
    assert trace.gaps(clipped, 0, 100) == [(10, 30), (50, 60)]
    assert [e.name for e in trace.named(clipped, r"^vfl_matmul")] == \
        ["vfl_matmul.1", "vfl_matmul.2"]
    assert trace.total_by_name(clipped) == {"vfl_matmul.1": 10,
                                            "fusion.3": 20,
                                            "vfl_matmul.2": 40}


def test_within_attributes_ops_to_programs():
    ops = [ev("k", 1, 2), ev("k", 12, 13), ev("k", 25, 26)]
    progs = [ev("jit_round_fn", 0, 10), ev("jit_predict", 20, 30)]
    assert len(trace.within(ops, trace.named(progs, "round_fn"))) == 1
    assert len(trace.within(ops, trace.named(progs, "predict"))) == 1


def test_idle_gaps_are_named_by_the_host():
    tr = trace.Trace(
        ops={0: [ev("op", 0, 10), ev("op", 10_010, 10_020),
                 ev("op", 50_020, 100_000)]},
        host=[ev(trace.WINDOW, 0, 100_000), ev("$session.py run", 0, 99_000),
              ev("$protocol.py evaluate", 10_020, 50_020)],
        window=(0.0, 100_000.0))
    b = trace.breakdown(tr, short_ns=1e3)
    idle = dict(b["idle_gaps"])
    assert idle["$protocol.py evaluate"] == pytest.approx(40_000e-9)
    assert idle["$session.py run"] == pytest.approx(10_000e-9)
    assert b["device_ops"][0] == ["op", pytest.approx(50_000e-9)]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "vfl_matmul.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_round_fn(1)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def test_load_reads_device_lines_within_the_window(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = trace.load(str(tmp_path))
    assert tr.window == (1000.0, 11000.0)
    assert tr.window_s == pytest.approx(10e-6)
    assert [e.name for e in tr.ops[0]] == ["vfl_matmul.1", "fusion.2"]
    assert trace.busy_s(tr) == pytest.approx(3e-6)
    assert trace.idle_share(tr) == pytest.approx(0.7)
    assert [e.name for e in tr.modules[0]] == ["jit_round_fn(1)"]

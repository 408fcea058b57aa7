"""The trace reduction, on a trace recorded on one TPU v5e
(``data/probe.trace.json.gz``): three rounds of a 1024x1024 bf16 matmul
and the program's computed-mask attention kernel (8 heads x 1024 x 128),
each round under the host spans ``bench.generate`` and ``engine.step``,
then 10 ms in ``bench.sleep``.  The profiler ran for 0.33203 s on the
host's clock."""
import gzip
import json
from pathlib import Path

import pytest

import trace_reduce as T

TRACE = Path(__file__).with_name("data") / "probe.trace.json.gz"
WINDOW_S = 0.33203


@pytest.fixture(scope="module")
def events():
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)["traceEvents"]


def test_parse_finds_device_host_and_launches(events):
    devices, host, launches, modules = T.parse(events)
    assert list(devices) == ["/device:TPU:0"]
    assert len(launches) == len(modules) == 6
    names = [n for _, _, n in host]
    assert names.count("bench.generate") == 3
    assert names.count("bench.sleep") == 3


def test_kernel_named_by_its_source_line(events):
    s = T.reduce_file(str(TRACE), WINDOW_S)
    assert s.kernel_s(T.PREFILL_ATTENTION) == pytest.approx(
        (238.76375 + 238.650078 + 238.33375) / 1e6)
    assert s.kernel_s(T.PAGED_ATTENTION) == 0
    assert "convolution_reduce_fusion" in s.ops


def test_busy_is_the_union_and_idle_goes_to_the_sleep(events):
    s = T.reduce_file(str(TRACE), WINDOW_S)
    devices, _, _, _ = T.parse(events)
    merged = T.union([(a, b) for _, a, b in devices["/device:TPU:0"]])
    assert s.busy_s == pytest.approx(sum(b - a for a, b in merged) / 1e6)
    assert s.idle_share > 0.99
    gaps = dict(s.breakdown()["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench.sleep"
    # two sleeps of ~10.3 and ~10.9 ms lie between the three rounds
    assert gaps["bench.sleep"] == pytest.approx(0.0224, abs=0.001)


def test_device_clock_moved_onto_the_host(events):
    _, _, launches, modules = T.parse(events)
    shift = T.clock_shift(launches, modules)
    # each module starts after its launch once shifted
    assert 900 < shift < 1300
    assert T.clock_shift(launches[:-1], modules) == 0.0


def test_union_and_attribute():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    spans = [(0, 10, "bench.sleep"), (9, 30, "engine.step")]
    assert T.attribute((8, 12), spans) == "engine.step"
    assert T.attribute((40, 50), spans) == "host"


def test_breakdown_keeps_ten_largest():
    s = T.TraceSummary(window_s=1.0, busy_s=0.5,
                       ops={f"op{i}": i / 100 for i in range(20)},
                       gaps=[("bench.sleep", 0.1), ("bench.sleep", 0.2),
                             ("engine.step", 0.05)])
    b = s.breakdown()
    assert [n for n, _ in b["device_ops"]] == [f"op{i}"
                                               for i in range(19, 9, -1)]
    assert b["idle_gaps"] == [["bench.sleep", pytest.approx(0.3)],
                              ["engine.step", 0.05]]


def test_nested_ops_count_their_own_time():
    evs = [("while", 0, 100), ("fusion", 10, 30), ("while", 40, 90),
           ("kernel", 50, 80), ("copy", 100, 105)]
    assert T.self_times(evs) == [("while", 30), ("fusion", 20),
                                 ("while", 20), ("kernel", 30),
                                 ("copy", 5)]
    s = T.summarize({"/device:TPU:0": evs}, [], 1.0)
    assert s.ops == {"while": 50e-6, "fusion": 20e-6, "kernel": 30e-6,
                     "copy": 5e-6}
    assert s.busy_s == pytest.approx(105e-6)

"""``trace_reduce`` against numbers read by hand from a trace recorded on
one v5e chip (``data/selfjoin-4k.xplane.pb``: a traced run of
``selfjoin.sift128-4k``, four requests in the window).

Read from the trace's own lines, event by event:
- ``bench.window`` on the host's python line: one event of 22.748511582 s;
- ``plan`` spans: four, 2.374010873 s together; ``execute``: four,
  18.029771653 s; the device ran nothing while a plan was made;
- the device's ``XLA Modules`` line: four ``jit_run`` programs, one per
  request, 2.209881651 s together, and its ``XLA Ops`` line runs only
  inside them;
- 32 ``fused_gather_gram_rect`` events (eight capacity buckets, four
  requests), 0.935400650 s together."""

import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "selfjoin-4k.xplane.pb")
NS = 1e-9


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_trace(TRACE)


def test_window_and_busy(red):
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(22_748_511_582 * NS, abs=1e-9)
    # the ops' union is the four programs' time, to their edge slack
    assert red["busy_s"] == pytest.approx(2_209_881_651 * NS, abs=5e-6)


def test_kernel_and_rest(red):
    assert red["kernel_events"] == 32
    assert red["kernel_s"] == pytest.approx(935_400_650 * NS, abs=1e-9)
    assert red["collective_s"] == 0.0
    assert red["kernel_s"] + red["other_s"] == pytest.approx(
        sum(red["op_s"].values()), rel=1e-12)
    assert max(red["op_s"], key=red["op_s"].get) == "fusion"


def test_gaps_by_host_span(red):
    gaps = red["gap_s"]
    assert gaps["plan"] == pytest.approx(2_374_010_873 * NS, abs=1e-9)
    assert gaps["execute"] <= 18_029_771_653 * NS
    assert gaps["execute"] > 0.99 * 18_029_771_653 * NS
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(
        red["window_s"], abs=1e-6)


def test_collectives_by_opcode():
    c = trace_reduce.COLLECTIVE
    assert c.search("%all_to_all.4 = f32[4,1,50450749]{2,1,0} all-to-all("
                    "f32[4,1,50450749]{2,1,0} %and_select_fusion)")
    assert c.search("%ag = f32[8] all-gather-start(f32[2] %p)")
    assert not c.search("%fusion.3 = s32[7170,1,256] fusion(s32[1] %p)")


def test_short_name():
    assert trace_reduce.short_name(
        "%fused_gather_gram_rect.15 = f32[4808,256,256]{2,1,0} "
        "custom-call(...)") == "fused_gather_gram_rect"
    assert trace_reduce.short_name("%all-to-all.3 = f32[4]") == "all-to-all"
    assert trace_reduce.short_name("fusion") == "fusion"

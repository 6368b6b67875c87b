"""The trace reduction, on a small trace recorded on one v5e chip and on
intervals made by hand.

``data/tiny_v5e.xplane.pb``: three steps, each a host ``train`` span
holding a ``data`` span (a 20 ms sleep), a ``dispatch`` span and a
``wait`` span, around one jitted program of four device operations:
copy-start, copy-done, ``convolution_tanh_fusion`` and ``fusion``.  The
numbers below were read off the trace's events by hand (nanoseconds):

* window: first ``train`` start 40926558 to last ``train`` end
  83773156 + 21672459 = 105445615, so 64519057 ns;
* busy: per step the four operations' durations, 14 + 3 + 89953 + 90877,
  13 + 3 + 89953 + 90871 and 13 + 2 + 89953 + 90872, which do not
  overlap: 542527 ns;
* the longest idle gap runs from the second step's last operation's end,
  83213256 + 90871 = 83304127, to the third step's first operation at
  104917605: 21613478 ns, most of it inside the third ``data`` span
  (83775526 to 104662765); the next, from the first step's last
  operation to the second's first, is 83123282 - 61958838 = 21164444 ns,
  also under ``data``; the gap from the window's start to the
  first operation at 61777987 is 20851429 ns.
"""

from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(str(TRACE)))


def test_window_busy_and_steps(reduced):
    assert reduced["steps"] == 3 and reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(64519057e-9, abs=2e-9)
    assert reduced["busy_s"] == pytest.approx(542527e-9, abs=2e-9)
    assert reduced["collective_s"] == 0.0


def test_top_ops_by_name(reduced):
    ops = dict(reduced["device_ops"])
    assert list(ops)[:2] == ["fusion", "convolution_tanh_fusion"]
    assert ops["fusion"] == pytest.approx((90877 + 90871 + 90872) * 1e-9,
                                          abs=2e-9)
    assert ops["convolution_tanh_fusion"] == pytest.approx(
        3 * 89953e-9, abs=2e-9)


def test_longest_gap_is_labelled_data(reduced):
    (first, s1), (second, s2) = reduced["idle_gaps"][:2]
    assert first == second == "data"
    assert s1 == pytest.approx(21613478e-9, abs=2e-9)
    assert s2 == pytest.approx(21164444e-9, abs=2e-9)


def test_nested_ops_count_their_self_time():
    ops = [("%while.1 = (...) while(...)", 0.0, 10.0),
           ("%fusion.2 = f32[] fusion()", 1.0, 4.0),
           ("%all-reduce.3 = f32[] all-reduce()", 5.0, 6.0),
           ("%fusion.2 = f32[] fusion()", 7.0, 9.0)]
    assert dict(tr.self_times(ops[:2])) == {"while.1": 7.0, "fusion.2": 3.0}
    trace = tr.Trace(device_ops={"/device:TPU:0": ops,
                                 "/device:TPU:1": [("%fusion.9", 2.0, 3.0)]},
                     host_spans=[("train", 0.0, 12.0), ("wait", 9.5, 12.0),
                                 ("data", 10.5, 11.0)])
    r = tr.reduce(trace)
    assert r["window_s"] == 12.0
    assert r["busy_s"] == (10.0 + 1.0) / 2
    assert r["collective_s"] == 1.0 / 2
    assert dict(r["device_ops"])["while.1"] == 4.0 / 2
    # device 0 idles 10..12 under wait; device 1 idles 0..2 and 3..12
    assert r["idle_gaps"][0] == ["wait", 9.0]
    assert ["wait", 2.0] in r["idle_gaps"] and ["host", 2.0] in r["idle_gaps"]


def test_no_steps_reads_nothing():
    assert tr.reduce(tr.Trace(device_ops={"/device:TPU:0": [("a", 0, 1)]},
                              host_spans=[])) is None

"""The reduction from a trace to the per-layer metrics, on a hand-made
trace whose answers are worked out by hand, and on a small trace
recorded on the chip (``trace_*.json.gz`` beside this file)."""
import glob
import importlib
import os
import types

import pytest

import tracefile as tr

DEV = "/device:TPU:0"
# two steps; step 1 holds a fusion (100-200), an all-gather overlapping it
# (150-260) and a wire kernel (300-350); the host makes a batch
# (410-500) before step 2's ops (520-600, 610-700)
HAND = tr.Trace(
    {DEV: [("fusion.1", 100, 200), ("all-gather.3", 150, 260),
           ("tpu_custom_call:custom-call.2", 300, 350), ("fusion.4", 520, 600),
           ("fusion.5", 610, 700)]},
    [("bench.window", 90, 720), ("bench.step", 95, 400),
     ("bench.batch", 410, 500), ("bench.step", 405, 710)])


def ctx(trace, steps=2, chips=1):
    cell = types.SimpleNamespace(chips=chips, tokens_per_step=1000)
    session = types.SimpleNamespace(routed_tokens=0.0, dropped_tokens=0.0,
                                    _params={})
    return types.SimpleNamespace(
        trace=trace, steps=steps, cell=cell, flops_per_token=1e3,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        session=session)


def read(name, c):
    return importlib.import_module(f"metrics.{name}").read(c)


def test_hand_made_trace():
    c = ctx(HAND)
    # busy: 100-260, 300-350, 520-600, 610-700 = 160+50+80+90 = 380 of 630
    assert read("device_idle_frac", c) == pytest.approx(1 - 380 / 630)
    # last op of step 1 ends at 350, first of step 2 starts at 520
    assert read("host_gap_ms", c) == pytest.approx(170e-6)
    # steps start 100 and 520 ns apart: 1e6 FLOP in 420 ns on 1e12 FLOP/s
    assert read("step_mfu", c) == pytest.approx(100 * 1e6 / 420e-9 / 1e12)
    assert tr.idle_gaps(HAND, DEV)[0] == ("bench.batch", pytest.approx(
        170e-9))
    assert tr.top_ops(HAND, DEV)[0] == ("all-gather.3", pytest.approx(
        110e-9))
    assert read("moe_drop_frac", c) is None


def test_op_names_from_hlo_text():
    assert tr.op_name("%fusion.185 = (bf16[64,2048]{1,0}) fusion(%a), "
                      "kind=kOutput") == "fusion.185"
    assert tr.op_name('%custom-call.7 = s8[1024]{0} custom-call(%x), '
                      'custom_call_target="tpu_custom_call"') == \
        "tpu_custom_call:custom-call.7"


def test_self_time_leaves_out_nested_ops():
    ops = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 90),
           ("fusion.4", 50, 60), ("copy.5", 120, 130)]
    assert dict(tr.self_times(ops)) == {"while.1": 30, "fusion.2": 20,
                                        "fusion.3": 40, "fusion.4": 10,
                                        "copy.5": 10}


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 5), (6, 9)], 3, 7) == [(3, 5), (6, 7)]


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__),
                                         "trace_*.json.gz")))


# what the reduction read from each recorded trace when it was recorded
# (two deepseek-v2-lite-2l steps on one TPU v5 lite: a 481 ms window,
# 455 ms of it busy, 4.1 ms between the steps)
RECORDED_READINGS = {
    "trace_deepseek-v2-lite-2l.json.gz": {
        "window_ns": 481366516, "busy_ns": 455326142, "gaps_ns": [4139381]},
}


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace(path):
    """A chip trace of two steps: every reading is finite and inside its
    range, busy time is the union of ops, and the two steps' gap is
    no longer than the window."""
    t = tr.load_json(path)
    lo, hi = tr.window(t)
    plane = tr.busiest(t)
    busy = tr.total(tr.busy(t, plane))
    assert 0 < busy <= hi - lo
    c = ctx(t, steps=2, chips=len(t.devices))
    idle = read("device_idle_frac", c)
    assert 0 <= idle < 1
    gaps = tr.step_gaps_ns(t, plane)
    assert len(gaps) == 1 and 0 <= gaps[0] <= hi - lo
    assert sum(s for _, s in tr.idle_gaps(t, plane)) <= (hi - lo) / 1e9
    want = RECORDED_READINGS.get(os.path.basename(path))
    if want:
        assert (hi - lo) == want["window_ns"]
        assert busy == want["busy_ns"]
        assert gaps == want["gaps_ns"]

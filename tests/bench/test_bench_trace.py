"""The trace reduction against numbers worked out by hand."""
import numpy as np

import tinyroot

from bench import trace as T


from pathlib import Path

CHIP_TRACE = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


def ev(name, start, dur, device=0):
    return T.Event(name, float(start), float(dur), device)


def synthetic():
    # window [0, 100); spans: cluster [10, 40), solve [50, 90)
    # ops: [5, 15) [12, 20) in-and-before cluster, [30, 45) across its end,
    #      [60, 70) and [80, 95) in and across the end of solve,
    #      [98, 110) past the window
    ops = [ev("a", 5, 10), ev("b", 12, 8), ev("_cd_body", 30, 15),
           ev("c", 60, 10), ev("_cd_body", 80, 15), ev("d", 98, 12)]
    spans = [ev("bench/window", 0, 100), ev("divide/level1/cluster", 10, 30),
             ev("divide/level1/solve", 50, 40)]
    mods = [ev("jit_f(1)", 5, 40), ev("jit_g(2)", 60, 50)]
    return T.Trace.from_events(ops, mods, spans)


def test_merge_and_overlap():
    iv = np.array([[5, 15], [12, 20], [30, 45], [40, 41]], float)
    assert T.merge(iv).tolist() == [[5, 20], [30, 45]]
    a = np.array([[0, 10], [20, 30]], float)
    b = np.array([[5, 25]], float)
    assert T.overlap(a, b) == 10.0


def test_busy_idle_and_spans_by_hand():
    t = synthetic()
    # busy inside [0, 100): [5,20) 15 + [30,45) 15 + [60,70) 10
    #   + [80,95) 15 + [98,100) 2 = 57 ns
    assert abs(t.window_s() - 100e-9) < 1e-18
    assert abs(t.busy_s() - 57e-9) < 1e-18
    # inside cluster [10,40): [10,20) 10 + [30,40) 10 = 20 ns
    assert abs(t.busy_in(lambda s: s.endswith("/cluster")) - 20e-9) < 1e-18
    # inside solve [50,90): [60,70) 10 + [80,90) 10 = 20 ns
    assert abs(t.busy_in(lambda s: s.endswith("/solve")) - 20e-9) < 1e-18
    assert t.span_at(35) == "divide/level1/cluster"
    assert t.span_at(47) == "host"


def test_breakdown_by_hand():
    t = synthetic()
    b = t.breakdown()
    ops = dict(b["device_ops"])
    assert abs(ops["_cd_body"] - 30e-9) < 1e-18
    assert b["device_ops"][0][0] == "_cd_body"
    # idle gaps in [0,100): [0,5) 5, [20,30) 10, [45,60) 15, [70,80) 10,
    # [95,98) 3; the longest is named by the span its midpoint (52.5) is in
    gaps = b["idle_gaps"]
    assert gaps[0][0] == "divide/level1/solve"
    assert abs(gaps[0][1] - 15e-9) < 1e-18
    assert gaps[-1][0] == "host" and abs(gaps[-1][1] - 3e-9) < 1e-18
    assert abs(sum(g for _, g in gaps) - 43e-9) < 1e-18


def test_kernel_events_by_name():
    t = synthetic()
    cd = t.ops(lambda n: "_cd_body" in n)
    assert len(cd) == 2
    assert t.span_at(cd[0].start) == "divide/level1/cluster"
    assert len(t.programs(lambda n: "jit_g" in n)) == 1


def test_short_names():
    assert T.short("%while.22 = (f32[4,1]{0,1:T(1,128)}, s32[]) while((f32"
                   "[4,1]) %tuple.31), condition=%c") == "%while.22 while"
    assert T.short('%custom-call.3 = f32[8,1]{1,0} custom-call(f32[8,4]{1,0}'
                   ' %p), custom_call_target="tpu_custom_call"') == \
        "%custom-call.3 custom-call tpu_custom_call"


# A trace recorded on one TPU v5e: inside the window span, span
# divide/level1/cluster runs one jitted program twice (a prefetch copy and a
# fusion each), the host sleeps 20 ms, span divide/level1/solve runs another
# program once, the host sleeps 10 ms.  The device ops, in ns on the trace's
# clock:
#   [38176640 +13) [38176655 +3152) [38179807 +23306)   program 1, run 1
#   [38493564 +13) [38493577 +3138) [38496716 +23302)   program 1, run 2
#   [60497274 +15850)                                   program 2
# window [37801067 +34130417), cluster [37813867 +1171280),
# solve [60081915 +849830).  By hand: run 1 is busy 13 + (3152 + 23306) =
# 26471 ns (the copy-done and the fusion touch); run 2 is 13 + 3138 = 3151
# then, after a 1 ns gap, 23302: 26453 ns; program 2 15850 ns.


def test_chip_trace_by_hand():
    t = T.Trace.from_file(CHIP_TRACE)
    assert len(t.op_start) == 7 and len(t.modules) == 3
    assert abs(t.window_s() - 34130417e-9) < 1e-12
    busy = 26471 + 26453 + 15850
    assert abs(t.busy_s() - busy * 1e-9) < 5e-12
    assert abs(t.busy_in(lambda s: s.endswith("/cluster"))
               - (26471 + 26453) * 1e-9) < 5e-12
    assert abs(t.busy_in(lambda s: s.endswith("/solve")) - 15850e-9) < 5e-12
    idle = 1.0 - busy / 34130417
    assert abs((1.0 - t.busy_s() / t.window_s()) - idle) < 1e-9
    b = t.breakdown()
    # the longest idle gap is the 20 ms host sleep between the spans:
    # from the end of run 2 (38496716 + 23302) to program 2's start
    name, gap = b["idle_gaps"][0]
    assert name == "host"
    assert abs(gap - (60497274 - 38520018) * 1e-9) < 5e-12
    assert b["device_ops"][0][1] > b["device_ops"][-1][1]


def test_cd_update_roofline_reads_shapes_from_the_op():
    from bench import harness as H

    name = ("%cd_column_update.6 = f32[50176,1]{1,0:T(8,128)S(1)} custom-call("
            "f32[50176,54]{1,0:T(8,128)S(1)} %pad.62, f32[50176,1]{1,0} "
            "%copy.30, f32[64,54]{1,0} %fusion.49, f32[64,1]{1,0} %copy.31), "
            'custom_call_target="tpu_custom_call"')
    other = ("%reduce.39 = f32[50176]{0} reduce(f32[50176,1]{1,0} "
             "%cd_column_update.6, f32[] %c), to_apply=%cd_column_update.3")
    ops = [ev(name, 10, 122400), ev(other, 200000, 18000),
           ev(name, 300000, 122400)]
    t = T.Trace.from_events(ops, [], [ev("bench/window", 0, 10**6)])
    inputs = H.LayerInputs(cell=None, counters={}, trace=t,
                           peaks=H.peaks_for("TPU v5 lite"))
    reader = H.load_module(tinyroot.REPO / "bench" / "layer_metrics"
                           / "cd_update_roofline.py")
    # bytes 4*(50176*54 + 2*50176 + 64*54 + 64) = 11,253,504 -> 13.74 us
    # at 819 GB/s, against 122.4 us a call: 11.23%
    got = reader.read(inputs)
    assert abs(got - 100 * 11_253_504 / 819e9 / 122.4e-6) < 1e-9

"""The ``subqp_device_ms`` reader against a synthetic trace worked out by
hand."""
import tinyroot

from bench import harness as H
from bench import trace as T

SWEEP = ("%small_qp_sweep.1 = f32[1,1,128]{2,1,0:T(1,128)S(1)} custom-call("
         "f32[1,128,128]{2,1,0} %pad.7, f32[1,1,128]{2,1,0} %fusion.3, "
         "f32[1,1,128]{2,1,0} %fusion.2, f32[1,1,128]{2,1,0} %fusion.1, "
         "f32[1,1,128]{2,1,0} %fusion), custom_call_target=\"tpu_custom_call\"")
# an op that only consumes the kernel's output, and a loop op of the XLA
# sweep: neither is the kernel
USER = ("%slice.4 = f32[1,1,64]{2,1,0} slice(f32[1,1,128]{2,1,0} "
        "%small_qp_sweep.1), slice={[0:1], [0:1], [0:64]}")
LOOP = "%while.74 = (s32[], f32[64]{0}, f32[64]{0}) while(%tuple.3)"


def ev(name, start, dur):
    return T.Event(name, float(start), float(dur), 0)


def read(ops):
    t = T.Trace.from_events(ops, [], [ev("bench/window", 0, 10**7)])
    inputs = H.LayerInputs(cell=None, counters={}, trace=t,
                           peaks=H.peaks_for("TPU v5 lite"))
    reader = H.load_module(tinyroot.REPO / "bench" / "layer_metrics"
                           / "subqp_device_ms.py")
    return reader.read(inputs)


def test_subqp_device_ms_reads_the_kernel_events():
    # three calls of 21.0, 24.0 and 27.0 us: 0.024 ms a call; the window
    # clips nothing (the third call overlaps its end and counts whole)
    ops = [ev(SWEEP, 100, 21_000), ev(USER, 30_000, 900),
           ev(SWEEP, 40_000, 24_000), ev(LOOP, 70_000, 1_150_000),
           ev(SWEEP, 10**7 - 1_000, 27_000)]
    assert abs(read(ops) - 0.024) < 1e-12


def test_subqp_device_ms_is_none_without_the_kernel():
    # a program whose sweep is an XLA while loop
    assert read([ev(LOOP, 0, 1_150_000), ev(USER, 2_000_000, 900)]) is None

"""Share of the traced slice of the conquer phase (the mix's
``trace_seconds`` from the end of level 1: the refine pass and the start of
the level-0 solve) in which no op ran on the device."""


def read(inputs):
    t = inputs.trace
    if t is None or not len(t.op_start) or inputs.counters.get("early"):
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())

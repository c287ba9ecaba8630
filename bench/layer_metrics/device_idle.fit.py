"""Share of the traced fit (the whole early-stopped fit) in which no op ran
on the device."""


def read(inputs):
    t = inputs.trace
    if t is None or not len(t.op_start) or not inputs.counters.get("early"):
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())

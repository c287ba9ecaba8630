"""Share of the traced serving window in which no op ran on the device."""


def read(inputs):
    t = inputs.trace
    if t is None or not len(t.op_start):
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())

"""Share of the traced serving window in which no op ran on the device
while the engine was not waiting for work: the device's idle time less the
idle time inside the engine's ``serve/idle`` spans (its batch loop waiting
for a request), over the window, in %."""

IDLE = "serve/idle"


def read(inputs):
    t = inputs.trace
    if t is None:
        return None
    waits = t.span_intervals(lambda name: name == IDLE)
    if not len(waits):
        return None
    wait_s = float((waits[:, 1] - waits[:, 0]).sum()) * 1e-9
    idle_s = t.window_s() - t.busy_s()
    idle_waiting_s = wait_s - t.busy_in(lambda name: name == IDLE)
    return 100.0 * (idle_s - idle_waiting_s) / t.window_s()

"""Host milliseconds per served batch in ``serve/sync`` (the engine's
executor thread: the wait for the device and the copy of the labels and
scores to the host), from the program's annotations in the traced
window."""


def read(inputs):
    t = inputs.trace
    if t is None:
        return None
    d = [s.dur for s in t.spans if s.name == "serve/sync"]
    return sum(d) * 1e-6 / len(d) if d else None

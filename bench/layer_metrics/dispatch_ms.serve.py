"""Host milliseconds per served batch in ``serve/dispatch`` (the engine's
executor thread: the bucket's transfer to the device and ``serve_batch`` up
to its return, the scoring program's launch and the eager slice, argmax and
class-gather launches), from the program's annotations in the traced
window."""


def read(inputs):
    t = inputs.trace
    if t is None:
        return None
    d = [s.dur for s in t.spans if s.name == "serve/dispatch"]
    return sum(d) * 1e-6 / len(d) if d else None

"""Host milliseconds per served batch in the engine's hop from its event
loop to the executor thread and back: the summed ``serve/batch`` spans
(loop thread, from the pop to the last result set) minus their parts
``serve/assemble``, ``serve/compute`` (executor thread) and
``serve/resolve``, over the number of batches, from the program's
annotations in the traced window."""

PARTS = ("serve/assemble", "serve/compute", "serve/resolve")


def read(inputs):
    t = inputs.trace
    if t is None:
        return None
    batches = [s.dur for s in t.spans if s.name == "serve/batch"]
    if not batches:
        return None
    parts = sum(s.dur for s in t.spans if s.name in PARTS)
    return (sum(batches) - parts) * 1e-6 / len(batches)

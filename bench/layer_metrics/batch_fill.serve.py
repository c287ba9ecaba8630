"""Mean of the engine's ``serve_batch_fill_ratio`` (real rows over bucket
rows per served batch) over the window, in %."""


def read(inputs):
    v = inputs.counters.get("batch_fill_mean")
    return None if v is None else 100.0 * v

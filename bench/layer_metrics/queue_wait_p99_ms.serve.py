"""99th percentile of the engine's ``serve_queue_wait_seconds`` histogram
(enqueue to batch formation) over the window, in ms."""


def read(inputs):
    v = inputs.counters.get("queue_wait_p99_s")
    return None if v is None else v * 1e3

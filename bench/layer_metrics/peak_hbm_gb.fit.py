"""Peak device memory of the run (``memory_stats()["peak_bytes_in_use"]``
read after the window), in GB."""


def read(inputs):
    b = inputs.counters.get("memory_peak_bytes")
    return None if not b else b / 1e9

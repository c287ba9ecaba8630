"""Level-0 block iterations of the fit (``level_stats[-1]["iters"]``)."""


def read(inputs):
    return inputs.counters.get("conquer_iters")

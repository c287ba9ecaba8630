"""Host seconds per fit in the conquer spans (``conquer/refine`` and
``conquer/solve``: ``dcsvm._solve_subset`` and ``_solve_full``), from the
program's own span tree; the solve ends in ``block_until_ready``."""


def read(inputs):
    sp = inputs.counters.get("spans")
    if not sp or inputs.counters.get("early"):
        return None
    return sum(v for k, v in sp.items() if k.startswith("conquer/"))

"""Host seconds per fit in the cluster-solve spans (``divide/level<l>/solve``:
``dcsvm._solve_clusters`` -> ``core/solver.py``), from the program's own span
tree.  Each ends in ``block_until_ready``, so it holds the device work."""


def read(inputs):
    sp = inputs.counters.get("spans")
    if not sp:
        return None
    return sum(v for k, v in sp.items()
               if k.startswith("divide/") and k.endswith("/solve"))

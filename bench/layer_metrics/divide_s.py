"""Host seconds per fit in the divide spans (``divide/level<l>/cluster``:
two-step kernel kmeans, ``core/kkmeans.py``), from the program's own span
tree.  The span ends after the assignment reaches the host (balanced
assignment runs in NumPy), so it holds the layer's device work too."""


def read(inputs):
    sp = inputs.counters.get("spans")
    if not sp:
        return None
    return sum(v for k, v in sp.items()
               if k.startswith("divide/") and k.endswith("/cluster"))

"""Share of the traced serving window spent in the interpreter's garbage
collector: the union of the program's ``host/gc`` annotations (a
``gc.callbacks`` hook of ``repro.obs.spans``), over the window, in %.  Read
only where the program annotates its engine (``serve/batch``); a window with
no collection there reads 0."""


def read(inputs):
    t = inputs.trace
    if t is None or not any(s.name == "serve/batch" for s in t.spans):
        return None
    iv = t.span_intervals(lambda name: name == "host/gc")
    return 100.0 * float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9 / t.window_s()

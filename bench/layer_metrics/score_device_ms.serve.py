"""Device milliseconds per run of the exact scoring program
(``serve_svm.serve_scores_exact``) in the traced window."""

PROGRAM = "serve_scores_exact"


def read(inputs):
    t = inputs.trace
    if t is None:
        return None
    runs = t.programs(lambda name: PROGRAM in name)
    if not runs:
        return None
    return sum(e.dur for e in runs) * 1e-6 / len(runs)

"""The correctness checks at a test size: sound runs pass the committed
limits, the control fails them, and so does each fault the cells can have.

The control is the fit's own bfloat16 Gram path (``compute_dtype``) for the
fit cells, and the reference computed at bf16_3x in place of the served
scores for the serving cell; the serving control's gap grows with the model,
so at this size it is held to its separation from the program's gap.  The faults are planted under the timed path
(``repro.core.fit``, the engine's ``serve_batch``); the harness runs as it
does on the chip, with its look for a chip skipped.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

import tinyroot


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.tiny_root(tmp_path_factory.mktemp("bench"))


FIT_CELLS = ["covtype-fit-exact", "covtype-fit-early"]


@pytest.mark.parametrize("cell", FIT_CELLS + ["covtype-serve-exact"])
def test_sound_run_is_correct(root, cell):
    line = tinyroot.run(root, cell, seconds=1.0)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("cell", FIT_CELLS)
def test_control_is_not_correct(root, cell):
    line = tinyroot.run(root, cell, seconds=1.0, control=True)
    assert not line["correct"], line["checks"]


def test_serve_control_separates(root):
    # the serving control's gap grows with the model: on the chip at the
    # cell's size it read 0.0528 against the committed limit 0.005 and the
    # program's 1.34e-4; at this size it stays under the limit, so the test
    # holds it to a tenfold separation from the program's own gap
    sound = tinyroot.run(root, "covtype-serve-exact", seconds=1.0)
    ctl = tinyroot.run(root, "covtype-serve-exact", seconds=1.0, control=True)
    gap = lambda line: line["checks"]["score_gap"]["value"]  # noqa: E731
    assert gap(ctl) > 10 * gap(sound), (sound["checks"], ctl["checks"])


def _with_alpha(model, a, y):
    a = jnp.asarray(a, jnp.float32)
    return dataclasses.replace(model, alpha=a, beta=a * jnp.asarray(y))


def _unchanged(fit, model, X, y):
    # a step that returns its state unchanged: the solver's start, alpha=0
    return _with_alpha(model, np.zeros(X.shape[0]), y)


def _half(fit, model, X, y):
    # half of the points left out of the solve
    n = X.shape[0] // 2
    a = np.zeros(X.shape[0], np.float32)
    a[:n] = np.asarray(fit(model.config, X[:n], y[:n]).alpha)
    return _with_alpha(model, a, y)


def _altered(fit, model, X, y):
    # one answer altered where it is produced
    a = np.asarray(model.alpha).copy()
    i = int(np.argmax(a))
    a[i] = 0.0 if a[i] > 0 else model.config.C
    return _with_alpha(model, a, y)


@pytest.mark.parametrize("cell", FIT_CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
def test_fit_fault_is_not_correct(root, monkeypatch, cell, fault):
    import repro.core as core

    real = core.fit

    def broken(cfg, X, y=None, **kw):
        return fault(real, real(cfg, X, y, **kw), X, y)

    monkeypatch.setattr(core, "fit", broken)
    line = tinyroot.run(root, cell, seconds=0.0)
    assert not line["correct"], line["checks"]


def test_early_partition_fault_is_not_correct(root, monkeypatch):
    # the divide step's answer altered where it is produced: a tenth of the
    # points moved to the next cluster
    import repro.core as core

    real = core.fit

    def broken(cfg, X, y=None, **kw):
        model = real(cfg, X, y, **kw)
        p = model.partition
        a = np.asarray(p.assign).copy()
        a[: len(a) // 10] = (a[: len(a) // 10] + 1) % p.k
        return dataclasses.replace(model,
                                   partition=dataclasses.replace(p, assign=a))

    monkeypatch.setattr(core, "fit", broken)
    line = tinyroot.run(root, "covtype-fit-early", seconds=0.0)
    c = line["checks"]["assign_mismatch"]
    assert not line["correct"] and c["value"] > c["limit"], line["checks"]


def _serve_half(pred, scores):
    n = pred.shape[0] // 2
    return pred.at[n:].set(1.0), scores.at[n:].set(0.0)


def _serve_altered(pred, scores):
    return -pred, -scores


@pytest.mark.parametrize("fault", [_serve_half, _serve_altered])
def test_serve_fault_is_not_correct(root, monkeypatch, fault):
    import repro.launch.engine as engine

    real = engine.serve_batch

    def broken(*a, **kw):
        return fault(*real(*a, **kw))

    monkeypatch.setattr(engine, "serve_batch", broken)
    line = tinyroot.run(root, "covtype-serve-exact", seconds=1.0)
    assert not line["correct"], line["checks"]

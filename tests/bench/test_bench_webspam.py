"""The webspam deployment's cell at a test size: it selects the fit cells'
metrics, its generator draws sparse labelled rows from its key, and the
correctness checks hold for it as for the covtype fit cells: a sound run
passes the committed limits, the bf16 control fails them, and so does each
fault planted under ``repro.core.fit``.
"""
import json

import numpy as np
import pytest

import tinyroot
from tinyroot import REPO
from test_bench_control import _altered, _half, _unchanged

from bench import harness as H

CELL = "webspam-fit-exact"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.tiny_root(tmp_path_factory.mktemp("bench"))


def test_webspam_cell_selects_the_exact_fit_metrics():
    wide = H.load_cell(CELL)
    exact = H.load_cell("covtype-fit-exact")
    names = lambda es: {e["name"] for e in es}  # noqa: E731
    assert names(wide.end_to_end()) == {"fit_s", "setup_s"}
    assert "conquer_s" in names(wide.per_layer())
    assert names(wide.per_layer()) == names(exact.per_layer())
    assert wide.kind().__name__.endswith("fit_py")
    assert wide.config["generator"] == "sparse_mixture"
    for e in wide.per_layer():
        assert (REPO / "bench" / "layer_metrics"
                / f"{e['name']}.py").is_file()


def test_sparse_mixture_rows_are_sparse_labelled_and_repeatable():
    from bench import generate as G
    from bench.kinds import fit as F

    cfg = json.loads((REPO / "bench" / "configs"
                      / "webspam-rbf.json").read_text())
    key = F.seed_key(cfg["data_seed"])
    X, y = (np.asarray(a) for a in G.generate(cfg, key, 2000))
    assert X.shape == (2000, 254) and X.dtype == np.float32
    assert X.min() >= 0.0 and X.max() <= 1.0
    assert abs((X != 0).mean() - cfg["density"]) <= 0.02
    assert y.dtype == np.float32 and set(np.unique(y)) == {-1.0, 1.0}
    X2, y2 = (np.asarray(a) for a in G.generate(cfg, key, 2000))
    assert np.array_equal(X, X2) and np.array_equal(y, y2)


def test_webspam_sound_run_is_correct(root):
    line = tinyroot.run(root, CELL, seconds=1.0)
    assert line["correct"], line["checks"]


def test_webspam_control_is_not_correct(root):
    line = tinyroot.run(root, CELL, seconds=1.0, control=True)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
def test_webspam_fit_fault_is_not_correct(root, monkeypatch, fault):
    import repro.core as core

    real = core.fit

    def broken(cfg, X, y=None, **kw):
        return fault(real, real(cfg, X, y, **kw), X, y)

    monkeypatch.setattr(core, "fit", broken)
    line = tinyroot.run(root, CELL, seconds=0.0)
    assert not line["correct"], line["checks"]

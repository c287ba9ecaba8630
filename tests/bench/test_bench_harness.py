"""The harness finds everything by name, and refuses to run off the chip."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tinyroot
from tinyroot import REPO

from bench import harness as H


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        H.peaks_for("TPU v99 imaginary")


def test_v5e_peaks_from_the_table():
    p = H.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_cells_select_their_metrics():
    exact = H.load_cell("covtype-fit-exact")
    early = H.load_cell("covtype-fit-early")
    serve = H.load_cell("covtype-serve-exact")
    names = lambda es: {e["name"] for e in es}  # noqa: E731
    assert names(exact.end_to_end()) == {"fit_s", "setup_s"}
    assert names(serve.end_to_end()) == {"serve_p50_ms", "setup_s"}
    assert "conquer_s" in names(exact.per_layer())
    assert "conquer_s" not in names(early.per_layer())
    assert "batch_fill.serve" in names(serve.per_layer())
    assert exact.kind().__name__.endswith("fit_py")
    for cell in (exact, early, serve):
        for e in cell.per_layer():
            assert (REPO / "bench" / "layer_metrics"
                    / f"{e['name']}.py").is_file()


def test_new_config_mix_and_metric_are_found_as_files(tmp_path):
    root = tinyroot.tiny_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "covtype-rbf.json").read_text())
    cfg.update(name="narrow-rbf", d=54, n_train=400)
    (b / "configs" / "narrow-rbf.json").write_text(json.dumps(cfg))
    (b / "traffic" / "fit_level2.json").write_text(json.dumps(
        {"kind": "fit", "why": "stop after level 2", "early_stop_level": 2,
         "draws": [5]}))
    (b / "limits" / "narrow-fit-level2.json").write_text(
        json.dumps({"kkt": 0.01, "box": 0.0, "assign_mismatch": 0}))
    (b / "layer_metrics" / "support_vectors.py").write_text(
        "def read(inputs):\n    return inputs.counters.get('n_sv')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "narrow-rbf", "source": "test",
                            "file": "bench/configs/narrow-rbf.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "narrow-fit-level2",
                              "config": "narrow-rbf",
                              "traffic": "fit_level2", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("narrow-fit-level2")
    spec["per_layer"].append({"name": "support_vectors", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "cluster solves", "moves": "fit_s",
                              "workloads": ["narrow-fit-level2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    line = tinyroot.run(root, "narrow-fit-level2", trace=False)
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert line["correct"] and line["attempted"] >= 1
    line = tinyroot.run(root, "narrow-fit-level2", trace=True)
    assert line["metrics"]["support_vectors"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] == 1


def _run_cli(cwd, *extra_env):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "covtype-fit-exact",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return all(not ln.startswith("{") for ln in out.splitlines())


def test_cpu_only_process_exits_nonzero_without_a_result():
    r = _run_cli(REPO)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)


def test_serve_schedule_same_work_for_every_seed():
    from bench.kinds import serve as S

    mix = json.loads((REPO / "bench" / "traffic"
                      / "serve_open_loop.json").read_text())
    a = S.schedule(mix, 5.0, 11, pool=1000)
    b = S.schedule(mix, 5.0, 2**31 + 12, pool=1000)
    assert len(a[0]) == len(b[0]) == round(mix["rate_rps"] * 5.0)
    assert sorted(a[1]) == sorted(b[1])            # the same sizes ...
    assert not np.array_equal(a[1], b[1])          # ... in another order
    assert abs(a[0][-1] - b[0][-1]) < 1.0          # the same span of arrivals
    assert a[1].min() >= mix["min_rows"] and a[1].max() <= mix["max_rows"]
    assert all(len(r) == s for r, s in zip(a[2], a[1]))


def test_fit_data_is_one_problem_and_seed_orders_the_held_out_rows():
    from bench.kinds import fit as F

    cfg = json.loads((REPO / "bench" / "configs"
                      / "covtype-rbf.json").read_text())
    cfg["n_train"] = 400
    a = [np.asarray(x) for x in F.make_data(cfg, 3)]
    b = [np.asarray(x) for x in F.make_data(cfg, 2**33 + 3)]
    assert a[0].shape == (400, 54) and a[2].shape == (100, 54)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    key = lambda X: np.lexsort(X.T)  # noqa: E731
    assert np.array_equal(a[2][key(a[2])], b[2][key(b[2])])
    assert not np.array_equal(a[2], b[2])


def test_fit_rounds_are_the_same_draws_for_every_seed():
    from bench.kinds import fit as F

    draws = [0, 1, 2, 3]
    orders = [F.round_order(draws, s) for s in (3, 4, 2**33 + 3, 2**31 + 1)]
    assert all(sorted(o) == draws for o in orders)
    assert len({tuple(o) for o in orders}) > 1
    assert F.round_order(draws, 2**33 + 3) == orders[2]

"""A copy of the benchmark at a size a CPU test run can hold.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``,
links the program's ``src/`` beside them, and cuts the configurations and
the mixes to a few hundred points and requests and two draws a round.  Limits stay as
committed, so a test that sees a control fail sees the committed limit fail
it.
"""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_CONFIG = {"n_train": 2000, "levels": 2, "m": 200}
TINY_SERVE = {"rate_rps": 40, "check_requests": 40, "grace_s": 10}
TINY_FIT = {"draws": [0, 1]}


def tiny_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    for p in (root / "bench" / "configs").glob("*.json"):
        c = json.loads(p.read_text())
        c.update(TINY_CONFIG)
        p.write_text(json.dumps(c))
    for p in (root / "bench" / "traffic").glob("*.json"):
        m = json.loads(p.read_text())
        m.update(TINY_SERVE if m["kind"] == "serve" else TINY_FIT)
        p.write_text(json.dumps(m))
    return root


def run(root: Path, workload: str, seed: int = 5, seconds: float = 0.0,
        trace: bool = False, control: bool = False) -> dict:
    """One run of a cell with the look for a chip skipped."""
    import time

    from bench.run import run_cell

    return run_cell(workload, seed, seconds, trace, root=root,
                    chip_check=False, control=control,
                    t_start=time.perf_counter())

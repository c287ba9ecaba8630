#!/usr/bin/env python3
"""Readings for setting a cell's correctness limits, in one process.

    python bench/tools/readings.py --workload covtype-fit-exact \
        --seeds 11 12 13 --control-seeds 101 102 103 [--seconds 0]

Runs the cell as ``bench/run.py`` does (on the chip, the same checks) once
per seed, then once per control seed with the control in the program's
place, and prints one JSON line per run: the numbers compared, the
end-to-end metrics and ``correct``.  The lower reading of a number is the
largest over the program's seeds, the upper the smallest over the control's.
Lines also go to ``chiprun_out/readings/<workload>.jsonl``.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.run import run_cell

    out = ROOT / "chiprun_out" / "readings"
    out.mkdir(parents=True, exist_ok=True)
    runs = [(s, False) for s in args.seeds] + [(s, True)
                                              for s in args.control_seeds]
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed, control in runs:
            t0 = time.perf_counter()
            try:
                line = run_cell(args.workload, seed, args.seconds,
                                bool(args.trace), control=control, t_start=t0)
                rec = {"seed": seed, "control": control,
                       "correct": line["correct"], "checks": line["checks"],
                       "metrics": line["metrics"],
                       "attempted": line["attempted"],
                       "failed": line["failed"], "device": line["device"],
                       "wall_s": time.perf_counter() - t0}
                if "breakdown" in line:
                    rec["breakdown"] = line["breakdown"]
            except Exception as e:  # noqa: BLE001 - a crashed run is a reading
                rec = {"seed": seed, "control": control, "error": repr(e),
                       "wall_s": time.perf_counter() - t0}
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

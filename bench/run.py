#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python bench/run.py --workload covtype-fit-exact --seed 7 --seconds 51 --trace 0

Run from the root of a checkout on the machine that holds the chips the cell
asks for.  Set-up (data, compiles, warm-up) is timed as
``setup_s``; then the cell's traffic runs for ``--seconds``; then the answers
the window produced are compared with the plain references.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from a profiler trace of the window.  The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, [``breakdown``], ``checks``); the numbers compared, each with its
limit, are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the compile cache lives at a fixed path inside the checkout; the program's
# own enable_compile_cache() takes it from this variable
CACHE_DIR = ROOT / ".jax_cache"


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, chip_check: bool = True,
             control: bool = False,
             t_start: float = None) -> dict:
    """One run of one cell; returns the result line.  ``chip_check=False``
    skips the look for a TPU (the CPU tests drive the rest of a run)."""
    import jax

    from bench import harness as H

    cell = H.load_cell(workload, root)
    devices = jax.devices()
    if chip_check:
        if devices[0].platform != "tpu":
            raise H.NoChip(f"JAX found no TPU (platform "
                           f"{devices[0].platform!r}); the benchmark runs "
                           "on the chip only")
        if len(devices) < cell.workload["chips"]:
            raise H.NoChip(f"{workload} needs {cell.workload['chips']} "
                           f"chips, JAX found {len(devices)}")
        H.peaks_for(devices[0].device_kind, root)
    devices = devices[: cell.workload["chips"]]
    ctx = H.RunContext(cell, seed, seconds, trace,
                       t_start=T_START if t_start is None else t_start,
                       control=control)
    out = cell.kind().run(ctx)
    shown = {k: v for k, v in out.counters.items() if k != "spans"}
    print(f"counters: {json.dumps(shown)}", file=sys.stderr, flush=True)
    return H.result_line(cell, ctx, out, devices)


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    args = _parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under {ROOT / 'src'}: run from a whole checkout",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench.harness import NoChip

    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 3
    print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

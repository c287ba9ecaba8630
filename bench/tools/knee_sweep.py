#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell to find the engine's knee.

    python bench/tools/knee_sweep.py --workload covtype-serve-exact \
        --seed 7 --rates 100 200 300 400 500 600 700 800 --seconds 15 \
        --repeat-seconds 30 51 --repeats 3

Sets the cell up once (data, fit, engine warm-up), then runs its open loop
at each rate in turn and prints one JSON line per rate: p50/p99 latency
timed from each request's due time, failures, how late the generator ran,
and the backlog trend (median latency of the last tenth of requests over the
first tenth).  The unloaded level is the p99 at the lowest rate; the knee is
the highest rate up to which no p99 is above twice it.  With
``--repeat-seconds`` the open loop then runs ``--repeats`` windows of each
length at 0.8 of the knee (rounded to 10 requests/s), each with its own
seed, and prints the quartile spread of p50 and p99 over them.  Lines also
go to ``chiprun_out/knee/<workload>.jsonl``.
"""
import argparse
import json
import os
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--repeat-seconds", type=float, nargs="*", default=[])
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import numpy as np
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness as H
    from bench.kinds import serve as S

    cell = H.load_cell(args.workload, ROOT)
    srv = S.Server(cell.config, cell.mix, args.seed)
    out = ROOT / "chiprun_out" / "knee"
    out.mkdir(parents=True, exist_ok=True)
    f = open(out / f"{args.workload}.jsonl", "a")

    def window(rate, seconds, seed):
        mix = {**cell.mix, "rate_rps": rate}
        due, sizes, rows = S.schedule(mix, seconds, seed, len(srv.pool))
        res = srv.window(due, rows, grace_s=30.0)
        lat = res["lat"] * 1e3
        tenth = max(1, len(lat) // 10)
        rec = {"rate_rps": rate, "seconds": seconds, "seed": seed,
               "requests": len(due),
               "rows_per_s": float(sizes.sum() / seconds),
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "failed": int(res["failed"].sum()),
               "late_p99_ms": float(np.percentile(res["late"], 99) * 1e3),
               "backlog_trend": float(np.median(lat[-tenth:])
                                      / np.median(lat[:tenth])),
               "batch_fill": srv.counters()["batch_fill_mean"]}
        print(json.dumps(rec), flush=True)
        f.write(json.dumps(rec) + "\n")
        return rec

    sweep = [window(r, args.seconds, args.seed + i)
             for i, r in enumerate(sorted(args.rates))]
    unloaded = sweep[0]["p99_ms"]
    knee = sweep[0]["rate_rps"]
    for rec in sweep:
        if rec["p99_ms"] > 2.0 * unloaded:
            break
        knee = rec["rate_rps"]
    rate = max(10.0, 10.0 * round(0.8 * knee / 10.0))
    summary = {"unloaded_p99_ms": unloaded, "knee_rps": knee,
               "cell_rate_rps": rate}
    for seconds in args.repeat_seconds:
        recs = [window(rate, seconds, args.seed + 1000 + j)
                for j in range(args.repeats)]
        for key in ("p50_ms", "p99_ms"):
            v = [r[key] for r in recs]
            q = statistics.quantiles(v, n=4)
            summary[f"spread_{key}_{seconds:g}s"] = ((q[2] - q[0])
                                                     / statistics.median(v))
    print(json.dumps(summary), flush=True)
    f.write(json.dumps(summary) + "\n")
    f.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

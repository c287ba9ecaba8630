"""DC-SVM end-to-end training driver (the paper's workload, all tasks).

    PYTHONPATH=src python -m repro.launch.train_svm --n 20000 --levels 3 \
        --dataset covtype_like --ckpt-dir /tmp/dcsvm_ckpt
    PYTHONPATH=src python -m repro.launch.train_svm --task svr \
        --dataset friedman1 --eps 0.1
    PYTHONPATH=src python -m repro.launch.train_svm --task weighted-svc \
        --dataset imbalanced --class-weight 20
    PYTHONPATH=src python -m repro.launch.train_svm --task one-class \
        --dataset outliers --nu 0.1
    PYTHONPATH=src python -m repro.launch.train_svm --task nu-svc --nu 0.3

Tasks: ``svc`` (hinge C-SVC), ``weighted-svc`` (cost-sensitive box
``c_i = C * w_{y_i}``; ``--class-weight POS[,NEG]``), ``svr``
(epsilon-insensitive regression; ``--eps``), ``nu-svc`` (nu-parameterized
classification; ``--nu`` bounds the support mass, ``--nu-bias`` restores
the bias term via the two-constraint dual solved per label group) and
``one-class`` (label-free anomaly detection via the equality-constrained
dual; ``--nu`` bounds the outlier fraction).  Regression reports MSE/MAE,
weighted classification additionally reports per-class recall, one-class
reports outlier precision/recall/F1 against the generator's ground-truth
labels.  ``--eq-block B`` runs the equality-family conquer with the
rank-2B blocked pairwise engine (B maximal-violating pairs per iteration;
1 = the paper-faithful SMO-style rank-2 engine).

Fault tolerance: after every level the (alpha, level, assign) state is
checkpointed; restart resumes at the next level (the expensive bottom levels
are never recomputed).  With --distributed the divide/conquer steps run
shard_mapped over all local devices: the conquer defaults to parallel block
minimization (every device solves its own top-B block per communication
round, --dist-mode replicated recovers the one-global-block baseline) and
covers svc, weighted-svc and svr through the generalized TaskDual path.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt import CheckpointManager
from repro.core import (
    DCSVMConfig, EpsilonSVR, Kernel, NuSVC, OneClassSVM, WeightedCSVC,
    accuracy, f1, fit, mae, mse, precision, predict_early, predict_exact,
    recall,
)
from repro.core.dcsvm import DCSVMModel
from repro.launch.compile_cache import enable_compile_cache
from repro.data import (
    checkerboard, covtype_like, friedman1, gaussian_mixture,
    gaussian_mixture_imbalanced, gaussian_with_outliers, sinc1d,
    stratified_split, train_test_split, webspam_like,
)

DATASETS = {
    "covtype_like": covtype_like,
    "webspam_like": webspam_like,
    "checkerboard": lambda k, n: checkerboard(k, n, cells=4),
    "gaussian": lambda k, n: gaussian_mixture(k, n, d=16, modes_per_class=8),
    "imbalanced": lambda k, n: gaussian_mixture_imbalanced(k, n, d=10),
    "outliers": gaussian_with_outliers,
    "sinc1d": sinc1d,
    "friedman1": friedman1,
}
REGRESSION_DATASETS = {"sinc1d", "friedman1"}
ONECLASS_DATASETS = {"outliers"}


def parse_class_weight(spec: str):
    """"POS" or "POS,NEG" -> (w_pos, w_neg)."""
    parts = [float(v) for v in spec.split(",") if v]
    if len(parts) == 1:
        return parts[0], 1.0
    if len(parts) == 2:
        return parts[0], parts[1]
    raise ValueError(f"--class-weight expects POS[,NEG], got {spec!r}")


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="svc",
                    choices=["svc", "weighted-svc", "svr", "nu-svc",
                             "one-class"])
    ap.add_argument("--dataset", default="gaussian", choices=sorted(DATASETS))
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--C", type=float, default=4.0)
    ap.add_argument("--gamma", type=float, default=8.0)
    ap.add_argument("--kernel", default="rbf", choices=["rbf", "poly", "linear"])
    ap.add_argument("--class-weight", default="10",
                    help="weighted-svc cost multipliers POS[,NEG] on top of C")
    ap.add_argument("--eps", type=float, default=0.1,
                    help="epsilon-SVR insensitivity tube half-width")
    ap.add_argument("--nu", type=float, default=0.1,
                    help="nu-svc / one-class support-mass bound in (0, 1]")
    ap.add_argument("--nu-bias", action="store_true",
                    help="nu-svc only: restore the bias term (two-constraint "
                         "dual, solved per label group)")
    ap.add_argument("--eq-block", type=int, default=1,
                    help="equality-family rank-2B block size B (pairs per "
                         "outer iteration); 1 = rank-2 pairwise engine")
    ap.add_argument("--levels", type=int, default=3)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--m", type=int, default=1000)
    ap.add_argument("--tol", type=float, default=1e-3)
    ap.add_argument("--block", type=int, default=0)
    ap.add_argument("--early", type=int, default=0,
                    help="stop at this level and use early prediction")
    ap.add_argument("--distributed", action="store_true",
                    help="shard the divide/conquer over all local devices "
                         "(svc, weighted-svc and svr; force host devices "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--dist-mode", default="parallel",
                    choices=["parallel", "replicated"],
                    help="conquer scheme: 'parallel' = P simultaneous local "
                         "block solves per communication round (CE-PBM), "
                         "'replicated' = one global block per round")
    ap.add_argument("--dist-cache", type=int, default=0,
                    help="per-device kernel-row LRU capacity for the "
                         "parallel conquer (0 = recompute rows on the fly)")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="Gram matmul-operand precision (accumulation stays "
                         "f32); float32 keeps the bit-exact default paths")
    ap.add_argument("--host-spill", action="store_true",
                    help="level-0 out-of-core solve: kernel-row panels live "
                         "in host RAM, a device LRU holds the working set "
                         "within --gram-budget bytes")
    ap.add_argument("--gram-budget", type=int, default=0,
                    help="byte budget for Gram storage tiers (0 = default)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="write a Chrome trace-event JSON of the fit's span "
                         "tree (divide/conquer phases) to this path and "
                         "print the aggregated span table; load in Perfetto "
                         "or chrome://tracing")
    ap.add_argument("--trace-cap", type=int, default=0,
                    help="device-resident convergence-trace ring capacity "
                         "for the level-0 solve (keeps the LAST N "
                         "per-iteration samples; 0 = tracing off, solver "
                         "jaxprs bit-identical to the untraced build)")
    ap.add_argument("--stats-json", default="",
                    help="dump per-level training stats (times, SV counts, "
                         "cache counters, convergence traces) as JSON")
    args = ap.parse_args(argv)

    is_reg = args.dataset in REGRESSION_DATASETS
    if (args.task == "svr") != is_reg:
        ap.error(f"--task {args.task} needs a "
                 f"{'regression' if args.task == 'svr' else 'classification'} "
                 f"dataset; --dataset {args.dataset} is not one "
                 f"(regression: {sorted(REGRESSION_DATASETS)})")
    if args.task == "one-class" and args.dataset not in ONECLASS_DATASETS:
        ap.error(f"--task one-class needs a dataset with inlier/outlier "
                 f"ground truth for evaluation: {sorted(ONECLASS_DATASETS)}; "
                 f"got --dataset {args.dataset}")

    task = None
    if args.task == "weighted-svc":
        w_pos, w_neg = parse_class_weight(args.class_weight)
        task = WeightedCSVC(w_pos=w_pos, w_neg=w_neg)
    elif args.task == "svr":
        task = EpsilonSVR(eps=args.eps)
    elif args.task == "nu-svc":
        task = NuSVC(nu=args.nu, with_bias=args.nu_bias)
    elif args.task == "one-class":
        task = OneClassSVM(nu=args.nu)
    if args.nu_bias and args.task != "nu-svc":
        ap.error("--nu-bias applies to --task nu-svc only")

    key = jax.random.PRNGKey(args.seed)
    X, y = DATASETS[args.dataset](key, args.n)
    split = stratified_split if args.dataset == "imbalanced" else train_test_split
    Xtr, ytr, Xte, yte = split(jax.random.fold_in(key, 1), X, y)
    kern = Kernel(args.kernel, gamma=args.gamma)
    extra = {}
    if args.compute_dtype != "float32":     # float32 = the bit-exact default
        extra["compute_dtype"] = args.compute_dtype
    if args.gram_budget > 0:
        extra["gram_budget"] = args.gram_budget
    if args.trace_cap > 0:
        extra["trace"] = args.trace_cap
    cfg = DCSVMConfig(kernel=kern, C=args.C, k=args.k, levels=args.levels,
                      m=args.m, tol=args.tol, block=args.block,
                      eq_block_size=args.eq_block,
                      early_stop_level=args.early, seed=args.seed,
                      host_spill=args.host_spill, **extra)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    tracer = None
    span_ctx = contextlib.nullcontext()
    if args.trace:
        from repro.obs.spans import SpanTracer
        tracer = SpanTracer()
        span_ctx = tracer.activate()

    t0 = time.perf_counter()
    with span_ctx:
        model = _train(args, cfg, task, Xtr, ytr, mgr)
    t_train = time.perf_counter() - t0

    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        print(f"chrome trace -> {args.trace}", flush=True)
        print(tracer.summary(), flush=True)
    if args.stats_json:
        payload = {"task": args.task, "dataset": args.dataset,
                   "n": int(Xtr.shape[0]), "train_time": t_train,
                   "levels": model.level_stats}
        with open(args.stats_json, "w") as f:
            json.dump(payload, f, indent=1, default=_json_default)
        print(f"stats -> {args.stats_json}", flush=True)
    _evaluate(args, model, Xte, yte, Xtr, t_train)
    if mgr is not None:
        mgr.wait()


def _json_default(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.ndarray, jax.Array)):
        return np.asarray(v).tolist()
    raise TypeError(f"not JSON-serializable: {type(v)!r}")


def _train(args, cfg, task, Xtr, ytr, mgr) -> DCSVMModel:
    def cb(level, alpha, st):
        print(f"level {level}: clusters={st.get('clusters', 1)} "
              f"n_sv={st['n_sv']} cluster_t={st.get('cluster_time', 0):.1f}s "
              f"train_t={st['train_time']:.1f}s", flush=True)
        if mgr is not None:
            mgr.save(cfg.levels - level + 1,
                     {"alpha": alpha, "level": jnp.asarray(level)},
                     blocking=False)

    if args.distributed:
        if args.task in ("nu-svc", "one-class"):
            raise SystemExit(
                "--distributed covers the box-constrained duals (svc, "
                "weighted-svc, svr); the equality-constrained tasks "
                f"({args.task}) need the pairwise engine — drop "
                "--distributed")
        from repro.core.distributed import fit_distributed_model
        from repro.launch.mesh import make_conquer_mesh
        mesh = make_conquer_mesh("i")
        model = fit_distributed_model(
            cfg, mesh, "i", Xtr, ytr, task=task,
            conquer_block=max(args.block, 64),
            mode=args.dist_mode, cache_cap=args.dist_cache)
        for st in model.level_stats:
            print({k: v for k, v in st.items() if k != "trace"}, flush=True)
        return model
    return fit(cfg, Xtr, ytr, callback=cb, task=task)


def _evaluate(args, model: DCSVMModel, Xte, yte, Xtr, t_train: float) -> None:
    if model.is_early:
        pred = predict_early(model, Xte)
        mode = f"early prediction (level {args.early})"
    else:
        pred = predict_exact(model, Xte)
        mode = "exact"
    n_sv = len(model.sv_index)
    if args.task == "svr":
        metrics = f"test mse {mse(yte, pred):.5f} mae {mae(yte, pred):.5f}"
    elif args.task == "one-class":
        metrics = (f"outlier recall {recall(yte, pred, -1.0):.4f} "
                   f"precision {precision(yte, pred, -1.0):.4f} "
                   f"f1 {f1(yte, pred, -1.0):.4f} | "
                   f"pred outlier rate {float(np.mean(np.asarray(pred) < 0)):.4f} "
                   f"(nu={args.nu}) rho={model.rho:.4f}")
    else:
        metrics = f"test acc {accuracy(yte, pred):.4f}"
        if args.task == "weighted-svc":
            metrics += (f" | recall +1 {recall(yte, pred, 1.0):.4f}"
                        f" -1 {recall(yte, pred, -1.0):.4f}")
    print(f"done in {t_train:.1f}s | {mode} | {metrics} | "
          f"SVs {n_sv}/{Xtr.shape[0]}", flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Bring-up check of the DC-SVM train -> serve path on a TPU.

    python chip_smoke.py             # one chip: kernels, fit, check, serve
    python chip_smoke.py --chips 4   # sharded fit on four chips vs one chip

Deployment: the paper's covtype workload at its published width — binary
RBF C-SVC, d=54, k=4, l_max=4, m=1000, C=8, gamma=1, tol=1e-3 — on
``covtype_like`` data made from ``--seed``.  The paper's n=464,810 is cut to
n_train=100,000 (the line starting ``cut:`` says why).

Every phase prints its wall and compile seconds.  Any failure exits
non-zero and prints no result; on success the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
There is no fallback: a process whose JAX finds no TPU fails at once.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (  # noqa: E402
    DCSVMConfig, Kernel, accuracy, decision_early, decision_exact, fit,
    predict_early, predict_exact, resolve_use_pallas,
)
from repro.core.kernels import gram  # noqa: E402
from repro.data import covtype_like, train_test_split  # noqa: E402
from repro.kernels import ops  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST
PAPER_N = 464_810           # covtype training points in the paper
N_TRAIN = 100_000           # the largest n_train the code holds today
N_PARITY = 16_384           # largest n with a dense level-0 Gram
KERNEL_TOL = 1e-4           # max |kernel - float64 host reference|
OBJ_TOL = 1e-3              # relative dual-objective agreement
AGREE_MIN = 0.995           # Pallas vs XLA prediction agreement
ACC_POINTS = 0.005          # sharded vs one-chip test accuracy


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# ---------------------------------------------------------------------------
# compile accounting and phase timing
# ---------------------------------------------------------------------------

class CompileMeter:
    """Seconds spent tracing, lowering and compiling (or fetching from the
    persistent cache), the number of executables built, and each phase's
    wall and compile seconds."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.executables = 0
        self.phases = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.executables += event == self.EVENTS[-1]

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.seconds
        print(f"== {name}", flush=True)
        yield
        wall, comp = time.perf_counter() - t0, self.seconds - c0
        self.phases[name] = (wall, comp)
        print(f"== {name}: {wall:.1f} s wall, {comp:.1f} s compile",
              flush=True)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# independent references: float64 on the host, HIGHEST-precision XLA
# ---------------------------------------------------------------------------

def rbf64(A: np.ndarray, B: np.ndarray, gamma: float) -> np.ndarray:
    """RBF kernel in float64 on the host (the expansion's cancellation is
    ~1e-16 relative there)."""
    A, B = np.asarray(A, np.float64), np.asarray(B, np.float64)
    sq = (A * A).sum(1)[:, None] + (B * B).sum(1)[None, :] - 2.0 * A @ B.T
    return np.exp(-gamma * np.maximum(sq, 0.0))


def reference_matvec(gamma: float, X, Z, v, rows: int = 2048) -> np.ndarray:
    """K(X, Z) @ v for the RBF kernel, streamed in row chunks through XLA at
    HIGHEST precision — independent of the solver's kernels and its
    maintained gradient.  Returns float64 on the host."""
    n, d = X.shape
    rows = min(rows, n)
    pad = (-n) % rows

    @jax.jit
    def run(Xp, Z, v):
        zz = jnp.sum(Z * Z, axis=-1)

        def chunk(Xc):
            g = jnp.matmul(Xc, Z.T, precision=HIGHEST)
            xx = jnp.sum(Xc * Xc, axis=-1)
            sq = jnp.maximum(xx[:, None] + zz[None, :] - 2.0 * g, 0.0)
            return jnp.matmul(jnp.exp(-gamma * sq), v, precision=HIGHEST)

        return jax.lax.map(chunk, Xp.reshape(-1, rows, d)).reshape(-1)

    Xp = jnp.pad(jnp.asarray(X), ((0, pad), (0, 0)))
    out = run(Xp, jnp.asarray(Z), jnp.asarray(v, jnp.float32))
    return np.asarray(out, np.float64)[:n]


def dual_check(gamma: float, C: float, X, y, alpha) -> dict:
    """Dual objective and projected-gradient KKT residual of the C-SVC dual
    at ``alpha``, from a fresh reference matvec."""
    a = np.asarray(alpha, np.float64)
    yv = np.asarray(y, np.float64)
    g = yv * reference_matvec(gamma, X, X, yv * a) - 1.0
    pg = np.where(a <= 0.0, np.minimum(g, 0.0), g)
    pg = np.where(a >= C, np.maximum(g, 0.0), pg)
    return {"objective": float(0.5 * a @ (g + 1.0) - a.sum()),
            "kkt": float(np.abs(pg).max())}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise Failed(f"JAX found no TPU (platform {platform!r}); this check "
                     "runs on the chip only")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"JAX found {len(devs)}")
    return devs


def make_data(n_train: int, seed: int):
    """covtype_like, split 80/20 as ``train_svm`` splits it."""
    n = -(-n_train * 5 // 4)
    key = jax.random.PRNGKey(seed)
    X, y = covtype_like(key, n)
    Xtr, ytr, Xte, yte = train_test_split(jax.random.fold_in(key, 1), X, y)
    check(Xtr.shape[0] == n_train, f"split gave {Xtr.shape[0]} rows")
    return Xtr, ytr, Xte, yte


def make_config(use_pallas=None) -> DCSVMConfig:
    """The config ``train_svm`` builds for ``--dataset covtype_like --C 8
    --gamma 1 --k 4 --levels 4 --m 1000 --tol 1e-3``."""
    return DCSVMConfig(kernel=Kernel("rbf", gamma=1.0), C=8.0, k=4,
                       levels=4, m=1000, tol=1e-3, block=0, eq_block_size=1,
                       early_stop_level=0, seed=0, host_spill=False,
                       use_pallas=use_pallas)


def check_kernels(X, gamma: float, rows: int = 4096, m: int = 1000,
                  k: int = 4, B: int = 64, seed: int = 0) -> dict:
    """The four Pallas kernels at the data's width against float64."""
    rng = np.random.default_rng(seed)
    Xh = np.asarray(X[:rows], np.float32)
    Yh = Xh[rng.permutation(rows)[: rows // 2]]
    kern = Kernel("rbf", gamma=gamma)
    K64 = rbf64(Xh, Yh, gamma)
    v = rng.standard_normal(Yh.shape[0]).astype(np.float32)
    s = np.sign(rng.standard_normal(rows)).astype(np.float32)
    Xb = Xh[rng.choice(rows, B, replace=False)]
    w = rng.standard_normal(B).astype(np.float32)
    Xm = Xh[rng.choice(rows, m, replace=False)]
    W = rng.random((m, k)).astype(np.float32)
    W /= W.sum(0, keepdims=True)
    sc = rng.random(k).astype(np.float32)

    err = {}
    err["kernel_matrix"] = np.abs(np.asarray(ops.kernel_matrix(
        jnp.asarray(Xh), jnp.asarray(Yh), kern)) - K64).max()
    err["kernel_matvec"] = np.abs(np.asarray(ops.kernel_matvec(
        jnp.asarray(Xh), jnp.asarray(Yh), jnp.asarray(v), kern))
        - K64 @ v.astype(np.float64)).max()
    err["cd_column_update"] = np.abs(np.asarray(ops.cd_column_update(
        jnp.asarray(Xh), jnp.asarray(s), jnp.asarray(Xb), jnp.asarray(w),
        kern)) - s * (rbf64(Xh, Xb, gamma) @ w.astype(np.float64))).max()
    assign, scores = ops.kmeans_assign(jnp.asarray(Xh), jnp.asarray(Xm),
                                       jnp.asarray(W), jnp.asarray(sc), gamma)
    ref = -2.0 * rbf64(Xh, Xm, gamma) @ W.astype(np.float64) + sc
    err["kmeans_assign"] = np.abs(np.asarray(scores) - ref).max()
    srt = np.sort(ref, axis=1)
    clear = srt[:, 1] - srt[:, 0] > 2 * KERNEL_TOL    # not a near-tie
    wrong = int(np.sum((np.asarray(assign) != ref.argmin(1)) & clear))
    for name, e in err.items():
        print(f"kernel {name}: max abs err vs float64 {e:.3e}", flush=True)
    print(f"kernel kmeans_assign: {wrong} wrong assignments outside "
          f"near-ties", flush=True)
    check(all(e <= KERNEL_TOL for e in err.values()),
          f"kernel error above {KERNEL_TOL}: {err}")
    check(wrong == 0, f"{wrong} kmeans assignments differ from float64")
    return {k2: float(e) for k2, e in err.items()}


def check_gram_is_pallas(cfg: DCSVMConfig, X) -> None:
    """The fit's Gram program must contain the compiled Pallas kernel: no
    interpret mode, no silent XLA replacement."""
    up = resolve_use_pallas(cfg.use_pallas)
    check(up, "resolve_use_pallas picked the XLA path on the chip")
    hlo = gram.lower(cfg.kernel, X[:1024], X[:1024], use_pallas=up,
                     compute_dtype=cfg.compute_dtype).compile().as_text()
    check("tpu_custom_call" in hlo, "Gram program has no tpu_custom_call")
    print("gram program: tpu_custom_call present", flush=True)


def train(cfg: DCSVMConfig, Xtr, ytr, meter: CompileMeter):
    """``repro.core.fit`` with per-level clusters, SVs, wall and compile."""
    last = [time.perf_counter(), meter.seconds]

    def cb(level, alpha, st):
        now = time.perf_counter()
        print(f"level {level}: clusters={st.get('clusters', 1)} "
              f"n_sv={st['n_sv']} wall={now - last[0]:.1f}s "
              f"compile={meter.seconds - last[1]:.1f}s"
              + (f" iters={st['iters']} pg_max={st['pg_max']:.2e}"
                 if level == 0 else ""), flush=True)
        last[:] = [now, meter.seconds]

    return fit(cfg, Xtr, ytr, callback=cb)


def check_solution(cfg: DCSVMConfig, model, Xtr, ytr, Xte, yte) -> dict:
    """KKT residual and objective from a fresh reference matvec, plus test
    accuracy of exact and early prediction.  Early prediction of the exact
    model scores the final alpha against the routed level-1 cluster only;
    the paper's eq. 11 uses the level-1 alpha, i.e. ``train_svm --early 1``,
    which is fit here too."""
    out = dual_check(cfg.kernel.gamma, cfg.C, model.X, model.y, model.alpha)
    out["acc_exact"] = accuracy(yte, predict_exact(model, Xte))
    out["acc_early"] = accuracy(yte, predict_early(model, Xte))
    early = fit(dataclasses.replace(cfg, early_stop_level=1), Xtr, ytr)
    out["acc_early_level1_model"] = accuracy(yte, predict_early(early, Xte))
    print(f"solution: kkt={out['kkt']:.3e} (tol {cfg.tol}) "
          f"objective={out['objective']:.6f} acc_exact={out['acc_exact']:.4f}"
          f" acc_early={out['acc_early']:.4f} acc_early of the level-1 "
          f"model={out['acc_early_level1_model']:.4f}", flush=True)
    check(out["kkt"] <= 2 * cfg.tol,
          f"KKT residual {out['kkt']:.3e} > 2*tol")
    return out


def check_two_impls(n_train: int, seed: int) -> dict:
    """Pallas and XLA fits of the same problem agree."""
    Xtr, ytr, Xte, _ = make_data(n_train, seed + 1)
    objs, preds = [], []
    for up in (True, False):
        cfg = make_config(use_pallas=up)
        model = fit(cfg, Xtr, ytr)
        objs.append(dual_check(cfg.kernel.gamma, cfg.C, Xtr, ytr,
                               model.alpha)["objective"])
        preds.append(np.sign(np.asarray(decision_exact(model, Xte))))
    rel = abs(objs[0] - objs[1]) / abs(objs[1])
    agree = float(np.mean(preds[0] == preds[1]))
    print(f"pallas vs xla at n_train={n_train}: objective {objs[0]:.6f} vs "
          f"{objs[1]:.6f} (rel {rel:.2e}), prediction agreement "
          f"{agree:.5f}", flush=True)
    check(rel <= OBJ_TOL, f"Pallas/XLA objectives differ by {rel:.2e}")
    check(agree >= AGREE_MIN, f"Pallas/XLA predictions agree {agree:.4f}")
    return {"rel_objective": rel, "agreement": agree}


def check_serving(model, Xpool, meter: CompileMeter, n_requests: int = 48,
                  seed: int = 0) -> dict:
    """Export -> registry -> async engine -> warmup, then a few dozen
    requests of 1-64 rows per strategy; served labels must equal the
    training-side predictions and nothing may compile after warmup."""
    from repro.launch.engine import AsyncServingEngine, EngineConfig
    from repro.launch.registry import ModelRegistry

    part = model.partition
    w = np.asarray(model.weights)
    max_sv = max(int(np.sum(w[part.idx[c][part.mask[c]]] != 0))
                 for c in range(part.k))
    registry = ModelRegistry()
    registry.register("covtype", model, with_bcm=False,
                      max_sv_per_cluster=max(max_sv, 1))
    engine = AsyncServingEngine(registry, EngineConfig(max_batch=256))

    rng = np.random.default_rng(seed)
    pool = np.asarray(Xpool)
    sizes = rng.integers(1, 65, size=n_requests)
    reqs = [pool[rng.integers(0, pool.shape[0], size=s)] for s in sizes]
    Xall = jnp.asarray(np.concatenate(reqs))
    refs = {"exact": np.asarray(decision_exact(model, Xall)),
            "early": np.asarray(decision_early(model, Xall))}
    warm = engine.warmup(strategies=list(refs))
    c0 = meter.executables

    async def drive():
        async with engine:
            return await asyncio.gather(
                *[engine.submit(X, "covtype", strategy=s)
                  for s in refs for X in reqs], return_exceptions=True)

    results = asyncio.run(drive())
    compiled = meter.executables - c0
    errors = [r for r in results if isinstance(r, BaseException)]
    check(not errors, f"{len(errors)} requests failed: {errors[:3]!r}")
    out = {"requests": len(results), "warmup_compiles": warm,
           "compiles_after_warmup": compiled,
           "engine_compiles_after_warmup":
               engine.stats()["compiles_after_warmup"]}
    it = iter(results)
    for strat, d in refs.items():
        pred = np.concatenate([np.asarray(next(it)[0]) for _ in reqs])
        mism = pred != np.sign(d)
        out[f"{strat}_mismatches"] = int(mism.sum())
        out[f"{strat}_min_abs_decision_at_mismatch"] = (
            float(np.abs(d[mism]).min()) if mism.any() else None)
    print(f"serving: {out}", flush=True)
    check(out["exact_mismatches"] == 0 and out["early_mismatches"] == 0,
          "served labels differ from predict_exact/predict_early")
    check(compiled == 0 and out["engine_compiles_after_warmup"] == 0,
          f"serving compiled {compiled} executables after warmup")
    return out


def check_sharded(devs, Xtr, ytr, Xte, yte, meter: CompileMeter) -> dict:
    """``fit_distributed_model`` over a 4-chip mesh against the one-chip
    ``fit`` of the same data and config."""
    from repro.core.distributed import fit_distributed_model
    from repro.launch.mesh import make_conquer_mesh

    cfg = make_config()
    mesh = make_conquer_mesh("i", devs[:4])
    with meter.phase("sharded fit (4 chips)"):
        model_d = fit_distributed_model(cfg, mesh, "i", Xtr, ytr,
                                        conquer_block=64)
        jax.block_until_ready(model_d.alpha)
        for st in model_d.level_stats:
            print({k: v for k, v in st.items() if k != "trace"}, flush=True)
    peaks = [peak_bytes(d) for d in devs[:4]]
    with meter.phase("one-chip fit"):
        model_1 = train(cfg, Xtr, ytr, meter)
    with meter.phase("compare"):
        yt = np.asarray(yte)
        res = {}
        for name, mdl in (("sharded", model_d), ("one_chip", model_1)):
            r = dual_check(cfg.kernel.gamma, cfg.C, Xtr, ytr, mdl.alpha)
            r["acc"] = float(np.mean(
                np.sign(np.asarray(decision_exact(mdl, Xte))) == yt))
            res[name] = r
        rel = (abs(res["sharded"]["objective"] - res["one_chip"]["objective"])
               / abs(res["one_chip"]["objective"]))
        dacc = abs(res["sharded"]["acc"] - res["one_chip"]["acc"])
        mean = float(np.mean(peaks))
        print(f"sharded vs one chip: {res} rel_objective={rel:.2e} "
              f"acc_diff={dacc:.4f}", flush=True)
        print(f"sharded fit peak_bytes_in_use per device: {peaks} "
              f"(mean {mean:.0f})", flush=True)
        check(rel <= OBJ_TOL, f"sharded objective off by {rel:.2e}")
        check(dacc <= ACC_POINTS, f"sharded accuracy off by {dacc:.4f}")
        check(max(peaks) <= 2 * mean, "one device holds over twice the "
              "mean peak memory")
    return {"rel_objective": rel, "acc_diff": dacc, "peaks": peaks}


# ---------------------------------------------------------------------------

def run_one_chip(devs, n_train: int, seed: int, meter: CompileMeter) -> None:
    print(f"cut: n {PAPER_N:,} -> {n_train:,} training points: at the "
          f"paper's n each level-1 cluster (~{PAPER_N // 4:,} points) needs "
          f"a dense Gram of ~{(PAPER_N // 4) ** 2 * 4 / 1e9:.0f} GB against "
          f"16 GB of HBM; n_train={n_train:,} gives ~{n_train // 4:,}-point "
          f"clusters ({(n_train // 4) ** 2 * 4 / 1e9:.1f} GB Grams)",
          flush=True)
    cfg = make_config()
    with meter.phase("data"):
        Xtr, ytr, Xte, yte = make_data(n_train, seed)
    with meter.phase("kernels"):
        check_kernels(Xtr, cfg.kernel.gamma, seed=seed)
        check_gram_is_pallas(cfg, Xtr)
    with meter.phase(f"train n_train={n_train}"):
        model = train(cfg, Xtr, ytr, meter)
        jax.block_until_ready(model.alpha)
    print(f"train peak_bytes_in_use: {peak_bytes(devs[0])}", flush=True)
    with meter.phase("check solution"):
        check_solution(cfg, model, Xtr, ytr, Xte, yte)
    with meter.phase(f"pallas vs xla n_train={N_PARITY}"):
        check_two_impls(N_PARITY, seed)
    with meter.phase("serve"):
        check_serving(model, Xte, meter, seed=seed)


def main(argv=None) -> int:
    cache_dir = enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        devs = require_tpu(args.chips)
        meter = CompileMeter()
        d0 = devs[0]
        print(f"device: {d0.platform} {d0.device_kind} count={len(devs)} "
              f"pallas={resolve_use_pallas(None)} compile_cache={cache_dir}",
              flush=True)
        if args.chips == 4:
            Xtr, ytr, Xte, yte = make_data(N_TRAIN, args.seed)
            check_sharded(devs, Xtr, ytr, Xte, yte, meter)
        else:
            run_one_chip(devs, N_TRAIN, args.seed, meter)
    except Exception as e:  # noqa: BLE001 - any failure fails the check
        import traceback

        traceback.print_exc()
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"total: {time.perf_counter() - t0:.1f} s wall, "
          f"{meter.seconds:.1f} s compile; phases "
          + json.dumps({k: [round(w, 1), round(c, 1)]
                        for k, (w, c) in meter.phases.items()}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

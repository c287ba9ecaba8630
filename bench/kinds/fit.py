"""Fit traffic: ``repro.core.fit`` back to back on one data set.

The work of a fit follows the draw of its clustering sample
(``DCSVMConfig.seed``): on one data set, different draws fitted in 7 to 22
seconds.  So the mix names a fixed set of draws (``draws``), and the window
fits them in rounds, each round every draw once, in an order drawn from the
run's seed: every run does the same work whatever its seed.

Set-up makes the cell's data on the device (``make_data``) and fits each
draw once, which compiles (or loads) every program the window's fits use: a
fit's shapes follow from its data and its draw.  The window then runs whole
rounds until ``--seconds`` have passed, finishing the round in flight;
``fit_s`` is the window's fit seconds over its number of fits.

With ``--trace 1`` the window is one round, with the program's host spans
recorded (``repro.obs.spans.SpanTracer``) over every fit, and the profiler on
over the fit of the first draw the mix lists, or, where the mix gives
``trace_from_level`` and ``trace_seconds``, for that many seconds from the
end of that level of that fit (the fit's level callback).  Every op of the
solvers' loops is a trace event, and the device's trace buffer holds about
6.3 million of them: a whole exact fit at n=50,000 made a 303 MB trace that
had lost all but the first quarter of its conquer.

Every distinct answer of the window is checked once the window has closed,
against ``bench.reference`` (a fresh HIGHEST-precision matvec, independent of
the solver's kernels and its maintained gradient, and a float64 partition):

* ``kkt``: the largest projected-gradient residual of the C-SVC dual at the
  returned alpha; for an early-stopped fit (``early_stop_level``) that of
  each cluster's own sub-QP at the stopping level (the block-diagonal
  problem eq. 11 predicts with), worst cluster;
* ``box``: the largest violation of 0 <= alpha <= C (exact: limit 0);
* ``assign_mismatch`` (early-stopped fits): the points whose cluster differs
  from the partition that the returned kernel k-means model (its sampled
  points and cluster weights) defines, balanced as the divide step balances.

The control is the program's own bfloat16 Gram path (``compute_dtype``); the
divide step has no such path, so in the control the reference's partition
with bfloat16 operands takes the place of the program's.

Mix keys: ``early_stop_level`` (0 = the exact fit), ``draws``,
``trace_from_level``, ``trace_seconds``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np
import jax

import repro.core as core

from bench import generate as G
from bench import harness as H
from bench import reference as R

CONTROL_DTYPE = "bfloat16"   # the program's own lower-precision Gram path


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def make_data(config: Dict[str, Any], seed: int) -> Tuple[jax.Array, ...]:
    """(Xtr, ytr, Xte, yte) from the config's generator, made on the device
    in one jitted call: n_train training rows and the held-out rest.

    The points and their order are fixed by the config's ``data_seed``, so
    every run fits the same problem.  The seed orders the held-out rows, from
    which the serving traffic draws its requests."""
    frac = float(config["test_frac"])
    n_train = int(config["n_train"])
    n = n_train
    while int(n * (1.0 - frac)) < n_train:
        n += 1

    @jax.jit
    def build(data_key, order_key):
        X, y = G.generate(config, data_key, n)
        Xtr, ytr, Xte, yte = G.train_test_split(
            jax.random.fold_in(data_key, 1), X, y, test_frac=frac)
        q = jax.random.permutation(order_key, Xte.shape[0])
        return Xtr, ytr, Xte[q], yte[q]

    out = build(seed_key(config["data_seed"]), seed_key(seed))
    jax.block_until_ready(out)
    return out


def dcsvm_config(config: Dict[str, Any], mix: Dict[str, Any],
                 control: bool = False, draw: int = 0) -> core.DCSVMConfig:
    return core.DCSVMConfig(
        kernel=core.Kernel(config["kernel"], gamma=float(config["gamma"])),
        C=float(config["C"]), k=int(config["k"]), levels=int(config["levels"]),
        m=int(config["m"]), tol=float(config["tol"]),
        max_iters=int(config["max_iters"]),
        early_stop_level=int(mix.get("early_stop_level", 0)),
        compute_dtype=CONTROL_DTYPE if control else None, seed=int(draw))


def round_order(draws: List[int], seed: int) -> List[int]:
    """The mix's draws in an order drawn from the run's seed."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    return [draws[i] for i in rng.permutation(len(draws))]


def _timed_fit(cfg, X, y, callback=None):
    t0 = time.perf_counter()
    model = core.fit(cfg, X, y, callback=callback)
    model.alpha.block_until_ready()
    return model, time.perf_counter() - t0


def work_counters(model, d: int) -> Dict[str, Any]:
    """Counts the per-layer readers use: level-0 iterations and the least
    kernel work that confirms the solution's KKT."""
    w = np.asarray(model.weights) != 0
    n = int(w.shape[0])
    c: Dict[str, Any] = {"n_sv": int(w.sum()), "early": bool(model.is_early)}
    if model.is_early:
        # one Q-alpha evaluation per cluster of the stopping level
        assign = np.asarray(model.partition.assign)
        work = 0
        for k in range(model.partition.k):
            m = assign == k
            work += (2 * d + 3) * int(m.sum()) * int(w[m].sum())
        c["confirm_flops"] = work
    else:
        c["confirm_flops"] = (2 * d + 3) * n * c["n_sv"]
        c["conquer_iters"] = int(model.level_stats[-1]["iters"])
    return c


def round_counters(fits, d: int) -> Dict[str, Any]:
    """``work_counters`` over the window's fits: the work and seconds summed,
    level-0 iterations per fit, and each draw's own readings."""
    per = [dict(work_counters(m, d), draw=draw, fit_s=s)
           for draw, m, s in fits]
    out: Dict[str, Any] = {
        "early": per[0]["early"],
        "fits": len(per),
        "fit_wall_s": sum(p["fit_s"] for p in per),
        "confirm_flops": sum(p["confirm_flops"] for p in per),
        "n_sv": sum(p["n_sv"] for p in per) / len(per),
        "per_draw": {str(p["draw"]): {k: p[k] for k in ("fit_s", "n_sv",
                                                       "conquer_iters")
                                      if k in p}
                     for p in per}}
    if "conquer_iters" in per[0]:
        out["conquer_iters"] = sum(p["conquer_iters"] for p in per) / len(per)
    return out


def span_seconds(tracer) -> Dict[str, float]:
    """Host seconds per span name over the tracer's tree."""
    out: Dict[str, float] = {}
    stack = list(tracer.roots)
    while stack:
        sp = stack.pop()
        out[sp.name] = out.get(sp.name, 0.0) + sp.duration
        stack.extend(sp.children)
    return out


def answer(model) -> Dict[str, np.ndarray]:
    out = {"alpha": np.asarray(model.alpha)}
    if model.is_early:
        out["assign"] = np.asarray(model.partition.assign)
        out["Xm"] = np.asarray(model.partition.model.Xm)
        out["W"] = np.asarray(model.partition.model.W)
    return out


def check(config: Dict[str, Any], limits: Dict[str, float], X, y,
          answers, control: bool = False) -> List["H.Check"]:
    """The worst reading over every distinct answer of the window."""
    gamma, C = float(config["gamma"]), float(config["C"])
    seen = set()
    worst = {"kkt": 0.0, "box": 0.0}
    for a in answers:
        key = b"".join(v.tobytes() for v in a.values())
        if key in seen:
            continue
        seen.add(key)
        if "assign" in a:
            r = R.cluster_kkt(gamma, C, X, y, a["alpha"], a["assign"])
            ref = R.partition_assign(gamma, X, a["Xm"], a["W"])
            got = (R.partition_assign(gamma, X, a["Xm"], a["W"], bf16=True)
                   if control else a["assign"])
            worst["assign_mismatch"] = max(worst.get("assign_mismatch", 0.0),
                                           float(np.sum(got != ref)))
        else:
            r = R.box_kkt(gamma, C, X, y, a["alpha"])
        worst["kkt"], worst["box"] = (max(worst["kkt"], r["kkt"]),
                                      max(worst["box"], r["box"]))
    return [H.Check(name, v, float(limits[name])) for name, v in worst.items()]


def run(ctx: "H.RunContext") -> "H.Outcome":
    Xtr, ytr, _, _ = make_data(ctx.config, ctx.seed)
    draws = [int(s) for s in ctx.mix["draws"]]
    cfgs = {s: dcsvm_config(ctx.config, ctx.mix, control=ctx.control, draw=s)
            for s in draws}
    # warm-up: every draw's shapes, the level callback's slice included
    for s in draws:
        _timed_fit(cfgs[s], Xtr, ytr, callback=lambda level, alpha, st: None)
    ctx.setup_done()

    order = round_order(draws, ctx.seed)
    fits = []
    spans: Dict[str, float] = {}
    if ctx.trace:
        from repro.obs.spans import SpanTracer

        start = ctx.mix.get("trace_from_level")
        tracer = SpanTracer()
        slices = []

        def at_level(level, alpha, st):
            if level == start:
                slices.append(ctx.trace_slice(float(ctx.mix["trace_seconds"])))

        with tracer.activate():
            for s in order:
                traced = s == draws[0]
                if traced and start is None:
                    ctx.start_trace()
                try:
                    model, secs = _timed_fit(
                        cfgs[s], Xtr, ytr,
                        callback=at_level if traced else None)
                finally:
                    if traced:
                        for th in slices:
                            th.join()
                        ctx.stop_trace()
                fits.append((s, model, secs))
        spans = {k: v / len(fits) for k, v in span_seconds(tracer).items()}
    else:
        t_end = time.perf_counter() + ctx.seconds
        while True:
            for s in order:
                fits.append((s, *_timed_fit(cfgs[s], Xtr, ytr)))
            if time.perf_counter() >= t_end:
                break
    ctx.read_memory_peak()

    counters = round_counters(fits, int(Xtr.shape[1]))
    counters["spans"] = spans
    answers = [answer(m) for _, m, _ in fits]
    times = [s for _, _, s in fits]
    X, y = np.asarray(Xtr), np.asarray(ytr)
    del fits, Xtr, ytr
    checks = check(ctx.config, ctx.cell.limits, X, y, answers,
                   control=ctx.control)
    return H.Outcome(metrics={"fit_s": sum(times) / len(times)},
                     attempted=len(times), failed=0, checks=checks,
                     counters=counters)

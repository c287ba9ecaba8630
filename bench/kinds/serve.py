"""Serve traffic: an open loop of requests through the async serving engine.

Set-up makes the cell's data (``fit.make_data``: the training rows fixed by
the configuration, the held-out rows ordered by the seed), fits the model
with ``repro.core.fit`` (the exact fit), registers it in a ``ModelRegistry`` and
warms every pad bucket of an ``AsyncServingEngine`` for the mix's strategy.
The window then offers requests on a fixed schedule for ``--seconds``:
Poisson arrivals at the mix's ``rate_rps``, request sizes log-uniform over
``[min_rows, max_rows]``, rows drawn from the held-out split.  The set of
gaps and sizes is the same for every seed (drawn once from ``schedule_seed``);
the run's seed only orders them and picks the rows.  Each request is timed
from the moment it was due to the moment its future resolved, so a stall
delays every request behind it.  The clients submit from a thread of their
own (``asyncio.run_coroutine_threadsafe``), so the schedule does not wait on
the engine's event loop.  A request that fails, or has not resolved
``grace_s`` after the window, counts as failed, with its wait as latency.

After the window a sample of the delivered requests drawn from the seed
(the largest included) is checked against ``bench.reference``: the plain
decision f(x) = sum_i w_i K(x_i, x) over the fitted model's weights.

* ``score_gap``: the largest |served f - reference f| over the sample;
* ``label_flips``: served labels that differ from sign(reference f) where
  |reference f| exceeds twice the ``score_gap`` limit (exact: limit 0).

With ``--trace 1`` the whole window is profiled.
"""
from __future__ import annotations

import asyncio
import functools
import threading
import time
from typing import Any, Dict, List

import numpy as np

import repro.core as core
from repro.launch.engine import AsyncServingEngine, EngineConfig
from repro.launch.registry import ModelRegistry
from repro.obs.metrics import MetricsRegistry

from bench import harness as H
from bench import reference as R
from bench.kinds import fit as F

MODEL = "model"


def schedule(mix: Dict[str, Any], seconds: float, seed: int, pool: int):
    """(due offsets in s, sizes, row indices per request) of one window."""
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(int(mix["schedule_seed"]))
    gaps = fixed.exponential(1.0 / rate, size=n)
    lo, hi = int(mix["min_rows"]), int(mix["max_rows"])
    sizes = np.floor(np.exp(fixed.uniform(np.log(lo), np.log(hi + 1),
                                          size=n))).astype(np.int64)
    sizes = np.clip(sizes, lo, hi)
    rng = np.random.default_rng(int(seed) % (1 << 64))
    gaps, sizes = gaps[rng.permutation(n)], sizes[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    rows = [rng.integers(0, pool, size=int(s)) for s in sizes]
    return due, sizes, rows


class Server:
    """The fitted model behind a warm engine."""

    def __init__(self, config: Dict[str, Any], mix: Dict[str, Any],
                 seed: int) -> None:
        Xtr, ytr, Xte, _ = F.make_data(config, seed)
        cfg = F.dcsvm_config(config, {"early_stop_level": 0})
        model = core.fit(cfg, Xtr, ytr)
        model.alpha.block_until_ready()
        w = np.asarray(model.weights)
        sv = np.nonzero(w)[0]
        self.Xsv, self.wsv = np.asarray(model.X)[sv], w[sv]
        self.gamma = float(config["gamma"])
        self.pool = np.asarray(Xte)
        self.strategy = mix["strategy"]
        registry = ModelRegistry()
        registry.register(MODEL, model, with_bcm=False,
                          max_sv_per_cluster=int(Xtr.shape[0]))
        self.metrics = MetricsRegistry()
        self.engine = AsyncServingEngine(
            registry, EngineConfig(max_batch=int(mix["max_batch"])),
            metrics=self.metrics)
        self.engine.warmup(MODEL, strategies=[self.strategy])

    def window(self, due, rows, grace_s: float) -> Dict[str, Any]:
        return asyncio.run(self._drive(due, rows, grace_s))

    async def _drive(self, due, rows, grace_s: float) -> Dict[str, Any]:
        n = len(due)
        lat = np.full(n, np.nan)
        late = np.zeros(n)
        answers: List[Any] = [None] * n
        futs: List[Any] = [None] * n
        eng = self.engine
        loop = asyncio.get_running_loop()

        def resolved(i: int, t_due: float, cf) -> None:
            # runs on the engine's loop as the request's future resolves
            t = time.perf_counter()
            if cf.cancelled():
                return
            if cf.exception() is not None:
                answers[i] = cf.exception()
                return
            answers[i] = cf.result()
            lat[i] = t - t_due

        def client(t0: float) -> None:
            # the clients' side: a thread of its own, so that its clock does
            # not wait on the engine's event loop
            for i in range(n):
                t_due = t0 + float(due[i])
                wait = t_due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late[i] = time.perf_counter() - t_due
                cf = asyncio.run_coroutine_threadsafe(
                    eng.submit(self.pool[rows[i]], MODEL,
                               strategy=self.strategy), loop)
                futs[i] = cf
                cf.add_done_callback(functools.partial(resolved, i, t_due))

        async with eng:
            t0 = time.perf_counter() + 0.05
            th = threading.Thread(target=client, args=(t0,),
                                  name="bench-client", daemon=True)
            th.start()
            while th.is_alive():
                await asyncio.sleep(0.05)
            th.join()
            waits = [asyncio.wrap_future(f) for f in futs if f is not None]
            t_close = t0 + float(due[-1])
            _, pending = await asyncio.wait(
                waits, timeout=max(0.0, t_close + grace_s
                                   - time.perf_counter()))
            for t in pending:
                t.cancel()
            await asyncio.gather(*waits, return_exceptions=True)
        t_end = time.perf_counter()
        failed = np.array([a is None or isinstance(a, BaseException)
                           for a in answers])
        for i in np.nonzero(failed)[0]:
            lat[i] = t_end - (t0 + float(due[i]))
        return {"lat": lat, "failed": failed, "late": late,
                "answers": answers}

    def check(self, res, rows, limits: Dict[str, float], seed: int,
              sample: int, control: bool = False) -> List[H.Check]:
        ok = np.nonzero(~res["failed"])[0]
        rng = np.random.default_rng((int(seed) + 1) % (1 << 64))
        pick = ok if len(ok) <= sample else rng.choice(ok, sample,
                                                       replace=False)
        if len(ok):
            biggest = ok[np.argmax([len(rows[i]) for i in ok])]
            pick = np.union1d(pick, [biggest])
        Xq = self.pool[np.concatenate([rows[i] for i in pick])] \
            if len(pick) else self.pool[:0]
        if control:
            # the reference at the next precision down, in the program's place
            f_served = R.decision(self.gamma, self.Xsv, self.wsv, Xq,
                                  precision=R.BF16_3X)
            lab = np.where(f_served >= 0, 1.0, -1.0)
        else:
            f_served = np.concatenate(
                [np.asarray(res["answers"][i][1])[:, 1] for i in pick]) \
                if len(pick) else np.zeros(0)
            lab = np.concatenate([np.asarray(res["answers"][i][0])
                                  for i in pick]) if len(pick) else f_served
        f_ref = R.decision(self.gamma, self.Xsv, self.wsv, Xq)
        gap = float(np.abs(f_served - f_ref).max()) if len(f_ref) else 0.0
        clear = np.abs(f_ref) > 2.0 * float(limits["score_gap"])
        flips = int(np.sum((lab != np.where(f_ref >= 0, 1.0, -1.0)) & clear))
        return [H.Check("score_gap", gap, float(limits["score_gap"])),
                H.Check("label_flips", float(flips),
                        float(limits["label_flips"]))]

    def counters(self) -> Dict[str, Any]:
        m = self.metrics
        wait = m.histogram("serve_queue_wait_seconds", lo=1e-6)
        fill = m.histogram("serve_batch_fill_ratio")
        return {"queue_wait_p99_s": wait.quantile(0.99) if wait.total else None,
                "batch_fill_mean": fill.sum / fill.total if fill.total else None,
                "batches": fill.total,
                "compiles_after_warmup":
                    self.engine.stats()["compiles_after_warmup"]}


def run(ctx: "H.RunContext") -> "H.Outcome":
    mix = ctx.mix
    srv = Server(ctx.config, mix, ctx.seed)
    due, sizes, rows = schedule(mix, ctx.seconds, ctx.seed, len(srv.pool))
    ctx.setup_done()
    with ctx.traced():
        res = srv.window(due, rows, float(mix["grace_s"]))
    ctx.read_memory_peak()
    counters = srv.counters()
    srv.engine = None
    lat_ms = res["lat"] * 1e3
    checks = srv.check(res, rows, ctx.cell.limits, ctx.seed,
                       int(mix["check_requests"]), control=ctx.control)
    late = res["late"]
    counters.update(requests=len(due), rows=int(sizes.sum()),
                    serve_p99_ms=float(np.percentile(lat_ms, 99)),
                    generator_late_p99_ms=float(np.percentile(late, 99) * 1e3),
                    generator_late_max_ms=float(late.max() * 1e3))
    return H.Outcome(
        metrics={"serve_p50_ms": float(np.percentile(lat_ms, 50))},
        attempted=len(due), failed=int(res["failed"].sum()), checks=checks,
        counters=counters)

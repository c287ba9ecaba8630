"""Device-resident convergence traces.

``ConvTrace`` is a preallocated ring buffer that lives on device as an
ordinary pytree leaf pair, so solver while-loops can record one sample per
outer iteration with a single ``dynamic_update_slice`` — no host sync, no
growing shapes, vmap/shard_map safe.  The buffer is fetched to host ONCE at
the end of a fit alongside the existing cache/spill counters (the same
discipline the transfer_guard tests pin for those counters).

Columns are fixed (``TRACE_COLS``); a recorder fills the columns it knows
and leaves the rest NaN, so one layout serves every solver family:

- box CD loops:   pg_max, objective, n_free        (+ cache_hits delta)
- equality loops: pg_max (max violation), objective, n_free
- CE-PBM conquer: pg_max, objective, n_free, gamma (combination step γ*)

Capacity is static.  When a solve runs longer than ``cap`` iterations the
ring keeps the LAST ``cap`` samples and ``trace_fetch`` reports how many
leading samples were dropped — the tail is where convergence curves live.

Gating is by Python ``None`` (static), the same pattern as
``compute_dtype=None``: the ring is an optional loop carry, and with
``trace=None`` it has no leaves and no recording op enters the program, so
default trajectories stay bit-identical.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

Array = Any

# Fixed column layout of the ring buffer (order matters — recorded rows and
# fetch unpack by position).
TRACE_COLS = ("pg_max", "objective", "n_free", "gamma", "cache_hits")
NCOLS = len(TRACE_COLS)


class ConvTrace(NamedTuple):
    """Ring buffer of per-iteration convergence samples (device resident)."""

    buf: Array    # (cap, NCOLS) f32, NaN where a column was not recorded
    count: Array  # ()           i32, total samples ever recorded


def trace_init(capacity: int) -> ConvTrace:
    """Fresh trace with room for ``capacity`` samples."""
    if capacity <= 0:
        raise ValueError(f"trace capacity must be positive, got {capacity}")
    return ConvTrace(
        buf=jnp.full((int(capacity), NCOLS), jnp.nan, jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def trace_record(
    tr: ConvTrace,
    pg_max: Optional[Array] = None,
    objective: Optional[Array] = None,
    n_free: Optional[Array] = None,
    gamma: Optional[Array] = None,
    cache_hits: Optional[Array] = None,
) -> ConvTrace:
    """Append one sample row (jit-safe; wraps around past capacity).

    ``None`` columns (a *static* choice per call site) are stored as NaN.
    """
    cap = tr.buf.shape[0]
    vals = (pg_max, objective, n_free, gamma, cache_hits)
    row = jnp.stack(
        [jnp.float32(jnp.nan) if v is None else jnp.asarray(v, jnp.float32)
         for v in vals]
    )
    pos = lax.rem(tr.count, jnp.int32(cap))
    buf = lax.dynamic_update_slice(tr.buf, row[None, :], (pos, jnp.int32(0)))
    return ConvTrace(buf=buf, count=tr.count + 1)


def _fetch_one(buf: np.ndarray, count: int) -> Dict[str, Any]:
    cap = buf.shape[0]
    kept = min(count, cap)
    if count <= cap:
        window = buf[:kept]
    else:  # ring wrapped: oldest surviving sample sits at count % cap
        start = count % cap
        window = np.concatenate([buf[start:], buf[:start]], axis=0)
    out: Dict[str, Any] = {
        "samples": int(kept),
        "dropped": int(count - kept),
    }
    for j, name in enumerate(TRACE_COLS):
        col = window[:, j]
        if kept and not np.all(np.isnan(col)):
            out[name] = [float(v) for v in col]
    return out


def trace_fetch(tr: ConvTrace) -> Any:
    """Host fetch (the ONE device->host sync), chronological order.

    Returns a dict with ``samples``/``dropped`` plus one list per column
    that was ever recorded (all-NaN columns are omitted).  A trace with
    leading batch dims (e.g. vmapped per-class solves) returns a nested
    list of dicts mirroring the batch shape.
    """
    buf = np.asarray(tr.buf)
    count = np.asarray(tr.count)
    if count.ndim == 0:
        return _fetch_one(buf, int(count))
    return [trace_fetch(ConvTrace(b, c)) for b, c in zip(buf, count)]


def trace_summary(fetched: Any) -> Dict[str, Any]:
    """Compact scalar summary of a fetched trace (batched: merged over all).

    Used for stats dumps where the full curve would be noise: sample and
    drop totals plus first/last pg_max and objective.  A raw (unfetched)
    ``ConvTrace`` is accepted too and fetched first.
    """
    if isinstance(fetched, ConvTrace):
        fetched = trace_fetch(fetched)
    if isinstance(fetched, list):
        flat = [trace_summary(f) for f in fetched]
        out: Dict[str, Any] = {
            "samples": sum(f["samples"] for f in flat),
            "dropped": sum(f["dropped"] for f in flat),
        }
        pgs = [f for f in flat if "pg_first" in f]
        if pgs:
            out["pg_first"] = max(f["pg_first"] for f in pgs)
            out["pg_last"] = max(f["pg_last"] for f in pgs)
        return out
    out = {"samples": fetched["samples"], "dropped": fetched["dropped"]}
    pg = fetched.get("pg_max")
    if pg:
        out["pg_first"] = pg[0]
        out["pg_last"] = pg[-1]
    obj = fetched.get("objective")
    if obj:
        out["obj_last"] = obj[-1]
    return out

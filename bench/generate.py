"""The benchmark's data, kept apart from the program's own data module.

A configuration names its generator (``generator``), the file
``bench/generators/<generator>.py``, and gives each of the generator's
``PARAMS`` as a key of its own, so the data a cell runs on is fixed by the
benchmark's files alone, whatever the program's data module becomes.  A
generator is a pure function of a PRNG key: the same key gives the same
points on every backend.  A new generator is a new file.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import jax

GENERATORS = Path(__file__).resolve().parent / "generators"


def generate(config: Dict[str, Any], key: jax.Array, n: int):
    """n rows (X, y) from the configuration's generator and its parameters."""
    from bench.harness import load_module

    gen = load_module(GENERATORS / f"{config['generator']}.py")
    return gen.generate(key, n, **{p: config[p] for p in gen.PARAMS})


def train_test_split(key: jax.Array, X, y, test_frac: float):
    """(Xtr, ytr, Xte, yte): a random ``test_frac`` share held out."""
    n = X.shape[0]
    perm = jax.random.permutation(key, n)
    nt = int(n * (1.0 - test_frac))
    tr, te = perm[:nt], perm[nt:]
    return X[tr], y[tr], X[te], y[te]

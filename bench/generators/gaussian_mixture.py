"""Each class a mixture of ``modes_per_class`` Gaussians in [0, 1]^d with
standard deviation ``spread``, a ``label_noise`` share of the labels flipped,
coordinates clipped to [0, 1].  Returns (X (n, d) float32, y (n,) +-1
float32)."""
import jax
import jax.numpy as jnp

PARAMS = ("d", "modes_per_class", "spread", "label_noise")


def generate(key, n: int, d: int, modes_per_class: int, spread: float,
             label_noise: float):
    k1, k2, k3, k4, _ = jax.random.split(key, 5)
    centers = jax.random.uniform(k1, (2 * modes_per_class, d))
    mode = jax.random.randint(k2, (n,), 0, 2 * modes_per_class)
    X = centers[mode] + spread * jax.random.normal(k3, (n, d))
    y = jnp.where(mode < modes_per_class, 1.0, -1.0)
    if label_noise > 0:
        flip = jax.random.bernoulli(k4, label_noise, (n,))
        y = jnp.where(flip, -y, y)
    return jnp.clip(X, 0.0, 1.0).astype(jnp.float32), y.astype(jnp.float32)

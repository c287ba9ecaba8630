"""The ``gaussian_mixture`` draw (each class a mixture of ``modes_per_class``
Gaussians in [0, 1]^d, clipped to [0, 1]), then each coordinate kept with
probability ``density`` and the rest set to 0: sparse features, as
webspam's are.  Returns (X (n, d) float32, y (n,) +-1 float32)."""
import jax

from bench.generators import gaussian_mixture

PARAMS = ("d", "modes_per_class", "spread", "label_noise", "density")


def generate(key, n: int, d: int, modes_per_class: int, spread: float,
             label_noise: float, density: float):
    k_mix, k_mask = jax.random.split(key)
    X, y = gaussian_mixture.generate(k_mix, n, d, modes_per_class, spread,
                                     label_noise)
    keep = jax.random.bernoulli(k_mask, density, X.shape)
    return X * keep, y

"""``fraction`` of the buffer's length drawn uniformly with replacement
(``jax.random.randint`` under the repeat's key), tiled back."""

import jax

from . import fit


def apply(x, p, key):
    n = x.shape[0]
    m = max(1, int(n * float(p["extra"].get("fraction", 0.25))))
    return fit(x[jax.random.randint(key, (m,), 0, n)], n)

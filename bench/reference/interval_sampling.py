"""Every ``stride``-th value, tiled back to the buffer's length."""

from . import fit


def apply(x, p, key):
    return fit(x[::int(p["extra"].get("stride", 4))], x.shape[0])

"""Rows of ``chunk`` values times the ``chunk`` x ``chunk`` matrix of the
buffer's first ``chunk**2`` values, over ``chunk``.  The product runs at
the reference's precision."""

from . import fit, rows


def apply(x, p, key):
    a = rows(x, p["chunk_size"])
    c = a.shape[1]
    return (a @ fit(x, c * c).reshape(c, c)) * (1.0 / c)

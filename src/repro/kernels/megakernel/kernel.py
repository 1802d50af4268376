"""One-kernel FusedStage execution: the stage megakernel emitter.

The ``fori_loop`` + ``lax.switch`` fused-stage form re-materializes the
carry through XLA between every trip.  This emitter compiles the whole
member chain into **one** ``pl.pallas_call``:

* the **carry stays resident in VMEM** for the whole stage: the output
  block *is* the carry — it is seeded from the input block once and every
  member's repeats read and write it in place, with no per-member HBM
  round-trip;
* the carry keeps the stage's 2-D ``(rows, lane)`` layout
  (:func:`~.bodies.mega_lane`): a chunk-row body sees one chunk per row,
  exactly the ``(rows, chunk)`` view its component takes, so no body
  reshapes inside the kernel;
* the **per-member trip counts** live in SMEM as one ``(k,)`` i32 vector,
  and each member runs its registered body (see :mod:`.bodies`) that many
  times via an in-kernel ``fori_loop`` whose bound is read from SMEM —
  weights are *data*, so stepping a weight never retraces, exactly like
  the switch path.

Trip order is therefore member 0's repeats, then member 1's, … — the
same order ``_fused_out``'s segmented trip space executes — and every
body is value-identical to its XLA counterpart, so the whole kernel is
bit-identical to the switch path (the ``test_schedule`` megakernel
sweep's contract).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: cap on the resident carry (f32 bytes): the carry's input and output
#: and the bodies' carry-sized temporaries must fit v5e's default scoped
#: VMEM (a 4 MiB carry runs out of it); a larger stage keeps the switch path
CARRY_VMEM_BYTES = 1 << 20


def _mega_kernel(w_ref, x_ref, o_ref, *, bodies):
    o_ref[...] = x_ref[...]
    for m, body in enumerate(bodies):
        def repeat(_, c, body=body):
            o_ref[...] = body(o_ref[...])
            return c

        jax.lax.fori_loop(0, w_ref[m], repeat, 0)


def mega_stage_kernel(x: jnp.ndarray, weights: jnp.ndarray,
                      bodies: Sequence, lane: int, *,
                      interpret: bool = True) -> jnp.ndarray:
    """Execute a fused stage as one kernel.

    ``x`` — flat f32 carry (the stage's ``data_size``); ``weights`` —
    (k,) i32 per-member trip counts (traced values, never statics);
    ``bodies`` — k registered ``(rows, lane) -> (rows, lane)`` segment
    bodies in member order; ``lane`` — the carry layout's row width.
    """
    size = x.shape[0]
    rows = size // lane
    kern = functools.partial(_mega_kernel, bodies=tuple(bodies))
    out = pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, lane), jnp.float32),
        name="megakernel",
        interpret=interpret,
    )(weights.astype(jnp.int32), x.reshape(rows, lane))
    return out.reshape(size)

"""Flash attention Pallas TPU kernel: blockwise online softmax.

Grid (B, H, nq, nkv); KV tiles stream HBM->VMEM; running (acc, m, l) live in
VMEM scratch across the nkv axis (innermost, sequential on TPU).  GQA is
handled in the K/V BlockSpec index maps (q-head h reads kv-head h // G) —
no materialized head repetition.  MXU-aligned tiles: bq/bkv multiples of
128 recommended, hd is the lane dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, bq: int, bkv: int, kv_len: int,
                  scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    cols = kj * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    # skip fully-masked kv blocks (beyond the causal diagonal / kv_len)
    live = (kj * bkv < kv_len)
    if causal:
        live = jnp.logical_and(live, kj * bkv <= qi * bq + bq - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = cols < kv_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                              # (bq, 1)
        m_new = jnp.maximum(m_prev[:, 0], s.max(axis=1))[:, None]
        p = jnp.exp(s - m_new)                           # (bq, bkv)
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, kv_len: int | None = None,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, Sq, hd); k/v: (B, Kv, Skv, hd) — Sq % block_q == 0,
    Skv % block_kv == 0 (ops.py pads)."""
    B, H, Sq, hd = q.shape
    Kv, Skv = k.shape[1], k.shape[2]
    G = H // Kv
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0
    nq, nkv = Sq // bq, Skv // bkv
    if kv_len is None:
        kv_len = Skv
    kernel = functools.partial(
        _flash_kernel, causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
        scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)

"""Pallas TPU kernel: fused FM second-order interaction (DeepFM hot path).

Computes 0.5 * sum_d[(sum_f x)^2 - sum_f x^2] per sample without
materializing the [B, F, D] squares or the [B, D] partial sums in HBM —
everything after the embedding gather stays in VMEM.

Layout: the batch rides the lanes.  The wrapper hands the kernel the
[F, D, B] transpose of the gathered rows, so a tile is (F, D, block_b) with
block_b lanes, the field sum is a plain add of (D, block_b) slabs, the
embedding sum is one sublane reduction, and the output is a lane-dense
(1, block_b) row.  A 1-D (block_b,) output block is refused by the TPU
compiler (its layout tiling differs from XLA's for the [B] result).

MXU is not used (elementwise + reductions only: this is a VPU kernel);
accumulation is fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref

LANES = 128


def _fm_kernel(emb_ref, out_ref):
    x = emb_ref[...].astype(jnp.float32)               # [F, D, Bb]
    s = jnp.sum(x, axis=0)                             # [D, Bb]
    ss = jnp.sum(x * x, axis=0)                        # [D, Bb]
    out_ref[...] = 0.5 * jnp.sum(s * s - ss, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_fm(emb: jnp.ndarray, *, block_b: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """emb: [B, F, D] -> [B] fp32.  B must be a multiple of block_b (pad at
    the call site; ops.py does), and compiled for the TPU block_b must be a
    multiple of 128 lanes."""
    b, f, d = emb.shape
    if b % block_b:
        raise ValueError(f"B={b} not a multiple of block_b={block_b}")
    if not interpret and block_b % LANES:
        raise ValueError(f"block_b={block_b} must be a multiple of {LANES} "
                         f"lanes for the TPU compiler")
    out = pl.pallas_call(
        _fm_kernel,
        grid=(b // block_b,),
        in_specs=[pl.BlockSpec((f, d, block_b), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((1, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        interpret=interpret,
    )(jnp.transpose(emb, (1, 2, 0)))
    return out[0]


reference = _ref.fused_fm

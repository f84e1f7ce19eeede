"""Pallas TPU kernel: EmbeddingBag — ragged gather + segment-reduce.

The serving hot path of every recsys arch here (DESIGN.md §7): multi-hot
feature fields gather up to L rows from a huge table and reduce them.  JAX has
no native EmbeddingBag; the pure-jnp construction (ref.py) materializes a
[B, L, D] intermediate in HBM.  This kernel never does: each bag's rows are
DMA'd row-by-row from the HBM-resident table into a 2-slot VMEM ring (double
buffering — issue row j+1's copy while accumulating row j), accumulated in
fp32 VMEM, and only the [B, D] result is written out.

This is the same AMAC-style dependence-breaking as neighbor_lookup.py, in its
simplest form (fixed-length chains of 1): a warm-up for the full probe kernel.

Layout notes (TPU): rows are (1, D) DMAs, and the TPU compiler accepts a
row slice only when D is a multiple of 128 lanes.  Nothing pads D: padding
would copy the whole table on every call, so a native call with any other D
raises (the recsys dims 10/18/32 take ``impl="ref"``).  Indices and weights
sit whole in SMEM so the DMA addresses are known before the grid body runs;
a batch whose [B, L] arrays would not fit there raises too.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

_NSLOTS = 2     # double buffer
_LANES = 128
_SMEM_BYTES = 1 << 20         # v5e scalar memory per core
_SMEM_RESERVED = 4 << 10      # the compiler keeps ~1.1 KiB; the rest is margin


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def smem_bytes(bsz: int, bag_len: int) -> int:
    """SMEM the [B, L] int32 indices and fp32 weights take: the compiler
    tiles each 2-D SMEM array to (8, 128) words."""
    return 2 * _round_up(bsz, 8) * _round_up(bag_len, _LANES) * 4


def _bag_kernel(idx_ref, wgt_ref, table_ref, out_ref, acc_ref, row_ref, sem,
                *, bags_per_block: int, bag_len: int, mode: str):
    blk = pl.program_id(0)

    def copy(b, j, slot):
        row = idx_ref[blk * bags_per_block + b, j]
        return pltpu.make_async_copy(
            table_ref.at[jnp.maximum(row, 0)], row_ref.at[slot], sem.at[slot])

    for b in range(bags_per_block):           # static unroll over bag tile
        gb = blk * bags_per_block + b
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(idx_ref[gb, 0] >= 0)
        def _start():
            copy(b, 0, 0).start()

        def body(j, count):
            valid = idx_ref[gb, j] >= 0
            slot = jax.lax.rem(j, _NSLOTS)

            @pl.when(valid)
            def _():
                copy(b, j, slot).wait()

            # issue next row's DMA before consuming this one
            @pl.when((j + 1 < bag_len) & (idx_ref[gb, j + 1] >= 0))
            def _():
                copy(b, j + 1, jax.lax.rem(j + 1, _NSLOTS)).start()

            @pl.when(valid)
            def _():
                row = row_ref[slot].astype(jnp.float32)
                w = wgt_ref[gb, j]
                acc_ref[...] = acc_ref[...] + row * w
            return count + valid.astype(jnp.int32)

        count = jax.lax.fori_loop(0, bag_len, body, jnp.int32(0))
        denom = (jnp.maximum(count, 1).astype(jnp.float32)
                 if mode == "mean" else jnp.float32(1.0))
        out_ref[b, :] = acc_ref[...] / denom


@functools.partial(jax.jit, static_argnames=("mode", "bags_per_block",
                                             "interpret"))
def embedding_bag(table: jnp.ndarray, indices: jnp.ndarray,
                  weights: Optional[jnp.ndarray] = None, *,
                  mode: str = "sum", bags_per_block: int = 8,
                  interpret: bool = False) -> jnp.ndarray:
    """table: [V, D]; indices: int32 [B, L] (-1 pad); weights: [B, L] or None.
    Returns fp32 [B, D].  B must divide by bags_per_block (ops.py pads).
    Compiled for the TPU, D must be a multiple of 128 and the [B, L] arrays
    must fit SMEM (``smem_bytes``); either violation raises ValueError."""
    if mode not in ("sum", "mean"):
        raise ValueError(mode)
    bsz, bag_len = indices.shape
    _, d = table.shape
    if bsz % bags_per_block:
        raise ValueError(f"B={bsz} % bags_per_block={bags_per_block} != 0")
    if not interpret:
        if d % _LANES:
            raise ValueError(f"D={d} is not a multiple of {_LANES}: the TPU "
                             f"compiler refuses a (1, {d}) row DMA")
        need = smem_bytes(bsz, bag_len)
        if need > _SMEM_BYTES - _SMEM_RESERVED:
            raise ValueError(
                f"[B, L]=[{bsz}, {bag_len}] indices and weights take {need} B "
                f"of SMEM, over the {_SMEM_BYTES - _SMEM_RESERVED} B "
                f"available: split the batch")
    if weights is None:
        weights = jnp.ones((bsz, bag_len), jnp.float32)
    grid = (bsz // bags_per_block,)
    kernel = functools.partial(_bag_kernel, bags_per_block=bags_per_block,
                               bag_len=bag_len, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # indices
            pl.BlockSpec(memory_space=pltpu.SMEM),    # weights
            pl.BlockSpec(memory_space=pl.ANY),        # table stays in HBM
        ],
        out_specs=pl.BlockSpec((bags_per_block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((d,), jnp.float32),            # accumulator
            pltpu.VMEM((_NSLOTS, d), table.dtype),    # row ring
            pltpu.SemaphoreType.DMA((_NSLOTS,)),
        ],
        interpret=interpret,
    )(indices, weights.astype(jnp.float32), table)


reference = _ref.embedding_bag

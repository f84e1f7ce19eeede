"""Pallas TPU kernels: NeighborHash batch probe (the paper's §2.1 hot path).

Two kernels, mirroring the paper's Figure 9 regimes:

* ``lookup_vec`` — the IMV analogue for VMEM-resident tables: the whole table
  block lives in VMEM and the entire query tile advances one probe step per
  iteration under an active-lane mask.  Best when the table fits in VMEM
  (≤ ~2 MB, like the paper's SIMD version winning on L2-resident tables).

* ``lookup_amac`` — the AMAC analogue for HBM-resident tables: the table
  stays in HBM in a *line-packed* layout ([n_lines, 4, BPL] uint32 — one
  512 B DMA fetches a whole neighbor line: key_hi/key_lo/val_hi/val_lo for
  BPL=32 buckets), and a ring of ``n_slots`` in-flight async copies keeps the
  memory system saturated: while query i's line is in flight, queries
  i+1..i+K-1 are being issued or consumed.  Chain-following reuses the slot —
  exactly AMAC's state-machine-per-miss-status-register, with TPU DMA
  semaphores playing the MSHR role (DESIGN.md §2).

Both validated in interpret mode against kernels/ref.py; ops.py dispatches.
Neither compiles for the TPU yet (JAX 0.9 / v5e): Mosaic supports only 2-D
gathers, which ``lookup_vec``'s ``jnp.take`` is not, and ``lookup_amac``
stores scalars to VMEM.  On the chip ``ops.neighbor_lookup`` therefore runs
the engine's probe (core/lookup.py) under ``impl="auto"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashcore as hc
from repro.kernels import ref as _ref

reference = _ref.neighbor_lookup


# ---------------------------------------------------------------------------
# host-side: pack a built table into the line-packed DMA layout
# ---------------------------------------------------------------------------
def pack_lines(key_hi: np.ndarray, key_lo: np.ndarray, val_hi: np.ndarray,
               val_lo: np.ndarray, buckets_per_line: int = hc.TPU_BUCKETS_PER_LINE
               ) -> np.ndarray:
    """-> uint32 [n_lines, 4, BPL]; one row == one DMA sector."""
    cap = key_hi.shape[0]
    bpl = buckets_per_line
    n_lines = -(-cap // bpl)
    pad = n_lines * bpl - cap
    def p(a, fill):
        return np.concatenate([a, np.full(pad, fill, np.uint32)]) if pad else a
    stack = np.stack([p(key_hi, hc.EMPTY_HI), p(key_lo, hc.EMPTY_LO),
                      p(val_hi, 0), p(val_lo, 0)])          # [4, cap+pad]
    return np.ascontiguousarray(
        stack.reshape(4, n_lines, bpl).transpose(1, 0, 2))  # [n_lines, 4, BPL]


# ---------------------------------------------------------------------------
# IMV-style vectorized kernel (table in VMEM)
# ---------------------------------------------------------------------------
def _vec_kernel(khi_ref, klo_ref, vhi_ref, vlo_ref, qhi_ref, qlo_ref,
                found_ref, phi_ref, plo_ref, *, capacity: int,
                max_probes: int):
    q_hi = qhi_ref[...]
    q_lo = qlo_ref[...]
    khi_t = khi_ref[...]
    klo_t = klo_ref[...]
    vhi_t = vhi_ref[...]
    vlo_t = vlo_ref[...]

    home = hc.bucket_of_jnp(q_hi, q_lo, capacity)
    khi = jnp.take(khi_t, home)
    klo = jnp.take(klo_t, home)
    vhi = jnp.take(vhi_t, home)
    vlo = jnp.take(vlo_t, home)
    empty = (khi == jnp.uint32(hc.EMPTY_HI)) & (klo == jnp.uint32(hc.EMPTY_LO))
    hit = (khi == q_hi) & (klo == q_lo) & ~empty
    rooted = ~empty & (hc.bucket_of_jnp(khi, klo, capacity) == home)
    found = hit
    p_hi = jnp.where(hit, vhi & jnp.uint32(hc.PAYLOAD_HI_MASK), jnp.uint32(0))
    p_lo = jnp.where(hit, vlo, jnp.uint32(0))
    active = rooted & ~hit

    def body(_, st):
        active, idx, vhi_cur, found, p_hi, p_lo = st
        off = hc.decode_offset_jnp(vhi_cur)
        active = active & (off != 0)
        idx = jnp.where(active, idx + off, idx)
        khi = jnp.take(khi_t, idx)
        klo = jnp.take(klo_t, idx)
        vhi = jnp.take(vhi_t, idx)
        vlo = jnp.take(vlo_t, idx)
        hit = active & (khi == q_hi) & (klo == q_lo)
        found = found | hit
        p_hi = jnp.where(hit, vhi & jnp.uint32(hc.PAYLOAD_HI_MASK), p_hi)
        p_lo = jnp.where(hit, vlo, p_lo)
        return active & ~hit, idx, vhi, found, p_hi, p_lo

    st = (active, home, vhi, found, p_hi, p_lo)
    _, _, _, found, p_hi, p_lo = jax.lax.fori_loop(0, max_probes, body, st)
    found_ref[...] = found.astype(jnp.uint32)
    phi_ref[...] = p_hi
    plo_ref[...] = p_lo


@functools.partial(jax.jit, static_argnames=("capacity", "max_probes",
                                             "block_q", "interpret"))
def lookup_vec(key_hi, key_lo, val_hi, val_lo, q_hi, q_lo, *, capacity: int,
               max_probes: int, block_q: int = 512, interpret: bool = False):
    n = q_hi.shape[0]
    if n % block_q:
        raise ValueError(f"N={n} % block_q={block_q} != 0 (pad at call site)")
    grid = (n // block_q,)
    table_spec = pl.BlockSpec((capacity,), lambda i: (0,))
    q_spec = pl.BlockSpec((block_q,), lambda i: (i,))
    kernel = functools.partial(_vec_kernel, capacity=capacity,
                               max_probes=max_probes)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[table_spec] * 4 + [q_spec] * 2,
        out_specs=[q_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32)] * 3,
        interpret=interpret,
    )(key_hi, key_lo, val_hi, val_lo, q_hi, q_lo)
    return out[0], out[1], out[2]


# ---------------------------------------------------------------------------
# AMAC-style kernel (table in HBM, ring of in-flight line DMAs)
# ---------------------------------------------------------------------------
def _amac_kernel(qhi_ref, qlo_ref, lines_ref, found_ref, phi_ref, plo_ref,
                 ring_ref, squery_ref, sbucket_ref, sfirst_ref, sem, *,
                 capacity: int, bpl: int, n_slots: int, block_q: int,
                 max_probes: int):
    """Per grid step: resolve block_q queries with n_slots outstanding DMAs.

    SMEM state per slot: squery (query lane or -1), sbucket (absolute bucket
    index whose line is in flight), sfirst (1 while probing the home bucket —
    the lodger check applies only there)."""

    def line_copy(slot, bucket):
        return pltpu.make_async_copy(
            lines_ref.at[bucket // bpl], ring_ref.at[slot], sem.at[slot])

    def q_at(i):
        return qhi_ref[i], qlo_ref[i]

    # ---- prologue: fill the ring -----------------------------------------
    for k in range(n_slots):                      # static unroll
        if k < block_q:
            qh, ql = q_at(k)
            home = hc.bucket_of_jnp(qh, ql, capacity)
            squery_ref[k] = jnp.int32(k)
            sbucket_ref[k] = home
            sfirst_ref[k] = jnp.int32(1)
            line_copy(k, home).start()
        else:
            squery_ref[k] = jnp.int32(-1)

    # ---- main loop ---------------------------------------------------------
    def slot_step(k, carry):
        resolved, next_q = carry
        qi = squery_ref[k]
        active = qi >= 0

        def when_active(carry):
            resolved, next_q = carry
            bucket = sbucket_ref[k]
            line_copy(k, bucket).wait()
            lane = jax.lax.rem(bucket, bpl)
            khi = ring_ref[k, 0, lane]
            klo = ring_ref[k, 1, lane]
            vhi = ring_ref[k, 2, lane]
            vlo = ring_ref[k, 3, lane]
            qh = qhi_ref[qi]
            ql = qlo_ref[qi]
            empty = (khi == jnp.uint32(hc.EMPTY_HI)) & \
                    (klo == jnp.uint32(hc.EMPTY_LO))
            hit = (khi == qh) & (klo == ql) & ~empty
            first = sfirst_ref[k] == 1
            lodger = first & \
                (hc.bucket_of_jnp(khi, klo, capacity) != bucket) & ~empty
            off = hc.decode_offset_jnp(vhi)
            dead_end = (off == 0)
            done = hit | empty | lodger | (dead_end & ~hit)

            @pl.when(done)
            def _emit():
                found_ref[qi] = hit.astype(jnp.uint32)
                phi_ref[qi] = jnp.where(
                    hit, vhi & jnp.uint32(hc.PAYLOAD_HI_MASK), jnp.uint32(0))
                plo_ref[qi] = jnp.where(hit, vlo, jnp.uint32(0))

                # refill the slot with the next pending query (AMAC refill)
                @pl.when(next_q < block_q)
                def _refill():
                    nqh = qhi_ref[next_q]
                    nql = qlo_ref[next_q]
                    nhome = hc.bucket_of_jnp(nqh, nql, capacity)
                    squery_ref[k] = next_q
                    sbucket_ref[k] = nhome
                    sfirst_ref[k] = jnp.int32(1)
                    line_copy(k, nhome).start()

                @pl.when(next_q >= block_q)
                def _retire():
                    squery_ref[k] = jnp.int32(-1)

            @pl.when(~done)
            def _chase():                          # follow the chain
                nbucket = bucket + off
                sbucket_ref[k] = nbucket
                sfirst_ref[k] = jnp.int32(0)
                line_copy(k, nbucket).start()

            return (resolved + done.astype(jnp.int32),
                    next_q + (done & (next_q < block_q)).astype(jnp.int32))

        return jax.lax.cond(active, when_active, lambda c: c,
                            (resolved, next_q))

    def sweep(carry):
        return jax.lax.fori_loop(0, n_slots, slot_step, carry)

    def cond(carry):
        resolved, _ = carry
        return resolved < block_q

    # safety: each sweep resolves ≥1 query or advances ≥1 probe; bound sweeps
    init = (jnp.int32(0), jnp.int32(min(n_slots, block_q)))
    jax.lax.while_loop(cond, lambda c: sweep(c), init)


@functools.partial(jax.jit, static_argnames=(
    "capacity", "bpl", "max_probes", "block_q", "n_slots", "interpret"))
def lookup_amac(lines, q_hi, q_lo, *, capacity: int, bpl: int,
                max_probes: int, block_q: int = 256, n_slots: int = 8,
                interpret: bool = False):
    """lines: uint32 [n_lines, 4, bpl] (pack_lines); queries uint32 [N].
    Returns (found u32[N], p_hi u32[N], p_lo u32[N])."""
    n = q_hi.shape[0]
    if n % block_q:
        raise ValueError(f"N={n} % block_q={block_q} != 0 (pad at call site)")
    grid = (n // block_q,)
    q_spec = pl.BlockSpec((block_q,), lambda i: (i,))
    kernel = functools.partial(
        _amac_kernel, capacity=capacity, bpl=bpl, n_slots=n_slots,
        block_q=block_q, max_probes=max_probes)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[q_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32)] * 3,
        scratch_shapes=[
            pltpu.VMEM((n_slots, 4, bpl), jnp.uint32),   # line ring
            pltpu.SMEM((n_slots,), jnp.int32),           # slot -> query
            pltpu.SMEM((n_slots,), jnp.int32),           # slot -> bucket
            pltpu.SMEM((n_slots,), jnp.int32),           # slot -> first-probe
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
        interpret=interpret,
    )(q_hi, q_lo, lines)
    return out[0], out[1], out[2]

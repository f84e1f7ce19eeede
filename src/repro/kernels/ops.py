"""jit'd dispatch wrappers for the Pallas kernels.

Backend policy, chosen from ``jax.devices()[0].platform``:
  * ``tpu``: ``impl='auto'`` runs the native kernel (interpret=False), except
    the neighbor probe: the TPU compiler refuses ``lookup_vec`` (1-D gather)
    and ``lookup_amac`` (scalar stores to VMEM), so ``auto`` runs ``ref``
    there, which is the engine's own probe (core/lookup.py).  A forced impl
    compiles natively.
  * ``cpu``: ``impl='auto'`` runs the pure-jnp oracle (``ref``); a forced
    ``impl='vec'|'amac'|'pallas'`` runs the kernel in interpret mode — what
    the parity tests do.
  * any other platform raises: no kernel silently falls back there.

All wrappers handle padding to the kernels' block multiples.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashcore as hc
from repro.kernels import ref as _ref
from repro.kernels import embedding_bag as _bag
from repro.kernels import fused_fm as _fm
from repro.kernels import neighbor_lookup as _nl


def _platform() -> str:
    platform = jax.devices()[0].platform
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"no kernel policy for platform {platform!r}: kernels run "
            f"natively on tpu and as ref/interpret on cpu")
    return platform


def _resolve(impl: str, tpu_impl: str) -> tuple[str, bool]:
    """-> (impl, interpret) for this platform."""
    on_tpu = _platform() == "tpu"
    if impl == "auto":
        impl = tpu_impl if on_tpu else "ref"
    return impl, not on_tpu


def _pad_to(x: jnp.ndarray, mult: int, axis: int = 0, fill=0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=fill), n


# ---------------------------------------------------------------------------
def neighbor_lookup(key_hi, key_lo, val_hi, val_lo, q_hi, q_lo, *,
                    max_probes: int, impl: str = "auto",
                    lines: Optional[jnp.ndarray] = None,
                    bpl: int = hc.TPU_BUCKETS_PER_LINE,
                    block_q: int = 256, n_slots: int = 8):
    """Returns (found u32[N], p_hi u32[N], p_lo u32[N])."""
    capacity = key_hi.shape[0]
    impl, interpret = _resolve(impl, "ref")
    if impl == "ref":
        return _ref.neighbor_lookup(key_hi, key_lo, val_hi, val_lo,
                                    q_hi, q_lo, max_probes=max_probes)
    if impl == "vec":
        qh, n = _pad_to(q_hi, block_q)
        ql, _ = _pad_to(q_lo, block_q)
        f, ph, pl_ = _nl.lookup_vec(key_hi, key_lo, val_hi, val_lo, qh, ql,
                                    capacity=capacity, max_probes=max_probes,
                                    block_q=block_q, interpret=interpret)
        return f[:n], ph[:n], pl_[:n]
    if impl == "amac":
        if lines is None:
            lines = jnp.asarray(_nl.pack_lines(
                np.asarray(key_hi), np.asarray(key_lo),
                np.asarray(val_hi), np.asarray(val_lo), bpl))
        qh, n = _pad_to(q_hi, block_q)
        ql, _ = _pad_to(q_lo, block_q)
        f, ph, pl_ = _nl.lookup_amac(lines, qh, ql, capacity=capacity,
                                     bpl=bpl, max_probes=max_probes,
                                     block_q=block_q, n_slots=n_slots,
                                     interpret=interpret)
        return f[:n], ph[:n], pl_[:n]
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
def embedding_bag(table, indices, weights=None, *, mode: str = "sum",
                  impl: str = "auto", bags_per_block: int = 8):
    """Native on TPU needs D % 128 == 0 and [B, L] within SMEM (the kernel
    raises otherwise); pass ``impl="ref"`` for other shapes."""
    impl, interpret = _resolve(impl, "pallas")
    if impl == "ref":
        return _ref.embedding_bag(table, indices, weights, mode)
    idx, n = _pad_to(indices, bags_per_block, fill=-1)
    w = None if weights is None else _pad_to(weights, bags_per_block)[0]
    out = _bag.embedding_bag(table, idx, w, mode=mode,
                             bags_per_block=bags_per_block,
                             interpret=interpret)
    return out[:n]


# ---------------------------------------------------------------------------
def fm_interaction(emb, *, impl: str = "auto", block_b: int = 128):
    impl, interpret = _resolve(impl, "pallas")
    if impl == "ref":
        return _ref.fused_fm(emb)
    x, n = _pad_to(emb, block_b)
    return _fm.fused_fm(x, block_b=block_b, interpret=interpret)[:n]

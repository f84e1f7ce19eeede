"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's tests sweep shapes/dtypes and assert_allclose against these.
The oracles are also the default implementation on the CPU (see ops.py), so
the whole framework runs end-to-end there; on the TPU, the neighbor-probe
oracle is the engine's own probe (core/lookup.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import hashcore as hc
from repro.core import lookup as lk


# ---------------------------------------------------------------------------
# neighbor_lookup
# ---------------------------------------------------------------------------
def neighbor_lookup(key_hi, key_lo, val_hi, val_lo, q_hi, q_lo, *,
                    max_probes: int, home_capacity: Optional[int] = None,
                    host_check: bool = True):
    """Batched NeighborHash probe (inline-offset variant).  Returns
    (found uint32[N], payload_hi uint32[N], payload_lo uint32[N])."""
    cap = home_capacity or key_hi.shape[0]
    found, p_hi, p_lo = lk.lookup(
        key_hi, key_lo, val_hi, val_lo, None, q_hi, q_lo,
        home_capacity=cap, inline=True, host_check=host_check,
        max_probes=max_probes)
    return found.astype(jnp.uint32), p_hi, p_lo


# ---------------------------------------------------------------------------
# embedding_bag — JAX has no native one (kernel_taxonomy §B.6): gather +
# segment-reduce built from take + masked sum.  indices: int32 [B, L] with -1
# padding; optional per-sample weights [B, L].
# ---------------------------------------------------------------------------
def embedding_bag(table: jnp.ndarray, indices: jnp.ndarray,
                  weights: Optional[jnp.ndarray] = None,
                  mode: str = "sum") -> jnp.ndarray:
    if mode not in ("sum", "mean"):
        raise ValueError(f"mode must be sum|mean, got {mode!r}")
    valid = indices >= 0
    safe = jnp.where(valid, indices, 0)
    rows = jnp.take(table, safe, axis=0)               # [B, L, D]
    mask = valid.astype(table.dtype)[..., None]
    if weights is not None:
        mask = mask * weights[..., None].astype(table.dtype)
    out = jnp.sum(rows * mask, axis=1)                 # [B, D]
    if mode == "mean":
        denom = jnp.maximum(valid.sum(axis=1, dtype=table.dtype), 1)
        out = out / denom[:, None]
    return out


# ---------------------------------------------------------------------------
# fused_fm — factorization-machine second-order term:
#   fm(x)_b = 0.5 * sum_d [ (sum_f x_bfd)^2 - sum_f x_bfd^2 ]
# ---------------------------------------------------------------------------
def fused_fm(emb: jnp.ndarray) -> jnp.ndarray:
    """emb: [B, F, D] -> [B] (fp32 accumulation regardless of input dtype)."""
    x = emb.astype(jnp.float32)
    s = jnp.sum(x, axis=1)                             # [B, D]
    ss = jnp.sum(x * x, axis=1)                        # [B, D]
    return 0.5 * jnp.sum(s * s - ss, axis=-1)          # [B]

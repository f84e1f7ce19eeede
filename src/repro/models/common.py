"""Shared model substrate: boxed params (value + PartitionSpec), norms,
activations, rotary embeddings, sharding-constraint helpers.

Convention (DESIGN.md §6): mesh axes are ("pod", "data", "model") multi-pod or
("data", "model") single-pod.  Logical roles:

    DP   = ("pod", "data")  — batch dims
    TP   = "model"          — heads / ffn-hidden / vocab / experts / table rows
    SP   = "model"          — sequence dim of activations between blocks

Models are pure functions over nested-dict param trees.  Parameters are built
as `Boxed(value, spec)`; `unbox` splits into (params, specs) so the dry-run
can `jax.eval_shape` the init and build NamedShardings without allocating.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

DP = ("pod", "data")     # data-parallel mesh axes (pod may be absent)
TP = "model"


@dataclasses.dataclass
class Boxed:
    value: Any
    spec: P


jax.tree_util.register_pytree_node(
    Boxed,
    lambda b: ((b.value,), b.spec),
    lambda spec, ch: Boxed(ch[0], spec),
)


def is_boxed(x) -> bool:
    return isinstance(x, Boxed)


def stack_specs(tree, prefix=(None,)):
    """After vmap-stacking layer params, prepend axes to every Boxed spec."""
    return jax.tree.map(
        lambda b: Boxed(b.value, P(*prefix, *b.spec)), tree,
        is_leaf=is_boxed)


def unbox(tree):
    params = jax.tree.map(lambda b: b.value, tree, is_leaf=is_boxed)
    specs = jax.tree.map(lambda b: b.spec, tree, is_leaf=is_boxed)
    return params, specs


def dp_spec(mesh_axes) -> tuple:
    """The data-parallel axis group present in this mesh."""
    return tuple(a for a in DP if a in mesh_axes)


def adapt_spec(spec: P, mesh_axes) -> P:
    """Drop mesh axes not present (e.g. 'pod' on the single-pod mesh)."""
    out = []
    for part in spec:
        if part is None:
            out.append(None)
        elif isinstance(part, (tuple, list)):
            kept = tuple(a for a in part if a in mesh_axes)
            out.append(kept if kept else None)
        else:
            out.append(part if part in mesh_axes else None)
    return P(*out)


def cs(x, *spec_parts):
    """with_sharding_constraint against the ambient mesh; a no-op where no
    mesh is set, and outside jit, where a concrete array keeps its
    placement.  Any other failure (an axis the mesh lacks) raises."""
    if jax.sharding.get_abstract_mesh().empty or \
            not isinstance(x, jax.core.Tracer):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec_parts))


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    """Trace-time mesh metadata so models can apply sharding constraints
    opportunistically (skip axes that don't divide a dim — e.g. 40 query
    heads on a 16-way 'model' axis stay replicated)."""
    axes: tuple
    sizes: dict

    @classmethod
    def from_mesh(cls, mesh) -> "MeshInfo":
        return cls(axes=tuple(mesh.axis_names),
                   sizes={a: int(s) for a, s in
                          zip(mesh.axis_names, mesh.devices.shape)})

    @classmethod
    def single(cls) -> "MeshInfo":
        return cls(axes=("data", "model"), sizes={"data": 1, "model": 1})

    @property
    def dp(self) -> tuple:
        return tuple(a for a in DP if a in self.axes)

    def axis_size(self, part) -> int:
        if part is None:
            return 1
        if isinstance(part, (tuple, list)):
            n = 1
            for a in part:
                n *= self.sizes.get(a, 1)
            return n
        return self.sizes.get(part, 1)

    def spec(self, *parts) -> P:
        """Adapt a spec to this mesh (drop absent axes)."""
        return adapt_spec(P(*parts), self.axes)

    def shard(self, x, *parts):
        """Constraint with divisibility checks; indivisible dims replicate."""
        parts = list(self.spec(*parts))
        while len(parts) < x.ndim:
            parts.append(None)
        fixed = []
        for dim, part in zip(x.shape, parts):
            n = self.axis_size(part)
            fixed.append(part if (n > 1 and dim % n == 0) or n == 1 else None)
        if all(p is None for p in fixed):
            return x
        return cs(x, *fixed)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def normal_init(key, shape, scale: float, dtype=jnp.float32):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)
            ).astype(dtype)


def dense_param(key, in_dim: int, out_dim: int, spec: P,
                dtype=jnp.float32) -> Boxed:
    scale = 1.0 / np.sqrt(in_dim)
    return Boxed(normal_init(key, (in_dim, out_dim), scale, dtype), spec)


def embed_param(key, vocab: int, dim: int, spec: P,
                dtype=jnp.float32) -> Boxed:
    return Boxed(normal_init(key, (vocab, dim), 0.02, dtype), spec)


def scale_param(dim: int, spec: P = P(None), dtype=jnp.float32) -> Boxed:
    return Boxed(jnp.ones((dim,), dtype), spec)


def keygen(key):
    """Infinite key splitter."""
    while True:
        key, sub = jax.random.split(key)
        yield sub


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------
def rms_norm(x, gamma, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
            ).astype(dt)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
            ).astype(dt)


def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "squared_relu": squared_relu,
    "sigmoid": jax.nn.sigmoid,
    "prelu_like": jax.nn.leaky_relu,
    "dice_like": jax.nn.sigmoid,    # DIN's Dice ≈ data-adaptive sigmoid gate
}


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------
def rope_angles(positions: jnp.ndarray, dim: int, base: float = 10000.0):
    """positions [*, S] int -> (cos, sin) [*, S, dim/2] fp32."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: [..., S, n_head, dim]; cos/sin broadcastable [..., S, 1, dim/2]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Numerically-stable CE; logits [*, V] fp32-accumulated, labels [*].

    The gold logit is extracted with a fused one-hot reduce, NOT
    take_along_axis: gathering along a vocab-sharded axis would force the
    partitioner to all-gather the logits (13+ GB/device at 4k×100k); the
    one-hot compare+select+reduce stays shard-local and fuses."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    v = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(labels.dtype, logits.shape, logits.ndim
                                    - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def bce_with_logits(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def count_params(tree) -> int:
    leaves = jax.tree.leaves(tree)
    return int(sum(np.prod(l.shape) for l in leaves))

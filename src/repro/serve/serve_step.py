"""Serving entry points per family — what `decode_*` / `serve_*` /
`retrieval_*` shape cells lower.

The recsys path can serve its feature columns out of a
``core.engine.MultiTableEngine``: one fused, deduplicated batch query
resolves every attribute/embedding table the request touches before the
jitted scoring step runs (paper Fig 2's query side in front of the model).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as cm
from repro.models import lm as lm_mod
from repro.models import recsys as rec_mod


def lm_decode_fn(cfg, mesh, mi):
    def step(params, token, pos, caches):
        return lm_mod.lm_decode_step(params, cfg, token, pos, caches, mesh,
                                     mi)
    return step


def lm_prefill_fn(cfg, mesh, mi):
    def step(params, tokens):
        h, _ = lm_mod.lm_backbone(params, cfg, tokens, mesh, mi)
        logits_last = lm_mod.lm_logits(params, cfg, h[:, -1:])[:, 0]
        return logits_last
    return step


def recsys_score_fn(cfg, mesh, mi, lookup_impl: str = "xla",
                    feature_engine=None,
                    feature_fields: Optional[Sequence[tuple]] = None,
                    feature_server=None,
                    feature_budget_s: Optional[float] = None,
                    feature_client=None,
                    feature_qos="RANKING"):
    """Scoring step; with a feature source the step first resolves
    ``feature_fields`` — ``(table_name, batch_field)`` pairs — in ONE fused
    batch query and splices the returned float32 rows into the batch's
    dense columns before the model runs.

    The feature source is a ``feature_client`` (``api.FeatureClient``, the
    API-v2 session — over a QueryServer its lookups coalesce with other
    in-flight scoring requests into QoS-laned micro-batches).  The PR-3
    shims remain for one release: ``feature_engine`` (a MultiTableEngine)
    and ``feature_server`` (a QueryServer) each wrap themselves in a
    client.  Exactly one of the three may be given.  Scoring lookups ride
    the ``feature_qos`` lane (default RANKING — this is the user-facing
    scoring path) with ``feature_budget_s`` as their latency budget."""
    def step(params, batch):
        return rec_mod.recsys_score(params, cfg, batch, mi, mesh,
                                    lookup_impl)

    sources = [s for s in (feature_engine, feature_server, feature_client)
               if s is not None]
    if len(sources) > 1:
        raise ValueError("pass exactly one of feature_client / "
                         "feature_engine / feature_server")
    if not sources:
        return step

    from repro.api.client import FeatureClient
    from repro.api.types import QoSClass
    client = (feature_client if feature_client is not None
              else FeatureClient(sources[0]))
    qos = QoSClass.parse(feature_qos)

    def resolve(request):
        return client.query(request, qos=qos, budget_s=feature_budget_s)

    fields = list(feature_fields or ())
    if not fields:
        raise ValueError("feature engine/server given but no feature_fields")
    names = [t for t, _ in fields]
    if len(set(names)) != len(names):
        raise ValueError("duplicate table names in feature_fields: one "
                         "fused request carries one key set per table")
    key_fields = {f for _, f in fields}
    # the model runs as one compiled program; the store key columns are
    # host-side inputs of the query and never reach it
    model_step = jax.jit(step)

    def step_with_store(params, batch):
        n_rows = len(np.asarray(batch["dense"]))
        request = {}
        for table, field in fields:
            ids = np.asarray(batch[field])
            if ids.ndim != 1 or len(ids) != n_rows:
                raise ValueError(
                    f"feature field {field!r} must be 1-D of length "
                    f"{n_rows} (one key per example), got {ids.shape}")
            request[table] = ids.astype(np.uint64)
        res = resolve(request)                   # one fused query, pinned
        cols = []
        for table, _field in fields:
            tr = res[table]
            if tr.values is not None:            # embedding: float32 rows
                rows = np.ascontiguousarray(tr.values).view(np.float32)
                rows = rows.reshape(len(tr.found), -1)
            else:                                # scalar: payload column
                rows = tr.payloads.astype(np.float32)[:, None]
            rows = rows * tr.found[:, None]      # misses contribute zeros
            cols.append(rows)
        feats = np.concatenate(cols, axis=-1)
        dense = np.array(batch["dense"])
        d = min(feats.shape[1], dense.shape[1])
        dense[:, :d] = feats[:, :d]
        batch = {k: v for k, v in batch.items() if k not in key_fields}
        batch["dense"] = jnp.asarray(dense)
        return model_step(params, batch)

    return step_with_store


def retrieval_fn(cfg, mesh, mi, top_k: int = 100):
    def step(params, batch, cand_ids, cand_cats):
        return rec_mod.retrieval_scores(params, cfg, batch, cand_ids,
                                        cand_cats, mi, top_k)
    return step


def bulk_rank_fn(cfg, mesh, mi, top_k: int = 100):
    """retrieval_cand for pointwise archs: score 1M candidate items for one
    user by broadcasting the user context over the candidate batch."""
    fwd = rec_mod.FORWARD[cfg.arch]

    def step(params, batch):
        logits = fwd(params, cfg, batch, mi)
        return jax.lax.top_k(logits, top_k)
    return step

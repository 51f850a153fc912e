"""EmbeddingBag built from scratch (JAX has none): ``jnp.take`` +
``jax.ops.segment_sum``, per the assignment notes.

Two forms:
  * ``embedding_bag``       — flat variable-length bags (ids + segment ids),
    the general production form;
  * ``fielded_lookup``      — fixed [B, n_fields, bag] layout with a mask,
    the static-shape fast path AutoInt uses (bag=1 ⇒ plain take).

Tables are stored as ONE fused [total_rows, dim] array (row-sharded over the
"model" mesh axis via the ``table_rows`` logical axis); per-field id spaces
are offset into it host-side.  ``sharded_lookup`` is the shard_map masked
local-take + psum variant that avoids materialising the full table on any
device (used when a mesh is active; beyond-paper wire optimization).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.api import constrain


def embedding_bag(table: jnp.ndarray, flat_ids: jnp.ndarray,
                  segment_ids: jnp.ndarray, n_segments: int,
                  *, weights: Optional[jnp.ndarray] = None,
                  combiner: str = "sum") -> jnp.ndarray:
    """Σ (or mean of) table[flat_ids] grouped by ``segment_ids``.

    flat_ids/segment_ids: [T] i32; padded entries use segment_id == n_segments.
    """
    rows = jnp.take(table, jnp.minimum(flat_ids, table.shape[0] - 1), axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    out = jax.ops.segment_sum(rows, segment_ids, num_segments=n_segments + 1)
    out = out[:n_segments]
    if combiner == "mean":
        ones = jnp.ones((flat_ids.shape[0],), rows.dtype)
        if weights is not None:
            ones = weights
        cnt = jax.ops.segment_sum(ones, segment_ids,
                                  num_segments=n_segments + 1)[:n_segments]
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out


def fielded_lookup(table: jnp.ndarray, ids: jnp.ndarray,
                   mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """ids [B, F] (or [B, F, bag]) of *global* row ids → [B, F, D].

    bag > 1 entries are sum-combined; masked entries contribute 0.
    """
    squeeze = ids.ndim == 2
    if squeeze:
        ids = ids[..., None]
    rows = jnp.take(table, jnp.minimum(ids, table.shape[0] - 1), axis=0)
    if mask is not None:
        m = mask if mask.ndim == ids.ndim else mask[..., None]
        rows = rows * m[..., None].astype(rows.dtype)
    return rows.sum(axis=2)


def sharded_lookup(table: jnp.ndarray, ids: jnp.ndarray, mesh: Mesh,
                   *, axis: str = "model") -> jnp.ndarray:
    """Masked local-take + psum: each device holds a row shard; ids outside
    the local range contribute zero and the psum assembles full rows.  Wire
    cost = |ids|·D instead of |table|·D (no table all-gather)."""
    n_rows = table.shape[0]
    n_shards = mesh.shape[axis]
    rows_loc = n_rows // n_shards

    def local(table_loc, ids):
        d = lax.axis_index(axis)
        lo = d * rows_loc
        local_ids = ids - lo
        ok = (local_ids >= 0) & (local_ids < rows_loc)
        rows = jnp.take(table_loc, jnp.clip(local_ids, 0, rows_loc - 1),
                        axis=0)
        rows = jnp.where(ok[..., None], rows, 0)
        return lax.psum(rows, axis)

    other = tuple(a for a in mesh.axis_names if a != axis)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis, None), P()),
                       out_specs=P(), check_vma=False)
    return fn(table, ids)


def build_field_offsets(rows_per_field: Sequence[int]) -> np.ndarray:
    """Host-side: per-field base offset into the fused table."""
    return np.concatenate([[0], np.cumsum(rows_per_field)[:-1]]).astype(
        np.int64)

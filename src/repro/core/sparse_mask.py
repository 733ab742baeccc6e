"""Structured group-CSR masks — the serving-side selection representation.

core/masks.py keeps selections as dense {0,1} tensors at neuron-group
granularity: right for training (the mask multiplies a tensor that was
computed anyway) but wrong for the serving hot path, where the point is to
NOT compute dropped groups.  The structured representation that turns a
mask into real compute savings is a per-row active-group index list
(group-level CSR): gathers over it are contiguous weight blocks, and a
host-side pattern update is an O(keep) integer write instead of a dense
tensor rebuild (Lasby et al., PAPERS.md; Graphcore popsparse / MindSpore
CSR, SNIPPETS.md).

A CSR row is (idx, count): `idx[:count]` are the active group indices in
ascending order, entries past `count` are zero-padded and must be ignored
(`csr_to_dense` and every consumer guard on `count`).  The row width is a
static *bound* bucketed to a power of two, so the decode step compiles at
most log2(G)+1 variants as per-lane counts drift, not one per count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def active_group_bound(max_count: int, n_groups: int) -> int:
    """Static CSR row width covering rows with up to `max_count` active
    groups: the count rounded up to a power of two, capped at G — decode
    compiles ≤ log2(G)+1 variants."""
    need = max(1, int(max_count))
    return min(1 << (need - 1).bit_length(), n_groups)


def active_group_buckets(n_groups: int) -> tuple:
    """Every bound active_group_bound can return for G groups — the set a
    warm pass pre-compiles and traffic models enumerate."""
    return tuple(sorted({min(1 << i, n_groups)
                         for i in range(n_groups.bit_length() + 1)}))


def dense_to_csr(mask: jax.Array, bound: int):
    """Dense group mask (..., G) -> (idx (..., bound), counts (...,)).

    jit-friendly (static output shapes): sorting the key
    `where(active, g, G + g)` lists active group indices first, each side
    ascending, so the leading `bound` entries are exactly the active list
    when `bound` covers the row's count (rows with more active groups than
    `bound` are truncated — size the bound with active_group_bound).
    Padded entries are zeroed so a row's representation is canonical
    (tests compare them directly)."""
    g = mask.shape[-1]
    active = mask > 0
    key = jnp.where(active, jnp.arange(g), g + jnp.arange(g))
    order = jnp.argsort(key, axis=-1)[..., :bound].astype(jnp.int32)
    counts = jnp.minimum(jnp.sum(active, axis=-1), bound).astype(jnp.int32)
    valid = jnp.arange(bound) < counts[..., None]
    return jnp.where(valid, order, 0), counts


def csr_to_dense(idx: jax.Array, counts: jax.Array,
                 n_groups: int) -> jax.Array:
    """(idx (..., K), counts (...,)) -> dense {0,1} float32 mask (..., G).
    Padded entries (positions >= count) are ignored, whatever they hold."""
    k = idx.shape[-1]
    valid = (jnp.arange(k) < counts[..., None]).astype(jnp.float32)
    oh = jax.nn.one_hot(idx, n_groups, dtype=jnp.float32)
    return jnp.minimum(jnp.einsum("...kg,...k->...g", oh, valid), 1.0)


def csr_rows(shape: tuple) -> int:
    rows = 1
    for s in shape:
        rows *= s
    return rows


def csr_overhead_bytes(batch_shape: tuple, bound: int,
                       idx_bytes: int = 4, count_bytes: int = 4) -> int:
    """Storage cost of the CSR pattern state: `bound` int32 indices plus
    one int32 count per row.  Compare masks.mask_overhead_bytes (1 bit per
    group per row): the bitmask is smaller at rest, but the CSR list is
    what the gather walks and what the host rewrites in O(keep) per
    refresh — the representation is priced for the decode loop, not for
    the stash."""
    return csr_rows(batch_shape) * (bound * idx_bytes + count_bytes)

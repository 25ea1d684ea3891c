"""Union-find for global label merging.

Replaces nifty.ufd.boost_ufd (reference thresholded_components/
merge_assignments.py:125-130, multicut/reduce_problem.py:161-163).

Two implementations:
  * ``union_find_np`` — host numpy, iterative with full path compression; used by
    single-shot merge tasks (these are 1-job reductions in the reference too).
  * ``merge_labels_device`` — pointer-jumping on device: given merge edges over a
    dense id space, converges parents in O(log n) gather sweeps under jit.  This
    is the building block for doing merges with ICI-resident data.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class UnionFindNp:
    """Array-based union-find with path compression (host)."""

    def __init__(self, n: int):
        self.parent = np.arange(n, dtype=np.int64)

    def find(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, dtype=np.int64)
        root = self.parent[x]
        # iterate until fixpoint (vectorized path walk)
        while True:
            nxt = self.parent[root]
            if (nxt == root).all():
                break
            root = nxt
        return root

    def merge(self, a: np.ndarray, b: np.ndarray) -> None:
        """Union pairs; roots are merged towards the smaller id."""
        a = np.asarray(a, dtype=np.int64).reshape(-1)
        b = np.asarray(b, dtype=np.int64).reshape(-1)
        # process iteratively: after each pass re-root and re-link
        while a.size:
            ra = self.find(a)
            rb = self.find(b)
            ne = ra != rb
            ra, rb = ra[ne], rb[ne]
            if ra.size == 0:
                break
            lo = np.minimum(ra, rb)
            hi = np.maximum(ra, rb)
            # link hi → lo; duplicate hi entries keep the smallest target
            order = np.lexsort((lo, hi))
            hi, lo = hi[order], lo[order]
            first = np.concatenate([[True], hi[1:] != hi[:-1]])
            self.parent[hi[first]] = lo[first]
            a, b = ra, rb  # re-check remaining conflicts next pass

    def compress(self) -> np.ndarray:
        """Full path compression; returns the root of every element."""
        while True:
            nxt = self.parent[self.parent]
            if (nxt == self.parent).all():
                break
            self.parent = nxt
        return self.parent


def _finalize_roots(
    roots: np.ndarray, consecutive: bool
) -> Tuple[np.ndarray, int]:
    roots[0] = 0
    if not consecutive:
        return roots, int(roots.max())
    uniq, inv = np.unique(roots, return_inverse=True)
    if uniq.size and uniq[0] == 0:
        assignment = inv.astype(np.int64)
        n_new = uniq.size - 1
    else:
        assignment = (inv + 1).astype(np.int64)
        n_new = uniq.size
    assignment[0] = 0
    return assignment, int(n_new)


def merge_assignments_np(
    n_labels: int, pairs: np.ndarray, consecutive: bool = True
) -> Tuple[np.ndarray, int]:
    """Merge equivalence ``pairs`` over ids [0, n_labels) and return a dense
    assignment array old_id → new_id (0 fixed to 0) plus the new max id."""
    uf = UnionFindNp(n_labels)
    if pairs.size:
        uf.merge(pairs[:, 0], pairs[:, 1])
    return _finalize_roots(uf.compress(), consecutive)


def merge_assignments_device(
    n_labels: int, pairs: np.ndarray, consecutive: bool = True
) -> Tuple[np.ndarray, int]:
    """Device analog of ``merge_assignments_np``: the id space lives on the
    mesh and equivalences resolve by pointer jumping (``merge_labels_device``)
    instead of a host union-find — the ICI replacement for the reference's
    1-job boost_ufd merge (merge_assignments.py:125-130).  Falls back to the
    host path when the id space exceeds int32."""
    if n_labels >= np.iinfo(np.int32).max:
        return merge_assignments_np(n_labels, pairs, consecutive)
    parent = jnp.arange(n_labels, dtype=jnp.int32)
    if pairs.size:
        edges = jnp.asarray(np.ascontiguousarray(pairs, dtype=np.int32))
    else:
        edges = jnp.zeros((1, 2), jnp.int32)
    roots = np.asarray(merge_labels_device(parent, edges)).astype(np.int64)
    return _finalize_roots(roots, consecutive)


def merge_value_table(
    a_vals: jnp.ndarray, b_vals: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Union-find over the *values* appearing in equivalence pairs
    ``(a_vals[i], b_vals[i])`` — the compact form of ``merge_labels_device``
    for id spaces the program cannot bound: the hierarchical flood's
    segment tables (ops/hier.py) and the all-gathered shard faces of
    parallel/sharded.py's stage 2.  The parent table covers only the values
    that occur in the pairs (O(#pairs) entries), not the id range they are
    drawn from.  Values that are flat indices of one block (the coarse CC's
    tile faces, ``ops.cc.merge_tiled_labels``) take
    :func:`merge_dense_values` instead, which indexes that range directly
    rather than binary-searching a sorted table.

    Padding slots must carry the same value on both sides (self-loops merge
    nothing).  Returns ``(vals, root_vals)``: ``vals`` is the sorted multiset
    of all pair values; ``root_vals[i]`` is the minimum value of the
    equivalence class of ``vals[i]``.  Apply with :func:`apply_value_roots`.

    Min semantics ride the sort: compacted ids (positions in ``vals``) are
    order-isomorphic to the values, so ``merge_labels_device``'s link-to-min
    over ids resolves each class to its minimal *value*.  Duplicate values
    share their leftmost slot (``searchsorted`` side='left'); the orphaned
    right slots stay self-rooted and are never referenced.
    """
    vals = jnp.sort(jnp.concatenate([a_vals, b_vals]))
    n = vals.shape[0]
    ca = jnp.searchsorted(vals, a_vals).astype(jnp.int32)
    cb = jnp.searchsorted(vals, b_vals).astype(jnp.int32)
    edges = jnp.stack([ca, cb], axis=1)
    roots = merge_labels_device(jnp.arange(n, dtype=jnp.int32), edges)
    return vals, vals[roots]


def apply_value_roots(
    x: jnp.ndarray, vals: jnp.ndarray, root_vals: jnp.ndarray
) -> jnp.ndarray:
    """Map every element of ``x`` through a resolved value table from
    :func:`merge_value_table`; values absent from ``vals`` pass through
    unchanged (components never touching a boundary keep their label)."""
    n = vals.shape[0]
    idx = jnp.clip(jnp.searchsorted(vals, x), 0, n - 1).astype(jnp.int32)
    hit = vals[idx] == x
    return jnp.where(hit, root_vals[idx], x)


def merge_dense_values(
    x: jnp.ndarray,
    a_vals: jnp.ndarray,
    b_vals: jnp.ndarray,
    id_offset,
    size: int,
    sentinel,
) -> jnp.ndarray:
    """``apply_value_roots(x, *merge_value_table(a_vals, b_vals))`` for
    values drawn from one dense range: every value of ``x``, ``a_vals`` and
    ``b_vals`` lies in ``[id_offset, id_offset + size)`` or equals
    ``sentinel``.  Each value has a slot (``value - id_offset``, the
    sentinel ``size``), so the lookups are gathers from tables of
    ``size + 1`` entries instead of binary searches.

    The values that occur in the pairs are numbered by a prefix count over
    their slots; those compact ids are order-isomorphic to the values, so
    ``merge_labels_device``'s link-to-min resolves each class to its minimal
    value, and the table stays O(#pairs) like ``merge_value_table``'s.
    """
    def slot(v):
        return jnp.where(v == sentinel, size, v - id_offset).astype(jnp.int32)

    m = a_vals.shape[0]
    s = slot(jnp.concatenate([a_vals, b_vals]))
    present = jnp.zeros((size + 1,), bool).at[s].set(True)
    compact = jnp.cumsum(present, dtype=jnp.int32) - 1
    c = compact[s]
    n = min(s.shape[0], size + 1)
    roots = merge_labels_device(
        jnp.arange(n, dtype=jnp.int32), jnp.stack([c[:m], c[m:]], axis=1)
    )
    slot_of = jnp.zeros((n,), jnp.int32).at[c].set(s)
    table = jnp.where(
        present,
        slot_of[roots][jnp.maximum(compact, 0)],
        jnp.arange(size + 1, dtype=jnp.int32),
    )
    out = table[slot(x)]
    return jnp.where(out == size, sentinel, out + id_offset).astype(x.dtype)


@partial(jax.jit)
def merge_labels_device(parent: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """Device merge: ``parent`` is a dense [n] parent array, ``edges`` [m,2]
    merge requests (may contain padding rows with a == b).

    Iterates (link-to-min over edges, then pointer jumping) until stable.
    Returns the fully compressed root array.
    """
    n = parent.shape[0]

    def cond(state):
        parent, changed = state
        return changed

    def body(state):
        parent, _ = state
        ra = parent[edges[:, 0]]
        rb = parent[edges[:, 1]]
        lo = jnp.minimum(ra, rb)
        hi = jnp.maximum(ra, rb)
        # link: parent[hi] <- min(parent[hi], lo); scatter-min resolves dups
        new = parent.at[hi].min(lo)
        # pointer jumping (two hops per sweep)
        new = new[new]
        new = new[new]
        return (new, jnp.any(new != parent))

    parent, _ = lax.while_loop(cond, body, (parent, jnp.bool_(True)))
    return parent

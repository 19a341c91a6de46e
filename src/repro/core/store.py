"""Versioned object store - the ``objects_store`` register array of the paper.

Layout per node (paper §III.A.1, adapted):

* ``values[K, V, W]``  - K objects x V version cells x W value words.
  Cell 0 always holds the last *tail-committed* ("clean") value.  Cells
  ``1..pending`` hold dirty (not yet acknowledged) versions in increasing
  sequence order.
* ``seqs[K, V]``       - the write sequence number of each stored version.
* ``pending[K]``       - number of dirty versions; the object is *clean* iff
  ``pending == 0`` (the paper's implicit-state trick: clean iff the latest
  value lives in the first cell).  The paper keeps two duplicate registers
  (``read_index`` / ``write_index``) because a Tofino register can be
  accessed once per pipeline pass; TPUs have no such constraint so we keep
  one array (deviation documented in DESIGN.md §3).
* ``next_seq[K]``      - per-key monotone counter used by the entry node to
  stamp client writes (our 32-bit answer to NetChain's 16-bit SEQ overflow).

All operations are functional (return a new ``Store``) and *batch
serialized*: concurrent writes to the same key within one query batch get
consecutive version slots via a stable within-batch rank, so the result is
identical to processing the batch one query at a time.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import ChainConfig


class Store(NamedTuple):
    values: jax.Array    # [K, V, W] int32
    seqs: jax.Array      # [K, V] int32 (-1 = empty cell)
    pending: jax.Array   # [K] int32
    next_seq: jax.Array  # [K] int32

    @property
    def num_keys(self) -> int:
        return self.values.shape[0]


def init_store(cfg: ChainConfig) -> Store:
    K, V, W = cfg.num_keys, cfg.num_versions, cfg.value_words
    return Store(
        values=jnp.zeros((K, V, W), jnp.int32),
        seqs=jnp.full((K, V), -1, jnp.int32).at[:, 0].set(0),
        pending=jnp.zeros((K,), jnp.int32),
        next_seq=jnp.ones((K,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Batch-rank helpers (serialization semantics within a batch)
# ---------------------------------------------------------------------------
def batch_rank(keys: jax.Array, active: jax.Array,
               dense: bool = False) -> jax.Array:
    """rank[i] = #{j < i : active[j] and keys[j] == keys[i]} for active i
    (stable order); inactive entries rank 0.

    Default is a segmented-sort ranking, O(B log B): two stable argsorts
    group entries by (active, key) preserving batch order, the rank is the
    offset within the run.  ``dense=True`` keeps the original O(B^2)
    bitmatrix (the pre-segmented engine's version - the ``fabric="dense"``
    baseline in benchmarks/fig_tick_cost.py; at the head txn stage's
    B = n * capacity the bitmatrix dominated the tick).
    """
    b = keys.shape[0]
    if dense:
        same = (
            (keys[None, :] == keys[:, None])
            & active[None, :] & active[:, None]
        )
        lower = jnp.tril(jnp.ones((b, b), bool), k=-1)
        return jnp.sum(same & lower, axis=1).astype(jnp.int32)
    active = active.astype(bool)
    o1 = jnp.argsort(keys, stable=True)            # by (key, batch idx)
    o2 = jnp.argsort(~active[o1], stable=True)     # active runs first
    order = o1[o2]                                 # by (inactive, key, idx)
    s_keys = keys[order]
    s_active = active[order]
    boundary = jnp.concatenate([
        jnp.ones((1,), bool),
        (s_keys[1:] != s_keys[:-1]) | (s_active[1:] != s_active[:-1]),
    ])
    j = jnp.arange(b, dtype=jnp.int32)
    run_start = jax.lax.cummax(jnp.where(boundary, j, 0))
    rank_sorted = jnp.where(s_active, j - run_start, 0)
    return jnp.zeros((b,), jnp.int32).at[order].set(rank_sorted)


# ---------------------------------------------------------------------------
# Reads
# ---------------------------------------------------------------------------
# Rows are read and written whole, through the [K, V*W] view of ``values``.
# The TPU gathers and scatters the rows of a 2-D array with the key axis
# minor, so every access to the store asks for one layout, and the tick
# copies no store into another layout between its reads and its writes.
def _get_rows(store: Store, keys: jax.Array):
    """The rows ``keys`` name: values [B, V, W] and seqs [B, V]."""
    K, V, W = store.values.shape
    vals = store.values.reshape(K, V * W)[keys].reshape(-1, V, W)
    return vals, store.seqs[keys]


def read_clean(store: Store, keys: jax.Array):
    """Value + seq of the committed version (cell 0). [B] -> ([B,W],[B])."""
    return store.values[keys, 0], store.seqs[keys, 0]


def read_latest(store: Store, keys: jax.Array):
    """Latest version: the newest dirty cell if any, else cell 0 (tail's
    dirty_read in Algorithm 1)."""
    slot = store.pending[keys]  # dirty cells live at 1..pending; latest == pending
    return (
        store.values[keys, slot],
        store.seqs[keys, slot],
    )


def _cell(x: jax.Array, pick: jax.Array) -> jax.Array:
    """The cell of each row of ``x`` [B, V, ...] that the one-hot ``pick``
    [B, V] marks: a masked sum over the V cells, not a gather."""
    pick = pick.reshape(pick.shape + (1,) * (x.ndim - 2))
    return jnp.where(pick, x, 0).sum(axis=1, dtype=x.dtype)


def read_versions(store: Store, keys: jax.Array):
    """``read_clean`` and ``read_latest`` of ``keys`` from one gather of
    their rows: ((value [B,W], seq [B]) of cell 0, the same of the newest
    cell)."""
    vals, seqs = _get_rows(store, keys)
    latest = jnp.arange(vals.shape[1]) == store.pending[keys][:, None]
    return (vals[:, 0], seqs[:, 0]), (_cell(vals, latest), _cell(seqs, latest))


def is_clean(store: Store, keys: jax.Array) -> jax.Array:
    return store.pending[keys] == 0


# ---------------------------------------------------------------------------
# Writes
# ---------------------------------------------------------------------------
def _put_rows(store: Store, keys, vals, seqs, pending, rep) -> Store:
    """Write whole rows back: entry ``i`` with ``rep[i]`` replaces row
    ``keys[i]`` with ``vals[i]``, ``seqs[i]`` and ``pending[i]``.  At most one
    entry per key may be marked (``first_of_key``); the others scatter out
    of bounds and are dropped, so duplicate keys cannot race (XLA scatter
    order with duplicate indices is undefined).  Rows no entry marks are
    neither read nor written."""
    K, V, W = store.values.shape
    row = jnp.where(rep, keys, K)  # out-of-bounds sentinel row
    put = lambda a, x: a.at[row].set(x, mode="drop")
    return store._replace(
        values=put(store.values.reshape(K, V * W),
                   vals.reshape(-1, V * W)).reshape(K, V, W),
        seqs=put(store.seqs, seqs),
        pending=put(store.pending, pending),
    )


def _same_key(keys: jax.Array, live: jax.Array) -> jax.Array:
    """[B, B]: entry ``j`` is live and names the key of entry ``i``.  A
    batch is a node's inbox, so comparing it with itself costs less than a
    scatter into a [K] vector and the gather back."""
    return (keys[:, None] == keys[None, :]) & live[None, :]


def first_index(keys: jax.Array, live: jax.Array) -> jax.Array:
    """For each entry, the batch index of the first live entry with its key
    (the batch size where there is none)."""
    b = keys.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    return jnp.where(_same_key(keys, live), idx[None, :], b).min(axis=1)


def first_of_key(keys: jax.Array, live: jax.Array) -> jax.Array:
    """True at the first live entry of each key: one entry per distinct
    live key."""
    idx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    return live & (first_index(keys, live) == idx)


def assign_seqs(store: Store, keys: jax.Array, needs: jax.Array,
                dense_rank: bool = False):
    """Stamp unsequenced client writes with per-key monotone seqs.

    Returns (new_store, seqs[B]).  Entries with needs==False keep seq
    untouched (-1 sentinel replaced by caller).
    """
    rank = batch_rank(keys, needs, dense=dense_rank)
    seqs = store.next_seq[keys] + rank
    counts = jnp.zeros((store.num_keys,), jnp.int32).at[keys].add(
        needs.astype(jnp.int32))
    new_next = store.next_seq + counts
    return store._replace(next_seq=new_next), jnp.where(needs, seqs, -1)


def append_dirty(store: Store, keys, values, seqs, active,
                 dense_rank: bool = False):
    """Append dirty versions at cells ``pending+1+rank``; drop if the window
    is exceeded (Algorithm 1 line 22-23).

    The accepted appends of a key land in the row of its first accepted
    entry, which is written back whole.  Returns (new_store, accepted[B]).
    """
    V = store.values.shape[1]
    b = keys.shape[0]
    rank = batch_rank(keys, active, dense=dense_rank)
    pending = store.pending[keys]
    slot = pending + 1 + rank
    accepted = active & (slot <= V - 1)
    first = first_index(keys, accepted)
    rep = accepted & (first == jnp.arange(b, dtype=jnp.int32))
    # (first, slot) pairs are unique among accepted entries by
    # construction; rejected entries scatter out of bounds.
    at = (jnp.where(accepted, first, b), slot)
    row_vals, row_seqs = _get_rows(store, keys)
    row_vals = row_vals.at[at].set(values, mode="drop")
    row_seqs = row_seqs.at[at].set(seqs, mode="drop")
    n_new = jnp.zeros((b,), jnp.int32).at[at[0]].add(1, mode="drop")
    return (
        _put_rows(store, keys, row_vals, row_seqs, pending + n_new, rep),
        accepted,
    )


def commit(store: Store, keys, values, seqs, active):
    """Tail commit / ACK application: install ``value`` as the clean version
    of ``key`` (cell 0) for the *largest* seq per key in the batch, then
    compact: delete all dirty versions with seq <= committed seq and shift
    the remainder down (versions are stored in increasing seq order).

    Only the rows the batch names are read and written, O(B*V*W) beside
    the [B, B] comparison of the batch's keys: each touched key's row is
    rebuilt from the first entry that carries its largest seq.  A key
    whose active entries all carry a negative seq is left as it is.
    """
    V = store.values.shape[1]
    active = active.astype(bool)

    # Per-key max committed seq in this batch (acks are cumulative).
    ack_seq = jnp.where(_same_key(keys, active), seqs[None, :], -1).max(axis=1)
    rep = first_of_key(keys, active & (seqs == ack_seq) & (ack_seq >= 0))

    row_vals, row_seqs = _get_rows(store, keys)
    seq0 = row_seqs[:, 0]
    # The representative supplies cell 0 if it is newer than the committed
    # version; the monotone guard never rolls the committed seq backwards.
    newer = seqs > seq0
    cell0 = jnp.where(newer[:, None], values, row_vals[:, 0])
    effective = jnp.maximum(seqs, seq0)  # per-key commit floor after batch

    # Compact the dirty region: keep dirty cells with seq > effective.
    cell_idx = jnp.arange(V)[None, :]
    dirty = (cell_idx >= 1) & (cell_idx <= store.pending[keys][:, None])
    keep = dirty & (row_seqs > effective[:, None])
    n_keep = keep.sum(axis=1).astype(jnp.int32)
    # The cells in the order of a stable sort on ~keep: kept dirty cells
    # first, in original (seq) order, then the others in theirs.
    to = jnp.where(keep, jnp.cumsum(keep, axis=1),
                   n_keep[:, None] + jnp.cumsum(~keep, axis=1)) - 1
    moved = to[:, None, :] == jnp.arange(V)[None, :, None]  # [B, to, from]
    kept_vals = jax.vmap(_cell, (None, 1), 1)(row_vals, moved)
    kept_seqs = jax.vmap(_cell, (None, 1), 1)(row_seqs, moved)

    # Shift kept versions to cells 1..n and blank the cells beyond them.
    new_vals = jnp.concatenate([cell0[:, None, :], kept_vals[:, : V - 1]], axis=1)
    new_seqs = jnp.concatenate(
        [jnp.where(newer, seqs, seq0)[:, None], kept_seqs[:, : V - 1]], axis=1)
    new_seqs = jnp.where(cell_idx <= n_keep[:, None], new_seqs, -1)
    return _put_rows(store, keys, new_vals, new_seqs, n_keep, rep)


def overwrite_clean(store: Store, keys, values, seqs, active):
    """NetChain-style single-version write: cell 0 := value iff seq newer
    (SEQ mitigates out-of-order delivery, paper §II.B.2)."""
    active = active.astype(bool)
    newer = active & (seqs > store.seqs[keys, 0])
    # Serialize same-key duplicates: highest seq wins; losers drop OOB.
    K = store.num_keys
    best = jnp.full((K,), -1, jnp.int32).at[keys].max(jnp.where(newer, seqs, -1))
    win = newer & (seqs == best[keys])
    safe_key = jnp.where(win, keys, K)
    cell0 = store.values[:, 0, :]
    new_cell0 = cell0.at[safe_key].set(values, mode="drop")
    seq0 = store.seqs[:, 0]
    new_seq0 = seq0.at[safe_key].set(seqs, mode="drop")
    return store._replace(
        values=store.values.at[:, 0, :].set(new_cell0),
        seqs=store.seqs.at[:, 0].set(new_seq0),
    )

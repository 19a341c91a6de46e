"""Device-side telemetry plane: latency histograms, flight-recorder ring,
sampled per-hop packet traces - all living INSIDE the jitted tick.

The paper's headline claims are latency-*distribution* claims, and the
Programmable Data Plane survey frames INT-style switch-local telemetry as
the observability substrate such systems need.  This module is that
substrate for the simulator: three fixed-shape int32 state groups that ride
``SimState.telemetry`` as traced arguments (never Python constants - the
RL002 contract), are donated and updated inside the same device program as
the data path (zero host round-trips while the engine runs).  On one TPU v5e
the plane (its ``telemetry`` stage: both recorders and the ring row) took
0.24 ms of a 390 ms NetCRAQ tick and 0.25 ms of a 90 ms NetChain tick
(``telemetry_ms`` of the benchmark's two YCSB-B cells).

1. **Latency histogram** ``lat_hist [OPCLASS, BKT]``: log2-bucketed
   ``ticks_in_flight`` of every reply that exits to a client, scattered over
   the SAME exit batch ``ReplyLog.append`` sees, split by op class
   (read/write/txn/nack - ``core/types.py::reply_op_class``).  Unlike the
   fixed-capacity reply log, the histogram never overflows: percentiles
   survive unbounded run lengths.
2. **Flight-recorder ring** ``ring [W, N_RING_FIELDS]``: one health row per
   tick (``RING_FIELDS``) at a wrapping cursor - a last-W-ticks window for
   postmortems and for the Balancer of ROADMAP item 1.  ``ring_cursor``
   counts *total* rows ever written (the write index is ``cursor % W``), so
   the host can both unwrap the window and tell how far it wrapped.
3. **Sampled packet traces** ``trace_* [S, HOPS]``: the INT analogue - a
   qid-hash-sampled per-hop event buffer recording (node, tick, op) for
   ~1/64 of queries.  Slots are direct-mapped by the hash, claimed by the
   first sampled arrival while free, and record one event per tick (the
   tick-synchronous engine processes a query at one node per tick; ties
   within a tick resolve to the lowest flat inbox index, so traces are a
   pure function of the schedule - determinism is pinned by
   tests/test_telemetry.py).  Exit events are the reply log's job.

Everything here is shape-static and branch-free; ``Telemetry.empty(0,0,0,0)``
produces zero-size leaves that compile the whole plane out bit-identically
(the ``wave_depth == 0`` pattern - see ``ChainSim(telemetry=False)``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import N_OPCLASS, OP_NOP, reply_op_class

# Flight-recorder ring columns, in row order.  Counter-typed fields
# (drops .. stale_routes) are per-tick deltas of the matching Metrics
# counters; gauge-typed fields (inflight, inbox_high_water, wave_occupancy)
# are end-of-tick readings.
RING_FIELDS = (
    "tick",              # SimState.t the row describes
    "inflight",          # live messages in the chain's inbox after the tick
    "inbox_high_water",  # max live messages at any single node's inbox
    "drops",             # fabric drops this tick
    "lock_conflicts",    # PREPARE_NACKs this tick
    "wave_occupancy",    # active wave-table slots (0 when wave_depth == 0)
    "replies",           # client replies landed this tick
    "stale_routes",      # stale-map NACK redirects this tick
)
N_RING_FIELDS = len(RING_FIELDS)

# qid-hash sampling: a query is traced iff the low TRACE_SAMPLE_BITS bits of
# the mixed hash are zero (~1 in 2**TRACE_SAMPLE_BITS = 1/64).  The xor-fold
# matters: qids are dense sequential integers, so any multiply-only hash
# taken mod a power of two would degenerate to ``qid % 64``.
TRACE_SAMPLE_BITS = 6

# Host-side default for histogram width: 16 log2 buckets cover latencies up
# to 2**15 ticks, far beyond any workload this repo runs.
DEFAULT_HIST_BUCKETS = 16


class Telemetry(NamedTuple):
    """Per-chain telemetry state (the engine vmaps this over the chain axis,
    so every leaf grows a leading [C] in ``SimState.telemetry``).  All
    leaves are strong int32 - same dtype-pin contract (RL003) as ``Msg``."""

    lat_hist: jax.Array     # [OPCLASS, BKT] exit-latency histogram
    ring: jax.Array         # [W, N_RING_FIELDS] flight-recorder rows
    ring_cursor: jax.Array  # [] total rows written (write idx = cursor % W)
    trace_qid: jax.Array    # [S] qid owning each trace slot (-1 = free)
    trace_node: jax.Array   # [S, H] node of each recorded hop event
    trace_tick: jax.Array   # [S, H] tick of each recorded hop event
    trace_op: jax.Array     # [S, H] opcode observed at each hop event
    trace_len: jax.Array    # [S] hop events recorded (clipped at H)

    @staticmethod
    def empty(hist_buckets: int, ring_window: int, trace_slots: int,
              trace_hops: int) -> "Telemetry":
        """Fresh per-chain telemetry.  Zero-size dims (telemetry off)
        produce zero-element leaves that still ride the pytree, so the
        SimState structure - and therefore the jit cache - is identical
        whether the plane is live or compiled out."""
        z = lambda *s: jnp.zeros(s, jnp.int32)
        return Telemetry(
            lat_hist=z(N_OPCLASS, hist_buckets),
            ring=z(ring_window, N_RING_FIELDS),
            ring_cursor=z(),
            trace_qid=jnp.full((trace_slots,), -1, jnp.int32),
            trace_node=z(trace_slots, trace_hops),
            trace_tick=z(trace_slots, trace_hops),
            trace_op=z(trace_slots, trace_hops),
            trace_len=z(trace_slots),
        )


def latency_bucket(ticks, n_buckets: int):
    """log2 bucket index of a tick count: bucket b covers [2**b, 2**(b+1)),
    the top bucket is open-ended, and ticks clamp at 1 (every exit is at
    least one tick in flight).  Branch-free comparison-sum, array-friendly
    for jax and numpy inputs alike - the host-side percentile math uses the
    same function, so parity is structural, not numerical luck."""
    t = jnp.maximum(jnp.asarray(ticks, jnp.int32), 1)
    edges = jnp.asarray([1 << j for j in range(1, n_buckets)], jnp.int32)
    return jnp.sum((t[..., None] >= edges).astype(jnp.int32), axis=-1)


def record_latency(lat_hist: jax.Array, op, seq, ticks) -> jax.Array:
    """Accumulate one exit batch into the [OPCLASS, BKT] histogram.  The
    batch is the tick's masked exit set - NOP padding and anything
    ``reply_op_class`` leaves at -1 count nowhere (their one-hot row is
    all zero).  One-hot matmul, NOT a scatter: XLA:CPU serializes
    scatter updates per element (the same cost the segmented fabric
    removed from reply logging), while ``[M, OPCLASS]^T @ [M, BKT]`` is
    a tiny GEMM.  float32 accumulation is exact (counts << 2**24)."""
    n_buckets = lat_hist.shape[1]
    cls = reply_op_class(op, seq)
    b = latency_bucket(ticks, n_buckets)
    cls_oh = (cls[:, None] == jnp.arange(N_OPCLASS, dtype=jnp.int32)
              ).astype(jnp.float32)
    bkt_oh = (b[:, None] == jnp.arange(n_buckets, dtype=jnp.int32)
              ).astype(jnp.float32)
    return lat_hist + (cls_oh.T @ bkt_oh).astype(jnp.int32)


def trace_hash(qid):
    """Mixed sampling hash (xor-fold; see TRACE_SAMPLE_BITS note)."""
    q = jnp.asarray(qid, jnp.int32)
    return q ^ (q >> TRACE_SAMPLE_BITS) ^ (q >> (2 * TRACE_SAMPLE_BITS))


def trace_sampled(qid):
    """True for the ~1/64 of qids the trace buffer samples."""
    mask = (1 << TRACE_SAMPLE_BITS) - 1
    return (trace_hash(qid) & mask) == 0


def trace_slot_of(qid, n_slots: int):
    """Direct-mapped trace slot of a sampled qid."""
    return (trace_hash(qid) >> TRACE_SAMPLE_BITS) % n_slots


def record_trace(tel: Telemetry, op, qid, node, t) -> Telemetry:
    """Record this tick's hop events into the sampled trace buffer.

    ``op/qid/node`` are the flattened per-chain arrival batch (every message
    a node observed this tick, pre-admission, so stale-NACKed arrivals are
    visible too).  Per slot, at most ONE event records per tick - the
    lowest-flat-index arrival of the slot's owning qid - selected with two
    dense [S, M] min-reductions instead of a sort or a scatter-min (both
    serialize on XLA:CPU, where this form was chosen).
    """
    n_slots, n_hops = tel.trace_node.shape
    m = op.shape[0]
    live = (op != OP_NOP) & (qid >= 0)
    samp = live & trace_sampled(qid)
    slot = jnp.where(samp, trace_slot_of(qid, n_slots), n_slots)
    idx = jnp.arange(m, dtype=jnp.int32)
    slot_ids = jnp.arange(n_slots, dtype=jnp.int32)
    in_slot = slot[None, :] == slot_ids[:, None]  # [S, M]

    # free slots claim the tick's first sampled arrival mapping to them
    first = jnp.min(jnp.where(in_slot, idx[None, :], m), axis=1)
    claim = (first < m) & (tel.trace_qid < 0)
    first_c = jnp.clip(first, 0, jnp.maximum(m - 1, 0))
    owner = jnp.where(claim, qid[first_c], tel.trace_qid).astype(jnp.int32)

    # events owned by their slot; the first per slot records this tick
    own = samp & (owner[jnp.clip(slot, 0, jnp.maximum(n_slots - 1, 0))] == qid)
    ev = jnp.min(jnp.where(in_slot & own[None, :], idx[None, :], m), axis=1)
    got = ev < m
    ev_c = jnp.clip(ev, 0, jnp.maximum(m - 1, 0))

    pos = tel.trace_len
    write = got & (pos < n_hops)  # hops beyond H are dropped, len saturates
    rows = jnp.where(write, jnp.arange(n_slots, dtype=jnp.int32), n_slots)
    cols = jnp.clip(pos, 0, jnp.maximum(n_hops - 1, 0))
    tick_col = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (n_slots,))
    return tel._replace(
        trace_qid=owner,
        trace_node=tel.trace_node.at[rows, cols].set(
            node[ev_c].astype(jnp.int32), mode="drop"
        ),
        trace_tick=tel.trace_tick.at[rows, cols].set(tick_col, mode="drop"),
        trace_op=tel.trace_op.at[rows, cols].set(
            op[ev_c].astype(jnp.int32), mode="drop"
        ),
        trace_len=jnp.where(
            got, jnp.minimum(pos + 1, n_hops), pos
        ).astype(jnp.int32),
    )


def record_ring(tel: Telemetry, row: jax.Array) -> Telemetry:
    """Write one [N_RING_FIELDS] health row at the wrapping cursor and
    advance it.  Only called when the ring is live (W >= 1)."""
    window = tel.ring.shape[0]
    cur = tel.ring_cursor
    return tel._replace(
        ring=jax.lax.dynamic_update_slice_in_dim(
            tel.ring, row[None].astype(jnp.int32), cur % window, axis=0
        ),
        ring_cursor=jnp.asarray(cur + 1, jnp.int32),
    )

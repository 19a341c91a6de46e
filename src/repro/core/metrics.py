"""Traffic and latency accounting - reproduces the paper's evaluation units.

Counting rules (paper §II.B): one *packet* per link traversal.  A read that
enters an n-node NetChain at the head costs 2n packets (client leg, n-1
forwards to the tail, n-1 reply relays, client leg).  A NetCRAQ clean read
costs 2 packets wherever it enters.  Multicast ACKs count one packet per
link per recipient (the PRE generates the copies; each still crosses links).

Latency model (used by the benchmarks to convert sim ticks to microseconds):

    latency_us = hops * T_HOP_US
               + kv_procs * (T_PARSE_PER_BYTE_US * header_bytes + T_OP_US)
               + queueing delay (M/D/1, from measured engine service rate)

The per-hop and per-byte constants are calibrated in benchmarks/common.py
from measured engine throughput on this host; EXPERIMENTS.md documents the
measured/modeled split.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Metrics(NamedTuple):
    packets: jax.Array        # link traversals
    bytes: jax.Array          # header+payload bytes crossing links
    kv_procs: jax.Array       # match-action pipeline passes (KV processing)
    reads_in: jax.Array
    writes_in: jax.Array
    replies: jax.Array
    dirty_appends: jax.Array  # dirty commits (paper Fig.5, right axis)
    store_rows: jax.Array     # store rows the node steps' commits rewrote
                              # (NetCRAQ ACKs and tail commits: one per
                              # distinct key a node commits in a tick)
    drops: jax.Array          # inbox-capacity drops, out-of-window drops,
                              # and traffic black-holed by dead nodes
    relay_procs: jax.Array    # reply-relay passes (CR retrace; IP-forwarded,
                              # not KVS pipeline work)
    write_nacks: jax.Array    # client writes rejected while writes_frozen
                              # (recovery copy window; excluded from replies)
    txn_commits: jax.Array    # COMMIT sub-ops accepted at the head (lock
                              # released, write admitted to the chain)
    txn_aborts: jax.Array     # ABORT sub-ops that released a held lock
    lock_conflicts: jax.Array # PREPAREs denied at the head (lock held by
                              # another txn, frozen chain, or misdirection)
    stale_routes: jax.Array   # client ops NACK-redirected at the entry node
                              # because they were routed under a stale
                              # partition map (OP_STALE_NACK; excluded from
                              # replies - the client re-routes and retries)
    migration_moves: jax.Array  # bucket migrations this chain participated
                                # in (source or destination; bumped by the
                                # CP's complete_rebalance, not by the tick)
    wave_commits: jax.Array   # transactions the in-network wave coordinator
                              # completed as committed (core/txn.py wave table)
    wave_aborts: jax.Array    # wave transactions completed as aborted
    wave_occupancy: jax.Array # sum over ticks of occupied wave slots - divide
                              # by ticks for mean coordinator occupancy
    offered: jax.Array        # client ops the open-loop generator addressed
                              # to this chain (pre-admission; includes the
                              # ops later shed) - the denominator of every
                              # offered-vs-served curve.  Bumped by
                              # ``ChainSim.run_openloop``, never by the tick
    admission_drops: jax.Array  # open-loop arrivals shed at admission: the
                                # generator's deferred-arrival backlog was
                                # full, so the op never entered an inbox.
                                # Distinct from ``drops`` (in-fabric losses)
                                # - nonzero admission_drops IS the overload
                                # signal past the hockey-stick knee
    lease_expiries: jax.Array # locks reclaimed by the in-tick lease-expiry
                              # stage (held past LockTable.lease_ticks: the
                              # holding client abandoned the transaction, or
                              # the lease was set too tight - the false-
                              # expiry arm of benchmarks/fig_chaos.py).
                              # Zero whenever lease_ticks == LEASE_OFF
    conflict_heat: jax.Array  # [B] per-bucket PREPARE-NACK counts (the
                              # ROADMAP item-1 telemetry hook: a raw integral
                              # the CP can EWMA-decay host-side to find hot
                              # buckets worth splitting/rebalancing)

    @staticmethod
    def zeros(num_buckets: int = 1) -> "Metrics":
        """Counters for one chain (the engine vmaps these over the chain
        axis, yielding [C] leaves - and a [C, B] leaf for the per-bucket
        conflict heat)."""
        z = jnp.zeros((), jnp.int32)
        return Metrics(
            *([z] * 22),
            conflict_heat=jnp.zeros((num_buckets,), jnp.int32),
        )

    def total(self) -> "Metrics":
        """Reduce per-chain [C] counters to cluster-wide scalars."""
        return Metrics(*[jnp.sum(v) for v in self])

    def asdict(self) -> dict:
        """Cluster totals (per-chain leaves are summed)."""
        return {k: int(v) for k, v in self.total()._asdict().items()}

    def per_chain(self) -> dict:
        """Per-chain counters as host lists (scalars become length-1;
        multi-dim leaves like the per-bucket conflict heat are summed over
        their trailing axes)."""
        out = {}
        for k, v in self._asdict().items():
            a = jnp.atleast_1d(v)
            if a.ndim > 1:
                a = a.sum(axis=tuple(range(1, a.ndim)))
            out[k] = [int(x) for x in a]
        return out

    def heat_per_bucket(self) -> list:
        """Cluster-wide per-bucket conflict heat ([B] host list): the
        [C, B] leaf summed over chains - every chain accounts NACKs only
        for buckets it owns, so the sum is the per-bucket total."""
        a = jnp.atleast_2d(self.conflict_heat)
        return [int(x) for x in a.sum(axis=0)]

    def heat_ewma(self, prev: "list | None", alpha: float) -> list:
        """One EWMA-decay step over ``heat_per_bucket()`` - the host-side
        decay the ROADMAP item-1 Balancer samples (the raw leaf is an
        undecayed integral, so without this a long-cold bucket looks as
        hot as a currently-contended one).

        Call on *interval* metrics (the difference of two snapshots - see
        ``obs.TelemetryHub``, which maintains this automatically):
        ``new[b] = (1 - alpha) * prev[b] + alpha * interval_heat[b]``,
        with ``prev=None`` starting from zeros.  Under constant
        per-interval heat ``h`` the iteration converges to the fixpoint
        ``h`` (and ``prev == [h, ...]`` maps to exactly ``[h, ...]``) -
        pinned by tests/test_telemetry.py.
        """
        cur = self.heat_per_bucket()
        if prev is None:
            prev = [0.0] * len(cur)
        assert len(prev) == len(cur), (len(prev), len(cur))
        return [(1.0 - alpha) * p + alpha * c for p, c in zip(prev, cur)]


class ReplyLog(NamedTuple):
    """Fixed-capacity record of replies that exited to clients."""

    qid: jax.Array       # [R] int32 (-1 = empty)
    op: jax.Array        # [R] int32
    key: jax.Array       # [R] int32
    seq: jax.Array       # [R] int32
    value0: jax.Array    # [R] int32 (first value word)
    t_inject: jax.Array  # [R] int32
    t_done: jax.Array    # [R] int32
    hops: jax.Array      # [R] int32 link traversals along this query's path
    ticks_in_flight: jax.Array  # [R] int32 ticks between injection and exit
                                #     (t_done - t_inject).  In the tick-
                                #     synchronous engine a live message is
                                #     processed by exactly one node per
                                #     tick, so this doubles as the total
                                #     pipeline-pass count (KV + relay) the
                                #     benchmarks split via the protocol's
                                #     routing - it is NOT a pure KV-pass
                                #     counter (the old field name, `procs`,
                                #     claimed it was).
    lost: jax.Array      # [] int32 replies that exited but could NOT be
                         #     logged because the log was full.  The cursor
                         #     alone cannot distinguish "exactly full" from
                         #     "overflowed" (it saturates at capacity), so
                         #     this counter is the explicit overflow flag
                         #     the percentile fallback keys on
                         #     (``TelemetryHub.log_overflowed``): a nonzero
                         #     ``lost`` means the log's tail is truncated
                         #     and only the device histograms are honest.
    cursor: jax.Array    # [] int32 next free slot

    @staticmethod
    def empty(capacity: int) -> "ReplyLog":
        neg = jnp.full((capacity,), -1, jnp.int32)
        z = jnp.zeros((capacity,), jnp.int32)
        return ReplyLog(neg, z, z, z, z, z, z, z, z,
                        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

    @property
    def chain_stacked(self) -> bool:
        """True when the log carries a leading per-chain axis [C, R]."""
        return self.qid.ndim == 2

    def merged(self) -> "ReplyLog":
        """Flatten a per-chain [C, R] log into one [sum cursor] log.

        Host-side (numpy) - this is the analysis/benchmark view; entries
        are concatenated in chain order, each chain's live prefix only.
        A flat single-chain log is returned truncated to its cursor, so
        callers can treat any engine's log uniformly.
        """
        import numpy as np

        n_rows = len(self._fields) - 2  # [R] record fields; lost/cursor are []
        if not self.chain_stacked:
            n = int(self.cursor)
            flat = ReplyLog(
                *[np.asarray(f)[:n] for f in self[:n_rows]],
                lost=np.int32(self.lost),
                cursor=np.int32(n),
            )
            return flat
        cur = np.asarray(self.cursor)
        C = cur.shape[0]

        def cat(field):
            field = np.asarray(field)
            return np.concatenate(
                [field[c, : cur[c]] for c in range(C)], axis=0
            )

        return ReplyLog(
            *[cat(f) for f in self[:n_rows]],
            lost=np.int32(np.asarray(self.lost).sum()),
            cursor=np.int32(cur.sum()),
        )

    def append(self, exits, t_done, dense: bool = False) -> "ReplyLog":
        """Record exiting replies (masked Msg-like fields) into the log.

        Default path scatters ONE int32 pointer per landing slot and then
        gathers every field through it (an [M] batch is mostly NOPs; nine
        per-field scatters of the whole batch were a top tick cost -
        scatters serialize on most backends, gathers vectorize).
        ``dense=True`` keeps the original scatter-per-field write (the
        pre-segmented engine, benchmarked as the ``fabric="dense"``
        baseline).  Both produce bit-identical logs.
        """
        live = exits.live()
        rank = jnp.cumsum(live.astype(jnp.int32)) - 1
        slot = self.cursor + rank
        cap = self.qid.shape[0]
        ok = live & (slot < cap)
        tgt = jnp.where(ok, slot, cap)  # overflow scatters OOB -> dropped
        new_cursor = jnp.minimum(self.cursor + live.sum(), cap)
        # exits that exist but found no free slot: the explicit overflow
        # counter (see the ``lost`` field docstring)
        new_lost = self.lost + (live.sum() - ok.sum()).astype(jnp.int32)

        if dense:
            def put(buf, val):
                return buf.at[tgt].set(val, mode="drop")

            return ReplyLog(
                qid=put(self.qid, exits.qid),
                op=put(self.op, exits.op),
                key=put(self.key, exits.key),
                seq=put(self.seq, exits.seq),
                value0=put(self.value0, exits.value[:, 0]),
                t_inject=put(self.t_inject, exits.t_inject),
                t_done=put(self.t_done, jnp.full_like(exits.qid, t_done)),
                hops=put(self.hops, exits.extra),
                ticks_in_flight=put(
                    self.ticks_in_flight,
                    jnp.full_like(exits.qid, t_done) - exits.t_inject,
                ),
                lost=new_lost,
                cursor=new_cursor,
            )

        M = live.shape[0]
        ptr = jnp.full((cap,), M, jnp.int32).at[tgt].set(
            jnp.arange(M, dtype=jnp.int32), mode="drop"
        )
        fresh = ptr < M
        pc = jnp.clip(ptr, 0, M - 1)

        def sel(buf, val):
            return jnp.where(fresh, val[pc], buf)

        t_done = jnp.asarray(t_done, jnp.int32)
        return ReplyLog(
            qid=sel(self.qid, exits.qid),
            op=sel(self.op, exits.op),
            key=sel(self.key, exits.key),
            seq=sel(self.seq, exits.seq),
            value0=sel(self.value0, exits.value[:, 0]),
            t_inject=sel(self.t_inject, exits.t_inject),
            t_done=jnp.where(fresh, t_done, self.t_done),
            hops=sel(self.hops, exits.extra),
            ticks_in_flight=jnp.where(
                fresh, t_done - exits.t_inject[pc], self.ticks_in_flight
            ),
            lost=new_lost,
            cursor=new_cursor,
        )

    def total_landed(self) -> int:
        """Host-side count of logged replies so far - transfers ONLY the
        cursor leaf ([C] ints, or a scalar for a flat log), never the log
        body.  Pollers (``TxnDriver._await``) watch this until an expected
        wave size lands, then pay the [C, R] body transfer exactly once."""
        import numpy as np

        return int(np.asarray(self.cursor).sum())

"""Cross-chain multi-key transactions - vectorized in-network 2PC.

The paper's headline use case is a *coordination service*, and coordination
means atomic multi-key operations (NetChain exists precisely to serve
locks/barriers that span keys).  This module adds that capability on top of
the multi-chain partition map: a two-phase commit whose participant logic
runs *in the data plane* (the head's match-action pass), with only the
coordinator role (the ``TxnPlanner``) on the host - mirroring the paper's
CP/DP split: per-query work never touches the control plane.

Protocol
--------
Phase 1 (``OP_PREPARE``, one per key, addressed to the owning chain's head;
the ``seq`` field carries the txn id):

* lock free at the head  -> lock it (txn id + client stamped into the
  ``LockTable``), reply ``OP_PREPARE_ACK`` carrying the head-latest value
  (the snapshot read) and the key's txn-version counter in ``seq``;
* lock held / chain frozen / misdirected -> reply ``OP_PREPARE_NACK``
  (``seq == -1``), counted in ``Metrics.lock_conflicts``.

Phase 2, decided by the coordinator once every participant answered:

* all ACKed -> ``OP_COMMIT`` per written key: the head validates the lock,
  releases it, bumps the version counter and admits the write into the
  chain (it propagates exactly like a plain write; the tail acknowledges
  the client with ``OP_TXN_REPLY`` carrying the stamped write seq).
  Read-locked keys are released with ``OP_ABORT`` (release-without-apply).
* any NACK -> ``OP_ABORT`` for every key that did ACK; the head releases
  the lock and acknowledges with ``OP_TXN_REPLY`` (``seq == -1``).

Because locks are acquired before any is released (strict two-phase
locking: the planner's prepare round is the growing phase, the commit /
abort round the shrinking phase), committed transactions are serializable
- the property test in ``tests/test_txn.py`` checks exactly that against
the host-side reference executor.

Two coordinators, one protocol
------------------------------
The participant half above always runs in the data plane.  The
*coordinator* half has two implementations:

* ``TxnPlanner`` + ``TxnDriver`` - the host-driven oracle: the phase
  state machine lives in Python, one host->device->host round trip per
  phase.  Simple, observable, and the correctness reference.
* the **wave table** (``WaveState`` + ``wave_coordinator_step``) - the
  in-network coordinator: each chain carries ``W`` coordinator slots as
  traced ``SimState`` leaves, and a per-tick stage *inside the jitted
  tick* collects PREPARE_ACK/NACKs, decides, and emits COMMIT/ABORT
  sub-ops into the packed outbox lanes of the same device program.
  Hundreds of independent transactions overlap per tick; the host keeps
  only batched admission (``TxnWaveDriver`` fills FREE slots between
  ``drain`` scans - zero recompiles, donated buffers respected).
  Sub-ops and their replies cross chains through the cluster router
  (``chain.cluster_route``), stamped with ``src/client >= WAVE_BASE`` so
  heads treat them exactly like client transaction traffic and the tick
  diverts their replies back to the owning coordinator slot.  The two
  paths are property-tested against the same serializability oracle.

Single-chain fast path
----------------------
When every key of a transaction lives on one chain the planner skips 2PC
entirely and injects plain ``OP_WRITE``/``OP_READ`` queries in a single
batch: the engine's tick-level batch serialization commits them atomically,
so a local transaction costs **zero extra round trips and zero extra
packets** over plain writes - the paper's traffic-reduction argument
applied to coordination that happens to be partition-local.

Scope and caveats
-----------------
* Locks order only *transactional* traffic: plain writes bypass the lock
  table (they carry no txn id).  Workloads that need isolation against
  non-transactional writers must route those writes as 1-key transactions.
* The lock table is a per-chain leaf on both engines: a ``SimState`` leaf
  in ``ChainSim``, a traced step argument in ``ChainDist.make_step``
  (replicated along the position axis; every device re-derives the same
  head lock transition from an all-gathered transaction batch).  The wave
  table itself is simulator-only for now.
* An admitted commit write still rides the version window: size
  ``num_versions`` above the per-key in-flight write depth (lock
  serialization bounds transactional depth at 1 per key; plain writes
  sharing the key add theirs), or a window overflow can drop a committed
  sub-write mid-chain after its lock released - the one path that breaks
  atomicity, and the reason the driver asserts its capacity contract.
* Recovery interop: a frozen chain NACKs PREPAREs (no new locks), while
  COMMIT/ABORT of already-held locks proceed - they only complete admitted
  transactions.  The CP waits for ``locks_all_free`` before copying (see
  the live-membership contract in ``core/chain.py``).

Machine-checked by repro-lint (see ``repro.analysis``): ``LockTable``
and ``WaveState`` lanes are strong int32 - RL003 rejects weak python
literals entering them (every coordinator Msg construction is pinned by
``.mask(...)``); RL001 verifies the drivers' ``state = sim.tick(state,
...)`` rebinding against the donated tick; RL004 keeps host control
flow out of the jitted coordinator stage; and the cluster router the
sub-ops ride is RL005 scatter-free.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import store as store_lib
from repro.core.types import (
    CLIENT_BASE,
    LEASE_OFF,
    NOWHERE,
    OP_ABORT,
    OP_COMMIT,
    OP_NOP,
    OP_PREPARE,
    OP_PREPARE_ACK,
    OP_PREPARE_NACK,
    OP_READ,
    OP_READ_REPLY,
    OP_STALE_NACK,
    OP_TXN_REPLY,
    OP_WRITE,
    OP_WRITE_NACK,
    OP_WRITE_REPLY,
    TO_CLIENT,
    WAVE_BASE,
    ChainConfig,
    ClusterConfig,
    Msg,
    Roles,
    as_cluster,
)


# ---------------------------------------------------------------------------
# Lock / intent registers (a new per-chain SimState leaf)
# ---------------------------------------------------------------------------
class LockTable(NamedTuple):
    """Per-chain lock/intent registers, keyed by local register index.

    The data-plane analogue of a lock service's lock words: one row per
    object register, living next to the object store and edited only by the
    head's transaction stage (``head_txn_stage``).
    """

    holder: jax.Array       # [K] int32 txn id holding the key's lock (-1 free)
    client: jax.Array       # [K] int32 client that owns the intent (-1 free)
    version: jax.Array      # [K] int32 committed-txn counter - the snapshot
                            #     coordinate PREPARE_ACK hands to multi-key
                            #     reads
    lease: jax.Array        # [K] int32 acquisition-tick stamp (-1 free) -
                            #     the lease clock lease_expiry_stage reclaims
                            #     against
    lease_ticks: jax.Array  # [] int32 lease length; LEASE_OFF disables
                            #     expiry (bit-identical to the pre-lease
                            #     engine)

    @staticmethod
    def empty(num_keys: int, lease_ticks: int = LEASE_OFF) -> "LockTable":
        neg = jnp.full((num_keys,), -1, jnp.int32)
        return LockTable(
            holder=neg, client=neg,
            version=jnp.zeros((num_keys,), jnp.int32),
            lease=neg,
            lease_ticks=jnp.asarray(lease_ticks, jnp.int32),
        )


def init_locks(cfg: ChainConfig, lease_ticks: int = LEASE_OFF) -> LockTable:
    return LockTable.empty(cfg.num_keys, lease_ticks=lease_ticks)


def locks_all_free(locks: LockTable) -> bool:
    """Host-side check the CP uses before a recovery copy: no in-flight
    transaction holds a lock anywhere (works on [K] and [C, K] tables)."""
    return bool((np.asarray(locks.holder) == -1).all())


def held_locks(locks: LockTable) -> int:
    """Host-side count of currently held locks (works on [K] and [C, K]
    tables) - the chaos suite's leaked-lock probe at drain."""
    return int((np.asarray(locks.holder) != -1).sum())


def set_lease(locks: LockTable, lease_ticks) -> LockTable:
    """Swap the lease length on a live lock table - a traced-leaf edit, so
    the donated tick never recompiles.  Works on the engine's vmapped
    [C]-leaf table (broadcasts a scalar over C) and on a single chain's."""
    new = jnp.broadcast_to(
        jnp.asarray(lease_ticks, jnp.int32), locks.lease_ticks.shape
    )
    return locks._replace(lease_ticks=new)


def lease_expiry_stage(locks: LockTable, t):
    """Reclaim locks held past their lease - runs inside the jitted tick,
    immediately *before* ``head_txn_stage`` (see the lock-lease rules in
    ``core/chain.py``).

    A key is expired when it is held and ``t - lease >= lease_ticks``.
    Reclamation clears holder/client/lease and *bumps the version counter*,
    so a straggler COMMIT from the expired transaction fails the
    ``holder == txn_id`` release validation in the same tick's lock stage
    (expiry runs first) and is NACKed with ``OP_TXN_REPLY`` ``seq == -1`` -
    never applied.  At ``lease_ticks == LEASE_OFF`` the predicate is never
    true and the stage is the identity (bit-identical to the pre-lease
    engine).

    Returns ``(locks', n_expired int32)`` - the count feeds
    ``Metrics.lease_expiries``.
    """
    held = locks.holder != -1
    age = t - locks.lease
    expired = held & (age >= locks.lease_ticks)
    neg = jnp.asarray(-1, jnp.int32)
    return LockTable(
        holder=jnp.where(expired, neg, locks.holder),
        client=jnp.where(expired, neg, locks.client),
        version=locks.version + expired.astype(jnp.int32),
        lease=jnp.where(expired, neg, locks.lease),
        lease_ticks=locks.lease_ticks,
    ), expired.sum().astype(jnp.int32)


# ---------------------------------------------------------------------------
# The head's transaction stage (runs inside _chain_tick, before node_step)
# ---------------------------------------------------------------------------
def head_txn_stage(locks: LockTable, roles: Roles, stores, inbox: Msg,
                   t, dense_rank: bool = False):
    """Process this tick's client transaction ops at the chain's live head.

    ``t`` is the engine tick: every granted lock is stamped with it in
    ``locks.lease`` so ``lease_expiry_stage`` can reclaim abandoned locks
    (``ChainDist.make_step``, which has no clock, passes 0 - inert while
    ``lease_ticks == LEASE_OFF``).

    ``dense_rank`` selects the O(B^2) same-key ranking of the pre-segmented
    engine (the ``fabric="dense"`` benchmark baseline; B here is the whole
    chain's n * capacity batch, where the bitmatrix dominated the tick).

    ``inbox`` is the chain's merged [n, cap] inbox (dead-masked, entry-
    stamped).  Client-originated PREPARE/ABORT ops are consumed here;
    validated COMMITs are passed through to the node step as write-like ops
    (``seq`` rewritten to -1 so the head stamps a fresh write seq).  Batch
    serialization order is *releases then acquires*: a lock freed by a
    COMMIT/ABORT in this batch is grantable to a PREPARE in the same batch.

    Returns ``(locks', inbox', txn_replies [n, cap], (commits, aborts,
    conflicts))``.  ``txn_replies`` carry ``dst == TO_CLIENT`` and join the
    node outboxes on the routing fabric, so the exits are packet-accounted
    exactly like any other reply.
    """
    n, cap = inbox.op.shape
    K = locks.holder.shape[0]
    W = stores.values.shape[-1]
    t_now = jnp.asarray(t, jnp.int32)
    flat: Msg = jax.tree.map(
        lambda x: x.reshape((n * cap,) + x.shape[2:]), inbox
    )
    node_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32), cap)
    head = roles.head_pos[0]
    frozen = roles.frozen[0]

    from_client = flat.src >= CLIENT_BASE
    live = flat.op != OP_NOP
    is_prep = live & from_client & (flat.op == OP_PREPARE)
    is_com = live & from_client & (flat.op == OP_COMMIT)
    is_abt = live & from_client & (flat.op == OP_ABORT)
    is_txn = is_prep | is_com | is_abt
    at_head = node_of == head
    txn_id = flat.seq
    key_ok = (flat.key >= 0) & (flat.key < K)
    k = jnp.clip(flat.key, 0, K - 1)

    # ---- release round: COMMIT/ABORT validated against current holders.
    # At most one release per key per batch can be valid (a lock has one
    # holder and txn ids are unique), so the scatters are race-free.
    valid_rel = (
        (is_com | is_abt) & at_head & key_ok & (txn_id >= 0)
        & (locks.holder[k] == txn_id)
    )
    com_ok = is_com & valid_rel
    abt_ok = is_abt & valid_rel
    rel_key = jnp.where(valid_rel, k, K)
    holder = locks.holder.at[rel_key].set(-1, mode="drop")
    client = locks.client.at[rel_key].set(-1, mode="drop")
    lease = locks.lease.at[rel_key].set(-1, mode="drop")
    com_key = jnp.where(com_ok, k, K)
    version = locks.version.at[com_key].add(1, mode="drop")

    # ---- acquire round: PREPAREs against the post-release table; among
    # same-key PREPAREs in one batch the first in stable order wins.  A
    # frozen chain grants nothing (recovery copy window - new transactions
    # must not take locks the CP would have to wait out).
    want = is_prep & at_head & key_ok & (txn_id >= 0) & ~frozen
    rank = store_lib.batch_rank(flat.key, want, dense=dense_rank)
    grant = want & (holder[k] == -1) & (rank == 0)
    g_key = jnp.where(grant, k, K)
    holder = holder.at[g_key].set(txn_id, mode="drop")
    client = client.at[g_key].set(flat.client, mode="drop")
    lease = lease.at[g_key].set(t_now, mode="drop")
    nack = is_prep & ~grant

    # ---- snapshot read for PREPARE_ACK: the head's latest version,
    # overlaid with any commit applied earlier in this batch's serial order
    # (its write enters the store in this tick's node step, after us).
    head_store = jax.tree.map(lambda x: x[head], stores)
    v_latest, _ = store_lib.read_latest(head_store, k)
    new_val = jnp.zeros((K, W), jnp.int32).at[com_key].set(
        flat.value, mode="drop"
    )
    has_new = jnp.zeros((K,), bool).at[com_key].set(True, mode="drop")
    snap_val = jnp.where(has_new[k][:, None], new_val[k], v_latest)

    # ---- replies: ACK/NACK for prepares, TXN_REPLY(-1) for aborts and
    # invalid releases.  Valid commits reply from the tail instead.
    rel_bad = (is_com | is_abt) & ~valid_rel
    abt_reply = abt_ok | rel_bad
    reply_mask = grant | nack | abt_reply
    reply_op = jnp.where(
        grant, OP_PREPARE_ACK, jnp.where(nack, OP_PREPARE_NACK, OP_TXN_REPLY)
    )
    replies = Msg(
        op=jnp.where(reply_mask, reply_op, OP_NOP),
        key=flat.key,
        value=jnp.where(grant[:, None], snap_val, 0),
        seq=jnp.where(grant, version[k], -1),
        src=node_of,
        dst=jnp.where(reply_mask, TO_CLIENT, NOWHERE),
        client=flat.client,
        entry=flat.entry,
        qid=flat.qid,
        t_inject=flat.t_inject,
        extra=flat.extra,
        ver=flat.ver,
    ).mask(reply_mask)

    # ---- inbox edit: keep non-txn traffic plus validated commits (their
    # seq reset to -1 so the node step stamps a fresh write sequence).
    keep = ~is_txn | com_ok
    passed = flat._replace(
        seq=jnp.where(com_ok, jnp.asarray(-1, jnp.int32), flat.seq)
    ).mask(keep)

    lift = lambda m: jax.tree.map(
        lambda x: x.reshape((n, cap) + x.shape[1:]), m
    )
    counts = (
        com_ok.sum().astype(jnp.int32),
        abt_ok.sum().astype(jnp.int32),
        nack.sum().astype(jnp.int32),
    )
    return (
        LockTable(holder=holder, client=client, version=version,
                  lease=lease, lease_ticks=locks.lease_ticks),
        lift(passed),
        lift(replies),
        counts,
    )


# ---------------------------------------------------------------------------
# The in-network 2PC coordinator: a per-chain wave table of W transaction
# slots, stepped inside the jitted tick (the device-resident twin of the
# host-side TxnPlanner/TxnDriver state machine below)
# ---------------------------------------------------------------------------
# Slot phases.  FREE slots are the host's admission surface: TxnWaveDriver
# writes a whole slot (participants + ADMITTED) between ticks; everything
# after that happens on-device until the slot frees itself.
WAVE_FREE = 0       # unoccupied - admissible
WAVE_ADMITTED = 1   # host filled the slot; PREPAREs go out next tick
WAVE_PREP = 2       # phase 1 in flight - awaiting every participant's reply
WAVE_FIN = 3        # phase 2 in flight - awaiting every release's ack

# Completion-log outcome codes (``log_committed`` column / ``committing``
# while a slot is in FIN): 0 = aborted, 1 = committed, 2 = lease-expired
# force-abort (the slot's phase 1 outlived ``lease_ticks``; see
# wave_coordinator_step).  ``TxnWaveDriver`` decodes 2 as ``mode ==
# "wave_expired"`` so overload abandonment is observable, not a wedge.
WAVE_EXPIRED = 2


class WaveState(NamedTuple):
    """One chain's in-flight-transaction wave table + completion log.

    All leaves are per-chain (the engine vmaps them over C).  ``[W]``
    leaves describe coordinator slots, ``[W, KT]`` their participants
    (KT = max keys per transaction; ``p_gkey == -1`` marks an unused
    participant column).  The completion log is an append-only record the
    host decodes *after* the run - results never ride a per-phase host
    round trip.  ``coord_in`` buffers the control replies the cluster
    router delivered to this chain's coordinator at the end of the
    previous tick (consumed and rebuilt every tick).
    """

    # -- slot scalars [W] --------------------------------------------------
    phase: jax.Array       # WAVE_FREE/ADMITTED/PREP/FIN
    txn_id: jax.Array      # transaction id (rides PREPARE/COMMIT seq)
    client: jax.Array      # external client id for the final TXN_REPLY
    qid: jax.Array         # client-facing query id for the final TXN_REPLY
    epoch: jax.Array       # partition epoch stamped on every sub-op (ver)
    t_admit: jax.Array     # tick of admission (latency accounting)
    committing: jax.Array  # -1 undecided / 0 aborting / 1 committing
    # -- participants [W, KT] ---------------------------------------------
    p_gkey: jax.Array      # global key (-1 = column unused)
    p_owner: jax.Array     # owning chain at admission time
    p_lkey: jax.Array      # local register slot on the owner
    p_wval: jax.Array      # value word 0 to commit (writes)
    p_write: jax.Array     # 1 = write intent, 0 = snapshot read
    p_replied: jax.Array   # phase-1 reply (ACK or NACK) received
    p_acked: jax.Array     # phase-1 reply was PREPARE_ACK
    p_done: jax.Array      # phase-2 release acknowledged
    p_snap: jax.Array      # snapshot value from PREPARE_ACK
    p_wseq: jax.Array      # stamped write seq from the tail's TXN_REPLY
    # -- completion log [Lg] / [Lg, KT] -----------------------------------
    log_txn: jax.Array
    log_committed: jax.Array
    log_t_admit: jax.Array
    log_t_done: jax.Array
    log_gkey: jax.Array
    log_write: jax.Array
    log_wseq: jax.Array
    log_snap: jax.Array
    log_cursor: jax.Array  # [] next free log row (saturates at capacity)
    # -- inter-chain reply buffer -----------------------------------------
    coord_in: Msg          # [Xr] control replies routed back to this chain

    @staticmethod
    def empty(wave_depth: int, wave_keys: int, log_capacity: int,
              coord_capacity: int, value_words: int) -> "WaveState":
        W, KT, Lg = wave_depth, wave_keys, log_capacity
        z = lambda *s: jnp.zeros(s, jnp.int32)
        neg = lambda *s: jnp.full(s, -1, jnp.int32)
        return WaveState(
            phase=z(W), txn_id=neg(W), client=neg(W), qid=neg(W),
            epoch=z(W), t_admit=z(W), committing=neg(W),
            p_gkey=neg(W, KT), p_owner=neg(W, KT), p_lkey=z(W, KT),
            p_wval=z(W, KT), p_write=z(W, KT), p_replied=z(W, KT),
            p_acked=z(W, KT), p_done=z(W, KT), p_snap=z(W, KT),
            p_wseq=neg(W, KT),
            log_txn=neg(Lg), log_committed=z(Lg), log_t_admit=z(Lg),
            log_t_done=z(Lg), log_gkey=neg(Lg, KT), log_write=z(Lg, KT),
            log_wseq=neg(Lg, KT), log_snap=z(Lg, KT),
            log_cursor=z(),
            coord_in=Msg.empty(coord_capacity, value_words),
        )


def wave_coordinator_step(wave: WaveState, chain_idx, t,
                          lease_ticks=LEASE_OFF):
    """One tick of one chain's device-resident 2PC coordinator.

    Runs inside the jitted tick, *before* the chain stage, vmapped over
    the chain axis.  Consumes ``wave.coord_in`` (last tick's control
    replies), advances every slot's phase, and returns

    ``(wave', sub_out [W*KT] Msg, sub_target [W*KT], final_out [W] Msg,
    (commits, aborts, occupancy))``

    where ``sub_out`` are this tick's PREPARE/COMMIT/ABORT sub-ops for
    the cluster router (``sub_target`` the owning chain per sub-op, -1
    when unused) and ``final_out`` the client-facing OP_TXN_REPLY of
    slots that completed this tick (they join the coordinator chain's
    outbox and exit through the normal fabric/reply-log path).

    Slot addressing rides the sub-op itself: ``src == client ==
    WAVE_BASE + chain * W + slot`` and ``qid == (chain * W + slot) * KT
    + participant`` - every reply path (lock stage, tail, stale-route
    admission) preserves client/qid, so one integer division recovers the
    (chain, slot, participant) coordinate.  A slot recycles only after
    every one of its sub-ops has been answered, so a qid can never alias
    a previous occupant's in-flight reply.

    Abort rule (mirrors ``TxnPlanner.phase2``): the coordinator waits for
    ALL phase-1 replies before deciding, then an aborting transaction
    releases EVERY key - including ones whose ACK was a NACK: the head
    refuses a release it does not hold (rel_bad), so the extra ABORT is
    free, and deciding early on the first NACK could otherwise race our
    own still-in-flight PREPARE and leak its lock forever.

    Lease interop: a PREP slot whose sub-ops have outlived ``lease_ticks``
    (``t - t_admit >= lease_ticks`` - locks were granted *after* admission,
    so every lock the slot could hold has expired by then) is **force-
    aborted**: its missing replies are synthesized, it enters FIN with
    ``committing == WAVE_EXPIRED`` and emits ABORTs for every participant.
    The heads no longer hold its locks (expiry reclaimed them), so the
    ABORTs come back as rel_bad TXN_REPLYs and the slot retires through
    the normal all-done path - qids never alias, and the straggler's locks
    can never be re-validated because expiry bumped the version counters.
    A slot already in FIN is never forced: its COMMIT/ABORTs were emitted
    at age < lease_ticks and land before any of its locks can expire.
    """
    W, KT = wave.p_gkey.shape
    VW = wave.coord_in.value.shape[-1]
    i32 = jnp.int32
    wave_id0 = chain_idx * W  # this chain's first wave-slot id

    # ---- 1. consume control replies (scatter by slot/participant) --------
    m = wave.coord_in
    live = m.live()
    wid = jnp.clip(m.qid, 0, None) // KT
    slot = wid - wave_id0
    j = jnp.clip(m.qid, 0, None) % KT
    in_range = live & (slot >= 0) & (slot < W)
    sl = jnp.clip(slot, 0, W - 1)
    ph = wave.phase[sl]
    # phase-1 replies: grant, deny, or a stale-route redirect of the
    # PREPARE itself (a NACK by another name - the txn aborts and the
    # admitting host replans under the fresh map)
    p1 = in_range & (ph == WAVE_PREP) & (
        (m.op == OP_PREPARE_ACK) | (m.op == OP_PREPARE_NACK)
        | (m.op == OP_STALE_NACK)
    )
    ack = p1 & (m.op == OP_PREPARE_ACK)
    # phase-2 acks: the tail's TXN_REPLY (committed write: seq >= 0),
    # the head's abort/rel_bad TXN_REPLY (seq == -1), or - defensively -
    # a stale/write NACK of the release (cannot occur while the lock is
    # held, because migration waits out held locks; treated as done so a
    # protocol bug surfaces as an abort, not a wedged slot)
    p2 = in_range & (ph == WAVE_FIN) & (
        (m.op == OP_TXN_REPLY) | (m.op == OP_STALE_NACK)
        | (m.op == OP_WRITE_NACK)
    )
    at1 = (jnp.where(p1, sl, W), j)
    at_ack = (jnp.where(ack, sl, W), j)
    at2 = (jnp.where(p2, sl, W), j)
    p_replied = wave.p_replied.at[at1].set(1, mode="drop")
    p_acked = wave.p_acked.at[at_ack].set(1, mode="drop")
    p_snap = wave.p_snap.at[at_ack].set(m.value[:, 0], mode="drop")
    p_done = wave.p_done.at[at2].set(1, mode="drop")
    p_wseq = wave.p_wseq.at[at2].set(m.seq, mode="drop")

    # ---- 2. slot transitions ---------------------------------------------
    used = wave.p_gkey >= 0                              # [W, KT]
    occupancy = (wave.phase != WAVE_FREE).sum().astype(i32)
    admitted = wave.phase == WAVE_ADMITTED
    # lease force-abort: a PREP slot past the lease can never hear its
    # missing replies (the heads reclaimed its locks) - synthesize them so
    # the slot decides NOW, as an abort, and retires through phase 2
    forced = (wave.phase == WAVE_PREP) & (
        (jnp.asarray(t, i32) - wave.t_admit)
        >= jnp.asarray(lease_ticks, i32)
    )
    p_replied = jnp.where(
        forced[:, None], jnp.maximum(p_replied, used.astype(i32)), p_replied
    )
    prep_all = (wave.phase == WAVE_PREP) & jnp.all(
        (p_replied > 0) | ~used, axis=1
    )
    all_ack = jnp.all((p_acked > 0) | ~used, axis=1)
    enter_fin = prep_all
    decide_commit = enter_fin & all_ack & ~forced
    committing = jnp.where(
        enter_fin,
        jnp.where(forced, jnp.asarray(WAVE_EXPIRED, i32),
                  decide_commit.astype(i32)),
        wave.committing,
    )
    fin_all = (wave.phase == WAVE_FIN) & jnp.all((p_done > 0) | ~used, axis=1)
    committed = wave.committing == 1                     # valid on FIN slots
    phase = jnp.where(
        admitted, WAVE_PREP,
        jnp.where(enter_fin, WAVE_FIN,
                  jnp.where(fin_all, WAVE_FREE, wave.phase)),
    )

    # ---- 3. emit sub-ops (one [W, KT] buffer: a slot is either entering
    # phase 1 or phase 2 this tick, never both) ----------------------------
    emit1 = admitted[:, None] & used
    emit2 = enter_fin[:, None] & used
    do_commit = decide_commit[:, None] & (wave.p_write > 0)
    op = jnp.where(
        emit1, OP_PREPARE,
        jnp.where(emit2, jnp.where(do_commit, OP_COMMIT, OP_ABORT), OP_NOP),
    )
    emit = emit1 | emit2
    slot_col = jnp.arange(W, dtype=i32)[:, None]
    my_id = WAVE_BASE + wave_id0 + slot_col              # [W, 1]
    sub_qid = (wave_id0 + slot_col) * KT + jnp.arange(KT, dtype=i32)[None, :]
    value = jnp.zeros((W, KT, VW), i32).at[:, :, 0].set(
        jnp.where(do_commit, wave.p_wval, 0)
    )
    flat2 = lambda x: x.reshape((W * KT,) + x.shape[2:])
    sub_out = Msg(
        op=flat2(jnp.where(emit, op, OP_NOP)),
        key=flat2(wave.p_lkey),
        value=flat2(value),
        seq=flat2(jnp.broadcast_to(wave.txn_id[:, None], (W, KT))),
        src=flat2(jnp.broadcast_to(my_id, (W, KT))),
        dst=jnp.full((W * KT,), NOWHERE, i32),
        client=flat2(jnp.broadcast_to(my_id, (W, KT))),
        entry=jnp.zeros((W * KT,), i32),
        qid=flat2(sub_qid),
        t_inject=jnp.full((W * KT,), jnp.asarray(t, i32)),
        extra=jnp.zeros((W * KT,), i32),
        ver=flat2(jnp.broadcast_to(wave.epoch[:, None], (W, KT))),
    ).mask(flat2(emit))
    sub_target = flat2(jnp.where(emit, wave.p_owner, -1))

    # ---- 4. completed slots: final client reply + completion log ---------
    final_out = Msg(
        op=jnp.where(fin_all, OP_TXN_REPLY, OP_NOP),
        key=wave.p_gkey[:, 0],
        value=jnp.zeros((W, VW), i32),
        seq=jnp.where(committed, 0, -1),
        src=jnp.zeros((W,), i32),  # the tick stamps the head position
        dst=jnp.where(fin_all, TO_CLIENT, NOWHERE),
        client=wave.client,
        entry=jnp.zeros((W,), i32),
        qid=wave.qid,
        t_inject=wave.t_admit,
        extra=jnp.zeros((W,), i32),
        ver=wave.epoch,
    ).mask(fin_all)

    Lg = wave.log_txn.shape[0]
    rank = jnp.cumsum(fin_all.astype(i32)) - 1
    row = wave.log_cursor + rank
    ok = fin_all & (row < Lg)
    tgt = jnp.where(ok, row, Lg)
    put = lambda buf, val: buf.at[tgt].set(val, mode="drop")
    log_cursor = jnp.minimum(wave.log_cursor + fin_all.sum(), Lg)

    n_commit = (fin_all & committed).sum().astype(i32)
    n_abort = (fin_all & ~committed).sum().astype(i32)

    new_wave = wave._replace(
        phase=phase,
        committing=jnp.where(fin_all, -1, committing),
        p_replied=p_replied, p_acked=p_acked, p_done=p_done,
        p_snap=p_snap, p_wseq=p_wseq,
        log_txn=put(wave.log_txn, wave.txn_id),
        # the outcome code verbatim (0 abort / 1 commit / 2 lease-expired)
        log_committed=put(wave.log_committed, wave.committing),
        log_t_admit=put(wave.log_t_admit, wave.t_admit),
        log_t_done=put(wave.log_t_done,
                       jnp.broadcast_to(jnp.asarray(t, i32), (W,))),
        log_gkey=put(wave.log_gkey, wave.p_gkey),
        log_write=put(wave.log_write, wave.p_write),
        log_wseq=put(wave.log_wseq, p_wseq),
        log_snap=put(wave.log_snap, p_snap),
        log_cursor=log_cursor,
        # coord_in is rebuilt by the tick's control-reply router; blank it
        # here so a routing bug cannot re-deliver stale replies
        coord_in=wave.coord_in.mask(jnp.zeros((m.op.shape[0],), bool)),
    )
    return new_wave, sub_out, sub_target, final_out, (
        n_commit, n_abort, occupancy
    )


# ---------------------------------------------------------------------------
# Host-side transaction description + planner (the 2PC coordinator role)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Txn:
    """A multi-key transaction over *global* keys.

    ``writes`` maps global key -> value (word 0 of the payload); ``reads``
    are additionally snapshot-read keys.  Key sets must be disjoint within
    one field and unique (a txn never touches a key twice).
    """

    txn_id: int
    writes: tuple[tuple[int, int], ...] = ()
    reads: tuple[int, ...] = ()
    client: int = 0

    @property
    def keys(self) -> tuple[int, ...]:
        return tuple(k for k, _ in self.writes) + tuple(self.reads)


@dataclasses.dataclass
class TxnResult:
    txn_id: int
    committed: bool
    mode: str                      # "direct" (single-chain) | "2pc" |
                                   # "wave" | "wave_expired" (lease-expired
                                   # force-abort: slot recycled, txn aborted)
    nacks: int = 0                 # prepare NACKs observed (2pc only)
    write_seqs: dict = dataclasses.field(default_factory=dict)  # gkey -> seq
    read_values: dict = dataclasses.field(default_factory=dict)  # gkey -> v0


class TxnPlanner:
    """Splits multi-key transactions into per-chain sub-ops via the
    cluster's partition map and plans the two phases.

    The planner is pure host-side metadata work (stream construction +
    reply decoding); all per-query processing stays in the data plane.
    Single-chain transactions take the fast path: plain reads/writes in one
    batch, no PREPARE round (``is_single_chain``).

    Under a live (rebalanced) partition map, pass the owning
    ``Coordinator``: the planner then splits transactions with the CP's
    *current* map and stamps its epoch into every sub-op, so the data
    plane NACK-redirects sub-ops planned against a map that has since
    moved instead of locking keys on the wrong chain.
    """

    def __init__(self, cfg: ChainConfig | ClusterConfig, qid_base: int = 1 << 24,
                 coordinator=None):
        self.cluster = as_cluster(cfg)
        self._next_qid = qid_base
        self._coordinator = coordinator

    # -- partition-map splitting -------------------------------------------
    def _key_to_chain(self, key: int) -> int:
        if self._coordinator is not None:
            return self._coordinator.key_to_chain(key)
        return int(self.cluster.key_to_chain(key))

    @property
    def _epoch(self) -> int:
        if self._coordinator is not None:
            return self._coordinator.partition_epoch
        return 0

    def chains_of(self, txn: Txn) -> list[int]:
        return sorted({self._key_to_chain(k) for k in txn.keys})

    def is_single_chain(self, txn: Txn) -> bool:
        return len(self.chains_of(txn)) == 1

    def _qids(self, m: int) -> list[int]:
        out = list(range(self._next_qid, self._next_qid + m))
        self._next_qid += m
        return out

    # -- stream construction ------------------------------------------------
    def _stream(self, subs: list[tuple]) -> Msg:
        """subs: (op, global_key, value0, seq, qid, client) -> [1, Q] Msg."""
        Q = max(len(subs), 1)
        W = self.cluster.chain.value_words
        arr = lambda i, fill=0: np.full((Q,), fill, np.int32) if not subs else \
            np.asarray([s[i] for s in subs] + [fill] * (Q - len(subs)), np.int32)
        op = arr(0, OP_NOP)
        value = np.zeros((Q, W), np.int32)
        value[:, 0] = arr(2)
        m = Msg(
            op=jnp.asarray(op),
            key=jnp.asarray(arr(1)),
            value=jnp.asarray(value),
            seq=jnp.asarray(arr(3, -1)),
            src=jnp.asarray(CLIENT_BASE + arr(5)),
            dst=jnp.full((Q,), NOWHERE, jnp.int32),
            client=jnp.asarray(CLIENT_BASE + arr(5)),
            entry=jnp.zeros((Q,), jnp.int32),
            qid=jnp.asarray(arr(4, -1)),
            t_inject=jnp.zeros((Q,), jnp.int32),
            extra=jnp.zeros((Q,), jnp.int32),
            ver=jnp.full((Q,), self._epoch, jnp.int32),
        )
        return jax.tree.map(lambda x: x[None], m)  # [T=1, Q]

    def phase1(self, txns: list[Txn]):
        """Plan phase 1: PREPAREs for cross-chain txns, direct plain ops
        for single-chain ones.  Returns (stream [1, Q] | None, plan)."""
        subs, plan = [], {}
        for t in txns:
            mode = "direct" if self.is_single_chain(t) else "2pc"
            entry = {"txn": t, "mode": mode, "p1": {}, "p2": {}}
            if mode == "direct":
                qids = self._qids(len(t.writes) + len(t.reads))
                it = iter(qids)
                for gk, v in t.writes:
                    q = next(it)
                    subs.append((OP_WRITE, gk, v, -1, q, t.client))
                    entry["p1"][q] = ("w", gk)
                for gk in t.reads:
                    q = next(it)
                    subs.append((OP_READ, gk, 0, -1, q, t.client))
                    entry["p1"][q] = ("r", gk)
            else:
                qids = self._qids(len(t.keys))
                for gk, q in zip(t.keys, qids):
                    subs.append((OP_PREPARE, gk, 0, t.txn_id, q, t.client))
                    entry["p1"][q] = ("p", gk)
            plan[t.txn_id] = entry
        return (self._stream(subs) if subs else None), plan

    def phase2(self, plan: dict, seen: dict):
        """Decide commit/abort per 2PC txn from phase-1 replies and plan the
        second round.  ``seen``: qid -> (op, seq, value0).  A missing or
        NACKed prepare aborts the txn.  An aborting txn releases EVERY key,
        including ones whose ACK it never saw: a reply lost after the grant
        would otherwise leak the lock forever, and the head refuses a
        release it does not hold (rel_bad), so the extra ABORT is free."""
        subs = []
        for entry in plan.values():
            t: Txn = entry["txn"]
            if entry["mode"] != "2pc":
                continue
            acks, nacks = {}, 0
            for q, (_, gk) in entry["p1"].items():
                r = seen.get(q)
                if r is not None and r[0] == OP_PREPARE_ACK:
                    acks[gk] = r
                else:
                    nacks += 1
            entry["nacks"] = nacks
            entry["decision"] = "commit" if nacks == 0 else "abort"
            wkeys = dict(t.writes)
            for gk in t.keys:
                q = self._qids(1)[0]
                if entry["decision"] == "commit" and gk in wkeys:
                    subs.append((OP_COMMIT, gk, wkeys[gk], t.txn_id, q,
                                 t.client))
                    entry["p2"][q] = ("c", gk)
                else:
                    subs.append((OP_ABORT, gk, 0, t.txn_id, q, t.client))
                    entry["p2"][q] = ("a", gk)
        return (self._stream(subs) if subs else None)

    def results(self, plan: dict, seen: dict) -> list[TxnResult]:
        out = []
        for entry in plan.values():
            t: Txn = entry["txn"]
            res = TxnResult(txn_id=t.txn_id, committed=False,
                            mode=entry["mode"], nacks=entry.get("nacks", 0))
            if entry["mode"] == "direct":
                ok = True
                for q, (kind, gk) in entry["p1"].items():
                    r = seen.get(q)
                    if kind == "w":
                        if r is None or r[0] != OP_WRITE_REPLY:
                            ok = False
                        else:
                            res.write_seqs[gk] = r[1]
                    else:
                        if r is None or r[0] != OP_READ_REPLY:
                            ok = False
                        else:
                            res.read_values[gk] = r[2]
                res.committed = ok
            else:
                if entry.get("decision") == "commit":
                    ok = True
                    for q, (kind, gk) in entry["p2"].items():
                        if kind != "c":
                            continue
                        r = seen.get(q)
                        if r is None or r[0] != OP_TXN_REPLY or r[1] < 0:
                            ok = False
                        else:
                            res.write_seqs[gk] = r[1]
                    res.committed = ok
                    if ok:
                        for q, (_, gk) in entry["p1"].items():
                            r = seen.get(q)
                            if r is not None and r[0] == OP_PREPARE_ACK \
                                    and gk in t.reads:
                                res.read_values[gk] = r[2]
            out.append(res)
        return out


# ---------------------------------------------------------------------------
# Host-side driver: runs the phases against a live ChainSim
# ---------------------------------------------------------------------------
class TxnDriver:
    """Ticks a ``ChainSim`` through a wave of transactions: inject phase 1,
    poll the reply log, decide, inject phase 2, poll again.

    Capacity contract: the caller sizes ``inject_capacity`` so one wave's
    sub-ops fit their head lanes (asserted - a dropped PREPARE would wait
    out the timeout, a dropped COMMIT would leak a lock) and the reply log
    holds every reply.
    """

    def __init__(self, sim, planner: TxnPlanner):
        self.sim = sim
        self.planner = planner

    def _reply_map(self, state) -> dict:
        r = state.replies.merged()
        return {
            int(q): (int(op), int(s), int(v))
            for q, op, s, v in zip(r.qid, r.op, r.seq, r.value0)
        }

    def _inject(self, state, stream):
        from repro.core.workload import route_stream

        co = self.planner._coordinator
        routed = route_stream(
            self.planner.cluster, stream, self.sim.c_in,
            pmap=co.partition_map() if co is not None else None,
        )
        assert int(routed.dropped) == 0, (
            f"txn stream overflowed injection lanes ({int(routed.dropped)} "
            "sub-ops dropped) - shrink the wave or grow inject_capacity"
        )
        return self.sim.tick(state, jax.tree.map(lambda x: x[0], routed.lanes))

    def _await(self, state, qids: set, max_ticks: int, landed_base: int):
        """Tick until the wave's replies land, then decode the log.

        Every sub-op yields exactly one logged exit (ACK/NACK/reply), so
        the wave is known to have landed once the reply cursors have grown
        by ``len(qids)`` since ``landed_base`` (counted *before* the wave
        was injected).  Polling therefore syncs only the [C] cursor leaf
        per tick (``ReplyLog.total_landed``) and transfers the [C, R] log
        body exactly once - the old loop device-synced the entire log
        every polled tick.  If the count never arrives (a dropped sub-op:
        a capacity-contract violation), fall back to full-log polling for
        the remaining tick budget, exactly like the old loop.
        """
        empty = self.sim.empty_injection()
        expected = len(qids)
        ticks = 0
        while (ticks < max_ticks
               and state.replies.total_landed() - landed_base < expected):
            state = self.sim.tick(state, empty)
            ticks += 1
        seen = self._reply_map(state)
        # Dropped-sub-op fallback: keep ticking for the budget, but stay on
        # the [C] cursor leaf - the log body is re-merged only on ticks
        # where the cursors actually grew (a late straggler landing), never
        # per polled tick.
        landed = state.replies.total_landed()
        while ticks < max_ticks and not qids <= seen.keys():
            state = self.sim.tick(state, empty)
            ticks += 1
            now = state.replies.total_landed()
            if now != landed:
                landed = now
                seen = self._reply_map(state)
        return state, seen

    def run(self, state, txns: list[Txn], max_ticks: Optional[int] = None):
        """Run one wave of transactions to completion.  Returns
        ``(state, [TxnResult])``."""
        max_ticks = max_ticks or (4 * self.sim.n + 8)
        stream1, plan = self.planner.phase1(txns)
        qids1 = {q for e in plan.values() for q in e["p1"]}
        base = state.replies.total_landed()
        if stream1 is not None:
            state = self._inject(state, stream1)
        state, seen = self._await(state, qids1, max_ticks, base)
        stream2 = self.planner.phase2(plan, seen)
        if stream2 is not None:
            base = state.replies.total_landed()
            state = self._inject(state, stream2)
            qids2 = {q for e in plan.values() for q in e["p2"]}
            state, seen = self._await(state, qids2, max_ticks, base)
        return state, self.planner.results(plan, seen)


# ---------------------------------------------------------------------------
# Batched admission for the in-network coordinator (the ONLY host work on
# the wave path: fill FREE slots, drain, decode the completion log)
# ---------------------------------------------------------------------------
class TxnWaveDriver:
    """Admits transactions into a wave-enabled ``ChainSim``'s device-side
    coordinator and decodes the completion log into ``TxnResult``s.

    Per admission round the host syncs ONE [C, W] int leaf (the slot
    phases), scatter-fills every free slot whose coordinator chain has
    queued work, and hands the engine back to a fixed-length ``drain``
    scan - so host round trips per transaction go to ~0 as W grows (the
    ISSUE-6 headline), and the admission loop never recompiles (static
    drain length, donated buffers rebound).

    Capacity contract (mirrors ``TxnDriver``'s): ``wave_log_capacity``
    must hold every admitted transaction (asserted), per-key in-flight
    write depth must fit ``num_versions`` (a dropped committed sub-write
    would break atomicity), and transactions wider than ``wave_keys``
    are rejected at admission.
    """

    def __init__(self, sim, planner: TxnPlanner):
        assert getattr(sim, "wave_depth", 0) > 0, (
            "TxnWaveDriver needs a wave-enabled ChainSim (wave_depth > 0)"
        )
        self.sim = sim
        self.planner = planner
        self.last_rounds = 0   # admission-loop iterations of the last run
        self.last_ticks = 0    # device ticks the last run consumed

    # -- planning ----------------------------------------------------------
    def _locate(self, gk: int):
        co = self.planner._coordinator
        if co is not None:
            return co.key_to_chain(gk), co.local_key(gk)
        cl = self.planner.cluster
        return int(cl.key_to_chain(gk)), int(cl.key_to_slot(gk))

    def _plan(self, txn: Txn) -> dict:
        KT = self.sim.wave_keys
        assert 0 < len(txn.keys) <= KT, (
            f"txn {txn.txn_id} has {len(txn.keys)} keys; this engine's "
            f"wave_keys is {KT}"
        )
        wkeys = dict(txn.writes)
        parts = []
        for gk in txn.keys:
            chain, lkey = self._locate(gk)
            is_w = gk in wkeys
            parts.append((gk, chain, lkey, wkeys.get(gk, 0), int(is_w)))
        # the coordinator chain is the first key's owner: admission load
        # follows the workload's key distribution
        return {"txn": txn, "coord": parts[0][1], "parts": parts,
                "qid": self.planner._qids(1)[0]}

    # -- admission ---------------------------------------------------------
    def _admit(self, state, queue: list, phases: np.ndarray, t_now: int):
        """Fill FREE slots from the queue (host-side scatter, between
        ticks).  Mutates ``queue``; returns (state, n_admitted)."""
        W, KT = self.sim.wave_depth, self.sim.wave_keys
        free: dict[int, list] = {
            c: list(np.nonzero(phases[c] == WAVE_FREE)[0])
            for c in range(phases.shape[0])
        }
        picked, rest = [], []
        for plan in queue:
            slots = free[plan["coord"]]
            if slots:
                picked.append((plan, int(slots.pop())))
            else:
                rest.append(plan)
        queue[:] = rest
        if not picked:
            return state, 0
        epoch = self.planner._epoch
        ic = np.asarray([p["coord"] for p, _ in picked], np.int32)
        isl = np.asarray([s for _, s in picked], np.int32)
        scal = lambda f: np.asarray([f(p) for p, _ in picked], np.int32)
        part = lambda f, fill: np.asarray(
            [[f(pp) for pp in p["parts"]]
             + [fill] * (KT - len(p["parts"])) for p, _ in picked],
            np.int32,
        )
        w = state.wave
        at = lambda leaf, val: leaf.at[ic, isl].set(jnp.asarray(val))
        state = state._replace(wave=w._replace(
            phase=at(w.phase, np.full(len(picked), WAVE_ADMITTED, np.int32)),
            txn_id=at(w.txn_id, scal(lambda p: p["txn"].txn_id)),
            client=at(w.client, scal(
                lambda p: CLIENT_BASE + p["txn"].client)),
            qid=at(w.qid, scal(lambda p: p["qid"])),
            epoch=at(w.epoch, np.full(len(picked), epoch, np.int32)),
            t_admit=at(w.t_admit, np.full(len(picked), t_now, np.int32)),
            committing=at(w.committing, np.full(len(picked), -1, np.int32)),
            p_gkey=at(w.p_gkey, part(lambda x: x[0], -1)),
            p_owner=at(w.p_owner, part(lambda x: x[1], -1)),
            p_lkey=at(w.p_lkey, part(lambda x: x[2], 0)),
            p_wval=at(w.p_wval, part(lambda x: x[3], 0)),
            p_write=at(w.p_write, part(lambda x: x[4], 0)),
            p_replied=at(w.p_replied, np.zeros((len(picked), KT), np.int32)),
            p_acked=at(w.p_acked, np.zeros((len(picked), KT), np.int32)),
            p_done=at(w.p_done, np.zeros((len(picked), KT), np.int32)),
            p_snap=at(w.p_snap, np.zeros((len(picked), KT), np.int32)),
            p_wseq=at(w.p_wseq, np.full((len(picked), KT), -1, np.int32)),
        ))
        return state, len(picked)

    # -- the run loop ------------------------------------------------------
    def run(self, state, txns: list[Txn], step_ticks: int = 2,
            max_rounds: Optional[int] = None):
        """Admit ``txns``, drain until every slot frees, decode the log.
        Returns ``(state, [TxnResult])`` (log order, one entry per txn).

        ``step_ticks`` is the static drain length between admission
        rounds - one compiled scan reused every round.  The whole run is
        device-paced: results come from the completion log, never from
        per-phase polling.
        """
        sim = self.sim
        base = np.asarray(state.wave.log_cursor).copy()   # [C] rows so far
        queue = [self._plan(t) for t in txns]
        n_total = len(queue)
        assert int(base.sum()) + n_total <= sim.C * sim.wave_log_capacity, (
            "completion log too small for this run - grow wave_log_capacity"
        )
        max_rounds = max_rounds or (
            8 * (n_total // max(sim.C * sim.wave_depth, 1) + 1)
            * (4 * sim.n + 8) // step_ticks
        )
        t0 = int(np.asarray(state.t))   # synced once; ticks tracked host-side
        rounds = 0
        while True:
            phases = np.asarray(state.wave.phase)     # the ONE synced leaf
            if queue:
                state, _ = self._admit(
                    state, queue, phases, t0 + rounds * step_ticks
                )
            elif (phases != WAVE_FREE).sum() == 0:
                break
            state = sim.drain(state, step_ticks)
            rounds += 1
            assert rounds <= max_rounds, (
                f"wave run wedged: {len(queue)} queued, "
                f"{(phases != WAVE_FREE).sum()} slots busy after "
                f"{rounds} rounds - check the capacity contract"
            )
        self.last_rounds = rounds
        self.last_ticks = rounds * step_ticks
        return state, self._decode(state, base, n_total)

    # -- completion-log decode --------------------------------------------
    def _decode(self, state, base: np.ndarray, n_total: int):
        w = jax.device_get(state.wave)
        results = []
        for c in range(w.log_txn.shape[0]):
            for r in range(int(base[c]), int(w.log_cursor[c])):
                outcome = int(w.log_committed[c, r])
                committed = outcome == 1
                res = TxnResult(
                    txn_id=int(w.log_txn[c, r]),
                    committed=committed,
                    mode="wave_expired" if outcome == WAVE_EXPIRED
                    else "wave",
                )
                if committed:
                    for gk, iw, ws, sn in zip(
                        w.log_gkey[c, r], w.log_write[c, r],
                        w.log_wseq[c, r], w.log_snap[c, r],
                    ):
                        if gk < 0:
                            continue
                        if iw:
                            res.write_seqs[int(gk)] = int(ws)
                        else:
                            res.read_values[int(gk)] = int(sn)
                results.append(res)
        assert len(results) == n_total, (
            f"completion log gained {len(results)} rows, expected "
            f"{n_total} (log overflow or wedged slot)"
        )
        return results


# ---------------------------------------------------------------------------
# Host-side reference executor (the serializability oracle)
# ---------------------------------------------------------------------------
def reference_execute(committed: list[Txn]) -> dict:
    """Apply committed transactions serially in list order.  Returns the
    expected {global_key: value} for every touched key (callers default
    untouched keys to the store's initial 0)."""
    kv: dict[int, int] = {}
    for t in committed:
        for k, v in t.writes:
            kv[k] = v
    return kv


def serial_order(results: list[TxnResult]) -> list[int]:
    """Topological serialization order of committed txns from observed
    per-key write seqs; raises if the precedence graph has a cycle (a
    serializability violation the lock protocol must prevent)."""
    committed = [r for r in results if r.committed and r.write_seqs]
    by_key: dict[int, list[tuple[int, int]]] = {}
    for r in committed:
        for k, s in r.write_seqs.items():
            by_key.setdefault(k, []).append((s, r.txn_id))
    edges: dict[int, set[int]] = {r.txn_id: set() for r in committed}
    indeg = {r.txn_id: 0 for r in committed}
    for k, pairs in by_key.items():
        pairs.sort()
        for (_, a), (_, b) in zip(pairs, pairs[1:]):
            if b not in edges[a]:
                edges[a].add(b)
                indeg[b] += 1
    order, ready = [], [t for t, d in indeg.items() if d == 0]
    while ready:
        t = ready.pop()
        order.append(t)
        for u in edges[t]:
            indeg[u] -= 1
            if indeg[u] == 0:
                ready.append(u)
    if len(order) != len(committed):
        raise AssertionError(
            "cyclic write-precedence among committed txns: not serializable"
        )
    return order


def committed_view(cluster: ClusterConfig, state, node: int = -1) -> dict:
    """{global_key: committed value} read from every chain's store (default:
    the physical tail slot).  Call after a drain, when all replicas agree.

    The inverse goes through the state's live ``PartitionMap`` occupancy
    table (``ClusterConfig.global_key`` - the one canonical inverse), so
    rebalanced buckets read from wherever they currently live; free
    regions (no bucket) are skipped."""
    vals = np.asarray(state.stores.values)[:, node, :, 0, 0]  # [C, K]
    C, K = vals.shape
    chains = np.repeat(np.arange(C), K)
    slots = np.tile(np.arange(K), C)
    gks = np.asarray(cluster.global_key(
        jnp.asarray(slots), jnp.asarray(chains), state.pmap))
    return {
        int(g): int(vals[c, s])
        for g, c, s in zip(gks, chains, slots)
        if g >= 0
    }

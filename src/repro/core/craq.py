"""NetCRAQ node control logic - the paper's Algorithm 1, vectorized.

A programmable switch processes one packet per pipeline pass; a TPU core
processes a *batch* of queries per step.  ``node_step`` is the branch-free
batch equivalent of the match-action control logic:

    READ  -> clean (pending==0): reply locally from cell 0  (any node!)
             dirty & tail:       reply the latest dirty version
             dirty & not tail:   forward to the tail
    WRITE -> append dirty version (drop if the window overflows);
             forward toward the tail (next live hop from the role table);
             at the tail: commit clean, multicast ACK, reply to client;
             while the chain's writes are frozen (recovery copy window)
             client writes are NACKed at the entry node instead
    ACK   -> commit: install clean value, compact versions <= acked seq
    COMMIT-> a txn phase-2 write admitted by the head's lock stage
             (core/txn.py): identical to WRITE except it keeps its opcode
             down the chain and the tail acknowledges with OP_TXN_REPLY;
             exempt from the freeze NACK (admission was at PREPARE)

Batch serialization order within one step: READs observe the state at step
start, then ACKs apply, then WRITEs (DESIGN.md §3).  The sequential oracle
used by the hypothesis tests replays exactly this order.

Telemetry hop events: ``node_step`` needs no instrumentation of its own -
every message a node processes arrives through the tick's merged inbox,
and the telemetry plane's sampled packet traces
(``core/telemetry.py::record_trace``) read exactly that pre-admission
arrival batch, so each forward/relay/commit a traced query performs here
shows up as one (node, tick, op) hop event.  Exit events (the reply leg)
are covered by the reply log and the latency histogram instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import store as store_lib
from repro.core.stages import stage
from repro.core.store import Store
from repro.core.types import (
    MULTICAST,
    NOWHERE,
    OP_ACK,
    OP_COMMIT,
    OP_READ,
    OP_READ_REPLY,
    OP_TXN_REPLY,
    OP_WRITE,
    OP_WRITE_NACK,
    OP_WRITE_REPLY,
    TO_CLIENT,
    CLIENT_BASE,
    ChainConfig,
    Msg,
    Roles,
)


def node_step(cfg: ChainConfig, store: Store, roles: Roles, inbox: Msg,
              dense_rank: bool = False):
    """Process one inbox batch on one node. Returns (store', outbox).

    outbox has 3*B slots: [replies | forwards | acks+write-replies].
    ``dense_rank`` selects the O(B^2) same-key write ranking of the
    pre-segmented engine (the ``fabric="dense"`` benchmark baseline).
    """
    del cfg
    B = inbox.batch
    is_read = inbox.op == OP_READ
    is_ack = inbox.op == OP_ACK
    is_commit = inbox.op == OP_COMMIT
    is_tail = roles.is_tail
    is_write, nacked = _writes(roles, inbox)

    # ---------------- READ path (observes pre-step state) ----------------
    with stage("store"):
        clean = store_lib.is_clean(store, inbox.key)
        (v_clean, s_clean), (v_latest, s_latest) = store_lib.read_versions(
            store, inbox.key)

    answer_local = is_read & clean                      # Algorithm 1 l.7-9
    answer_tail = is_read & ~clean & is_tail            # l.10-12
    answers = answer_local | answer_tail
    fwd_read = is_read & ~clean & ~is_tail              # l.13-14

    reply_val = jnp.where(answer_tail[:, None], v_latest, v_clean)
    reply_seq = jnp.where(answer_tail, s_latest, s_clean)
    replies = Msg(
        op=jnp.where(answers, OP_READ_REPLY, 0),
        key=inbox.key,
        value=reply_val,
        seq=reply_seq,
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(answers, TO_CLIENT, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(answers)

    # ---------------- ACK path ----------------
    with stage("store"):
        new_store = store_lib.commit(store, inbox.key, inbox.value, inbox.seq,
                                     is_ack)

    # ---------------- WRITE path ----------------
    # Entry node stamps client writes with per-key monotone sequence numbers.
    needs_seq = is_write & (inbox.seq < 0)
    with stage("store"):
        new_store, stamped = store_lib.assign_seqs(
            new_store, inbox.key, needs_seq, dense_rank=dense_rank)
    wseq = jnp.where(needs_seq, stamped, inbox.seq)

    if_tail_commit = is_write & is_tail
    if_appended = is_write & ~is_tail
    with stage("store"):
        new_store, accepted = store_lib.append_dirty(
            new_store, inbox.key, inbox.value, wseq, if_appended,
            dense_rank=dense_rank,
        )
        # Tail: commit directly (clean_write, Algorithm 1 l.27-28).
        new_store = store_lib.commit(
            new_store, inbox.key, inbox.value, wseq, if_tail_commit
        )

    # Forward accepted writes toward the tail (next hop in the chain).
    fwd_write = accepted
    fwd_mask = fwd_read | fwd_write
    fwd_dst = jnp.where(
        fwd_read,
        roles.tail_pos,                       # dirty reads go straight to tail
        roles.next_pos,                       # writes propagate along the
    )                                         # live chain (skips dead slots)
    forwards = Msg(
        op=jnp.where(fwd_read, OP_READ,
                     jnp.where(is_commit, OP_COMMIT, OP_WRITE)),
        key=inbox.key,
        value=inbox.value,
        seq=wseq,
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(fwd_mask, fwd_dst, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(fwd_mask)

    # Tail: multicast ACK to the rest of the chain + acknowledge the client.
    ack_mask = if_tail_commit
    acks = Msg(
        op=jnp.where(ack_mask, OP_ACK, 0),
        key=inbox.key,
        value=inbox.value,
        seq=wseq,
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(ack_mask, MULTICAST, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(ack_mask)
    # Write replies share a section with freeze NACKs (disjoint masks: a
    # NACKed write never reaches the tail-commit path).  Txn commit writes
    # are acknowledged as OP_TXN_REPLY so the planner can tell them apart.
    wr_mask = ack_mask | nacked
    wreplies = Msg(
        op=jnp.where(nacked, OP_WRITE_NACK,
                     jnp.where(ack_mask,
                               jnp.where(is_commit, OP_TXN_REPLY,
                                         OP_WRITE_REPLY), 0)),
        key=inbox.key,
        value=inbox.value,
        seq=jnp.where(nacked, -1, wseq),
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(wr_mask, TO_CLIENT, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(wr_mask)

    outbox = Msg.concat([replies, forwards, acks, wreplies])
    return new_store, outbox


def _writes(roles: Roles, inbox: Msg):
    """(writes the node step applies, client writes the freeze NACKs)."""
    is_write = inbox.op == OP_WRITE
    # Write freeze (recovery phase 2 copy window): client writes entering
    # the chain are NACKed; in-flight writes (already sequenced) drain
    # normally so the pre-freeze prefix commits before the CP copies.
    nacked = is_write & (inbox.seq < 0) & roles.frozen
    # Txn phase-2 write admitted by the head's lock stage: rides the chain
    # exactly like a plain write but keeps its opcode so the tail can
    # acknowledge with OP_TXN_REPLY.  Never frozen-NACKed - admission
    # happened at PREPARE time (the freeze stops new PREPAREs instead).
    return (is_write & ~nacked) | (inbox.op == OP_COMMIT), nacked


def commit_rows(roles: Roles, inbox: Msg) -> jax.Array:
    """The store rows ``node_step``'s two commits rewrite for ``inbox``: one
    per distinct key its ACKs name with a seq, and one per distinct key the
    tail commits (a tail write always carries a seq)."""
    is_write, _ = _writes(roles, inbox)
    rows = lambda live: store_lib.first_of_key(inbox.key, live).sum(
        dtype=jnp.int32)
    return (rows((inbox.op == OP_ACK) & (inbox.seq >= 0))
            + rows(is_write & roles.is_tail))


def stamp_entry(inbox: Msg, my_pos) -> Msg:
    """Record the chain position where a client query entered the system."""
    from_client = inbox.src >= CLIENT_BASE
    return inbox._replace(
        entry=jnp.where(from_client, jnp.asarray(my_pos, jnp.int32), inbox.entry)
    )

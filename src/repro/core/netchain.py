"""NetChain (Chain Replication) baseline node logic - paper §II.

The comparison target: only the tail answers reads, so a read entering the
chain at distance d from the tail costs 2d+2 packets (query forwarded hop by
hop to the tail, reply forwarded hop by hop back to the entry node, plus the
client legs) - 2n packets for head-directed reads on an n-node chain, exactly
the paper's accounting.  Writes enter at the head, overwrite the single
version and propagate to the tail which acknowledges the client (n+1
packets).

The 16-bit SEQ field critique (paper §II.B.2): NetChain's sequence number
wraps after 65,536 writes.  We reproduce the wrap behaviour behind
``SEQ_BITS`` so the overflow test can demonstrate the failure mode, while
NetCRAQ uses 32-bit seqs.

Telemetry hop events: as with the NetCRAQ logic, the per-hop forwarding
this module emits is observed by the telemetry plane at the *arrival* side
(``core/telemetry.py::record_trace`` samples the tick's pre-admission
inbox batch), so the baseline's longer read paths show up as
proportionally longer sampled traces - no instrumentation lives here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import store as store_lib
from repro.core.stages import stage
from repro.core.store import Store
from repro.core.types import (
    NOWHERE,
    OP_COMMIT,
    OP_READ,
    OP_READ_REPLY,
    OP_TXN_REPLY,
    OP_WRITE,
    OP_WRITE_NACK,
    OP_WRITE_REPLY,
    TO_CLIENT,
    ChainConfig,
    Msg,
    Roles,
)

SEQ_BITS = 16  # NetChain's default SEQ width (the overflow the paper calls out)


def node_step(cfg: ChainConfig, store: Store, roles: Roles, inbox: Msg,
              dense_rank: bool = False):
    """One CR pipeline pass over an inbox batch. Returns (store', outbox).

    outbox has 3*B slots: [tail replies | forwards | reply relays].
    ``dense_rank`` selects the O(B^2) same-key write ranking of the
    pre-segmented engine (the ``fabric="dense"`` benchmark baseline).
    """
    del cfg
    B = inbox.batch
    is_read = inbox.op == OP_READ
    is_write = inbox.op == OP_WRITE
    is_reply = inbox.op == OP_READ_REPLY
    # Txn phase-2 write (core/txn.py): write-like, keeps its opcode so the
    # tail replies OP_TXN_REPLY; exempt from the freeze NACK (admission was
    # at PREPARE - the freeze stops new PREPAREs instead).
    is_commit = inbox.op == OP_COMMIT
    is_tail = roles.is_tail

    # Write freeze (recovery copy window): client writes NACK at the entry.
    nacked = is_write & (inbox.seq < 0) & roles.frozen
    is_write = (is_write & ~nacked) | is_commit

    # ---------------- READ: only the tail replies ----------------
    with stage("store"):
        v0, s0 = store_lib.read_clean(store, inbox.key)
    tail_answers = is_read & is_tail
    fwd_read = is_read & ~is_tail
    # Reply retraces the chain: next stop is one hop back toward the entry
    # node (or the client if the read entered at the tail itself).  The
    # retrace follows the live chain (prev_pos skips spliced-out nodes).
    back_dst = jnp.where(inbox.entry == roles.my_pos, TO_CLIENT, roles.prev_pos)
    replies = Msg(
        op=jnp.where(tail_answers, OP_READ_REPLY, 0),
        key=inbox.key,
        value=v0,
        seq=s0,
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(tail_answers, back_dst, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(tail_answers)

    # ---------------- READ_REPLY relay back toward the entry node --------
    relay_dst = jnp.where(inbox.entry == roles.my_pos, TO_CLIENT, roles.prev_pos)
    relays = Msg(
        op=jnp.where(is_reply, OP_READ_REPLY, 0),
        key=inbox.key,
        value=inbox.value,
        seq=inbox.seq,
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(is_reply, relay_dst, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(is_reply)

    # ---------------- WRITE: overwrite + propagate ----------------
    needs_seq = is_write & (inbox.seq < 0)
    with stage("store"):
        new_store, stamped = store_lib.assign_seqs(store, inbox.key, needs_seq,
                                                   dense_rank=dense_rank)
    # NetChain's 16-bit SEQ: wrap-around reproduces the overflow limitation.
    wseq = jnp.where(needs_seq, stamped % (1 << SEQ_BITS), inbox.seq)
    with stage("store"):
        new_store = store_lib.overwrite_clean(
            new_store, inbox.key, inbox.value, wseq, is_write
        )
    fwd_write = is_write & ~is_tail
    forwards = Msg(
        op=jnp.where(fwd_write,
                     jnp.where(is_commit, OP_COMMIT, OP_WRITE), 0),
        key=inbox.key,
        value=inbox.value,
        seq=wseq,
        src=jnp.full((B,), roles.my_pos, jnp.int32),
        dst=jnp.where(fwd_write, roles.next_pos, NOWHERE),
        client=inbox.client,
        entry=inbox.entry,
        qid=inbox.qid,
        t_inject=inbox.t_inject,
        extra=inbox.extra,
        ver=inbox.ver,
    ).mask(fwd_write | fwd_read)
    # Forwarded reads ride in the same section (op stays READ).
    forwards = forwards._replace(
        op=jnp.where(fwd_read, OP_READ, forwards.op),
        seq=jnp.where(fwd_read, inbox.seq, forwards.seq),
        dst=jnp.where(fwd_read, roles.next_pos, forwards.dst),
    )

    # Tail acknowledges the write straight to the client (CR semantics);
    # freeze NACKs share the section (disjoint masks).
    wack = is_write & is_tail
    wr_mask = wack | nacked
    wreplies = Msg(
        op=jnp.where(nacked, OP_WRITE_NACK,
                     jnp.where(wack,
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

    outbox = Msg.concat([replies, forwards, relays, wreplies])
    return new_store, outbox

"""The plain reference that decides ``correct``.

The configurations state strong consistency: every read returns the value
of a write that a linearizable register of that key could return, and
every acknowledged write reads back from every replica once the chain is
drained.  The reference is that register, one per key, written from the
op stream the harness drew itself (``ycsb.Arrivals``) and the values it
loaded (``ycsb.load_values``).  It imports nothing of the program: it reads
only the replies the clients received and, after the drain, each
replica's committed cell.

Numbers compared (each with its limit in ``check``):

* ``ops_mismatch``: replies whose qid is not an op the harness drew, that
  answer an op twice, or whose kind, key, injection tick or written value
  differ from the drawn op.
* ``offered_mismatch``: the program's count of offered ops against the
  harness's own count of live lanes over the same ticks.
* ``read_value_mismatch``: reads whose (seq, value) is no version of that
  key: neither the loaded value (seq 0) nor an acknowledged write.
* ``stale_reads``: reads older than a write acknowledged, or a read
  answered, before the read was sent.
* ``future_reads``: reads of a write sent after the read was answered.
* ``write_order_violations``: two acknowledged writes of one key with one
  seq, or a write ordered after a write that was sent after it completed.
* ``replica_mismatch``: (replica, key) cells whose committed value or seq
  differ from the key's newest acknowledged write (or its loaded value).
* ``dirty_after_drain``: (replica, key) cells still holding an
  uncommitted version after the drain.
* ``unresolved``: ops still in the engine (inbox, backlog) after the drain.
* ``log_lost``: replies the program's reply log could not hold.
* ``lost_reads``: reads that never got a reply.  The configurations drop
  no read: a lost read is a lost message.
* ``lost_writes``: updates that never got a reply where the configuration
  allows none.  A NetCRAQ head drops an update, with no reply, only when
  the key's version window is full: ``num_versions - 1`` earlier updates
  of the key hold a dirty cell at the head.  An update holds its cell from
  its arrival at the head until the tail's ACK comes back, which is the
  tick its reply is stamped with (``t_done``), so at any tick ``tau`` the
  cells held are at most the answered updates of the key with
  ``t_inject <= tau < t_done``.  A lost update is allowed only if that
  count reaches ``num_versions - 1`` at some tick between its send tick and
  that tick plus the longest wait for a lane any answered op of the run
  shows.  NetChain drops none, so every lost update counts.

Every number is an exact count with the limit 0.  Ops that fail as the
configuration allows (an update dropped at a full version window) count
in ``failed``.
"""
from __future__ import annotations

import numpy as np

from bench.ycsb import OP_READ_REPLY, OP_WRITE_NACK, OP_WRITE_REPLY

BIG = np.int64(1) << 32


def _group_suffix_min_exclusive(keys, vals):
    """For arrays sorted by key: min of vals[j] over j > i in i's key group
    (inf where there is none)."""
    n = keys.shape[0]
    out = np.full(n, np.iinfo(np.int64).max, np.int64)
    if n == 0:
        return out
    rk, rv = keys[::-1], vals[::-1]
    # within a key group the running min restarts because every earlier
    # (larger-key) group's values sit above all of this group's
    enc = np.minimum.accumulate(rk * BIG + rv)[::-1] - keys * BIG
    same_next = np.zeros(n, bool)
    same_next[:-1] = keys[1:] == keys[:-1]
    out[:-1] = np.where(same_next[:-1], enc[1:], out[:-1])
    return out


def _lower_bounds(ev_key, ev_t, ev_seq, q_key, q_t):
    """For each query (key, t): the largest ev_seq among events of that key
    with ev_t <= t, or -1."""
    if ev_key.size == 0:
        return np.full(q_key.shape, -1, np.int64)
    order = np.lexsort((ev_t, ev_key))
    k, t, s = ev_key[order], ev_t[order], ev_seq[order]
    run = np.maximum.accumulate(k * BIG + s) - k * BIG
    pos = np.searchsorted(k * BIG + t, q_key * BIG + q_t, side="right") - 1
    ok = (pos >= 0) & (k[np.clip(pos, 0, None)] == q_key)
    return np.where(ok, run[np.clip(pos, 0, None)], -1)


def _held_cells(held_key, held_t0, held_t1, q_key, q_t):
    """For each query (key, tau): the number of intervals of that key with
    t0 <= tau < t1."""
    s = np.sort(held_key * BIG + held_t0)
    e = np.sort(held_key * BIG + held_t1)
    q = q_key * BIG + q_t
    # every key's intervals open and close in pairs, so the intervals of
    # smaller keys cancel between the two counts
    return np.searchsorted(s, q, side="right") - np.searchsorted(e, q, side="right")


def lane_wait(ops: dict, answered: np.ndarray, t_done: np.ndarray,
              chain_len: int) -> int:
    """The longest an answered op can have waited for a lane, in ticks, and
    one more: a write needs ``chain_len`` ticks from its head to its reply,
    a read at least one."""
    lat = t_done - ops["t"]
    least = np.where(ops["is_write"], chain_len, 1)
    return int(np.max(np.where(answered, lat - least, 0), initial=0)) + 1


def lost_writes(ops: dict, answered: np.ndarray, t_done: np.ndarray,
                window_cells: int | None, wait: int) -> int:
    """Unanswered updates that the configuration's version window cannot
    explain (module docstring).  ``t_done`` [ops]: reply tick of answered
    ops.  ``window_cells``: earlier unacknowledged updates of a key that
    fill its window, None where the configuration drops no update.
    ``wait``: ticks past its send tick at which an update may have met the
    head."""
    is_w = ops["is_write"]
    lost = np.nonzero(is_w & ~answered)[0]
    if window_cells is None or lost.size == 0:
        return int(lost.size)
    held = np.nonzero(is_w & answered)[0]
    taus = ops["t"][lost][:, None] + np.arange(wait + 1)[None, :]
    keys = np.broadcast_to(ops["gkey"][lost][:, None], taus.shape)
    count = _held_cells(ops["gkey"][held], ops["t"][held], t_done[held],
                        keys.ravel(), taus.ravel()).reshape(taus.shape)
    return int((count.max(axis=1) < window_cells).sum())


def evaluate(ops: dict, replies: dict, load: np.ndarray, final: dict,
             counters: dict, window: tuple[int, int],
             window_cells: int | None, chain_len: int) -> dict:
    """Compare one run with the reference.

    ``ops``: the harness's drawn live lanes over every generated tick
    (``Arrivals.draw``).  ``replies``: every reply the clients received,
    with ``gkey`` the global key the program answered for.  ``load``:
    [G, W] loaded values.  ``final``: ``value`` [R, G, W], ``seq`` [R, G],
    ``pending`` [R, G] committed cells per replica after the drain.
    ``counters``: ``offered`` (program), ``unresolved``, ``log_lost``.
    ``window``: [t0, t1) ticks of the measured window.
    ``window_cells``, ``chain_len``: what ``lost_writes`` needs.

    Returns the compared numbers, ``attempted`` and ``failed`` of the
    window, and the mask of success replies (``ok``) for the metrics.
    """
    o_qid = ops["qid"]
    r_qid = replies["qid"].astype(np.int64)
    n_ops, n_rep = o_qid.size, r_qid.size
    if n_ops == 0:
        raise ValueError("the run sent no op")
    idx = np.clip(np.searchsorted(o_qid, r_qid), 0, n_ops - 1)
    found = o_qid[idx] == r_qid
    o_wr = ops["is_write"][idx]
    r_op = replies["op"]
    kind_ok = np.where(o_wr, (r_op == OP_WRITE_REPLY) | (r_op == OP_WRITE_NACK),
                       r_op == OP_READ_REPLY)
    good = (found & kind_ok
            & (replies["gkey"] == ops["gkey"][idx])
            & (replies["t_inject"] == ops["t"][idx])
            & ~((r_op == OP_WRITE_REPLY) & (replies["value0"] != ops["value0"][idx])))
    _, first, counts = np.unique(r_qid, return_index=True, return_counts=True)
    dup = np.ones(n_rep, bool)
    dup[first] = False
    ok_reply = good & ~dup & ((r_op == OP_READ_REPLY) | (r_op == OP_WRITE_REPLY))
    ops_mismatch = int((~good).sum() + (counts - 1).sum())

    # --- acknowledged writes: the versions of each key -------------------
    w = ok_reply & (r_op == OP_WRITE_REPLY)
    w_key = ops["gkey"][idx][w]
    w_seq = replies["seq"][w].astype(np.int64)
    w_val = ops["value0"][idx][w]
    w_ti = ops["t"][idx][w]
    w_td = replies["t_done"][w].astype(np.int64)
    order = np.lexsort((w_seq, w_key))
    w_key, w_seq, w_val, w_ti, w_td = (a[order] for a in (w_key, w_seq, w_val, w_ti, w_td))
    same = np.zeros(w_key.size, bool)
    same[1:] = (w_key[1:] == w_key[:-1]) & (w_seq[1:] == w_seq[:-1])
    later_done = _group_suffix_min_exclusive(w_key, w_td)
    write_order = int(same.sum() + (later_done <= w_ti).sum())

    # --- reads ------------------------------------------------------------
    r = ok_reply & (r_op == OP_READ_REPLY)
    r_key = ops["gkey"][idx][r]
    r_seq = replies["seq"][r].astype(np.int64)
    r_val = replies["value0"][r].astype(np.int64)
    r_ti = ops["t"][idx][r]
    r_td = replies["t_done"][r].astype(np.int64)
    G = load.shape[0]
    # version table: (key, seq) -> (value0, tick sent); seq 0 = loaded
    v_code = np.concatenate([np.arange(G, dtype=np.int64) * BIG, w_key * BIG + w_seq])
    v_val = np.concatenate([load[:, 0].astype(np.int64), w_val])
    v_ti = np.concatenate([np.full(G, -1, np.int64), w_ti])
    vo = np.argsort(v_code, kind="stable")
    v_code, v_val, v_ti = v_code[vo], v_val[vo], v_ti[vo]
    q = r_key * BIG + r_seq
    vi = np.clip(np.searchsorted(v_code, q), 0, v_code.size - 1)
    known = (v_code[vi] == q) & (r_seq >= 0)
    read_value_mismatch = int((~known | (v_val[vi] != r_val)).sum())
    future_reads = int((known & (v_ti[vi] >= r_td)).sum())
    lb = _lower_bounds(np.concatenate([w_key, r_key]),
                       np.concatenate([w_td, r_td]),
                       np.concatenate([w_seq, r_seq]), r_key, r_ti)
    stale_reads = int((r_seq < lb).sum())

    # --- committed state on every replica after the drain ---------------
    ref_val = np.array(load, np.int64)
    ref_seq = np.zeros(G, np.int64)
    if w_key.size:
        last = np.ones(w_key.size, bool)
        last[:-1] = w_key[1:] != w_key[:-1]
        ref_val[w_key[last]] = 0
        ref_val[w_key[last], 0] = w_val[last]
        ref_seq[w_key[last]] = w_seq[last]
    bad_cell = ((final["value"] != ref_val[None]).any(axis=-1)
                | (final["seq"] != ref_seq[None]))
    replica_mismatch = int(bad_cell.sum())
    dirty_after_drain = int((final["pending"] != 0).sum())

    # --- the window's attempted and failed ops ---------------------------
    t0, t1 = window
    in_win = (ops["t"] >= t0) & (ops["t"] < t1)
    answered = np.zeros(n_ops, bool)
    answered[idx[ok_reply]] = True
    attempted = int(in_win.sum())
    failed = int((in_win & ~answered).sum())

    # lost ops: every read, and the updates the window cannot explain
    lost_reads = int((~ops["is_write"] & ~answered).sum())
    t_done = np.zeros(n_ops, np.int64)
    t_done[idx[ok_reply]] = replies["t_done"][ok_reply]
    wait = lane_wait(ops, answered, t_done, chain_len)
    n_lost_writes = lost_writes(ops, answered, t_done, window_cells, wait)

    numbers = {
        "ops_mismatch": ops_mismatch,
        "offered_mismatch": abs(int(counters["offered"]) - n_ops),
        "read_value_mismatch": read_value_mismatch,
        "stale_reads": stale_reads,
        "future_reads": future_reads,
        "write_order_violations": write_order,
        "replica_mismatch": replica_mismatch,
        "dirty_after_drain": dirty_after_drain,
        "unresolved": int(counters["unresolved"]),
        "log_lost": int(counters["log_lost"]),
        "lost_reads": lost_reads,
        "lost_writes": n_lost_writes,
    }
    return {"numbers": numbers, "attempted": attempted, "failed": failed,
            "ok": ok_reply, "reads_checked": int(r.sum()),
            "writes_acked": int(w.sum()), "lane_wait": wait}


LIMITS = {name: 0 for name in (
    "ops_mismatch", "offered_mismatch", "read_value_mismatch", "stale_reads",
    "future_reads", "write_order_violations", "replica_mismatch",
    "dirty_after_drain", "unresolved", "log_lost", "lost_reads",
    "lost_writes")}


def check(numbers: dict) -> tuple[bool, dict]:
    limits = LIMITS
    table = {name: {"value": numbers[name], "limit": limits[name]}
             for name in limits}
    correct = all(numbers[n] <= limits[n] for n in limits)
    return correct, table

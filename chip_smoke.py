"""Bring-up smoke run of the coordination store on a TPU.

Drives the served path once, in this one process, at a state size a
deployment would hold, and checks every answer against the repo's plain
references.  It proves that the system runs on the chip; it is not a
benchmark, and the seconds it prints are bring-up wall time.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: phase C only

Phase A, the open-loop store: 16 NetCRAQ chains of 4 replicas with the
  paper's 128-bit values, uniform keys, open-loop traffic at half the
  injection-lane capacity (25% writes, 5% two-shot PREPARE->COMMIT
  clients, a finite lock lease), run by ``run_scenario`` with no
  disturbance.  Checks: stores equal the serial reference, every replica
  agrees, nothing is left in flight and no lock leaked.
Phase B, transactions: 256 two-key cross-chain transactions through the
  in-tick wave coordinator (``TxnWaveDriver``) on an engine of the same
  size.  Checks: every transaction resolves and most commit, locks and
  wave slots drain, and the stores equal the serial replay of the
  committed subset.
Phase C, four chips: ``ChainDist.make_step`` with one chain node per chip
  serves a stream of reads, writes and transactions.  Checks: every
  acknowledged write reads back from all four replicas, locks are free at
  drain, and the committed store equals the same stream replayed through
  a one-chip ``ChainSim``.

Every phase builds its data from ``--seed``.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmarks.fig_txn_pipeline import check_serial  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    CLIENT_BASE, OP_COMMIT, OP_PREPARE, OP_READ, OP_READ_REPLY, OP_TXN_REPLY,
    OP_WRITE, OP_WRITE_REPLY, ChainConfig, ChainDist, ChainSim, ClusterConfig,
    Msg, TxnPlanner, TxnWaveDriver, TxnWorkloadConfig, locks_all_free,
    make_loadgen, make_txn_workload, none_scenario, run_scenario,
)

N_NODES = 4
# Phase A asks for 2^20 keys (16 chains x 65536 registers).  The v5e
# compiler refuses that program: the store's temporaries [C, n, K, V=4,
# W=4] int32 tile their two minor dimensions to (8, 128), so the program
# needs 32.08 GiB at 2^20 keys and 16.06 GiB at 2^19, against 15.75 GiB of
# HBM.  Halving until it fits gives 2^18 keys, 8.05 GiB of temporaries.
KEYS_ASKED = 1 << 20
N_CHAINS = 16
KEYS_PER_CHAIN = 1 << 14
CUT = ("2^20 -> 2^18 keys: the v5e program needs 32.08 GiB at 2^20 and "
       "16.06 GiB at 2^19 (store temporaries tiled to (8, 128)), 8.05 GiB "
       "at 2^18, of 15.75 GiB")
DIST_KEYS = 1 << 18
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class _Clock:
    """Wall seconds of a phase and the part of them XLA spent compiling."""

    def __enter__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        self.t0 = time.perf_counter()
        return self

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.compile_s += secs

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self.t0
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def _nbytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def _cluster(num_keys: int, n_chains: int) -> ClusterConfig:
    return ClusterConfig(
        chain=ChainConfig(n_nodes=N_NODES, num_keys=num_keys, num_versions=4,
                          value_words=4, protocol="netcraq"),
        n_chains=n_chains,
    )


# -- phase A: the open-loop store ------------------------------------------
def phase_openloop(num_keys: int, n_chains: int, seed: int,
                   inject_capacity: int = 64, ticks: int = 64,
                   segment_ticks: int = 16, lease_ticks: int = 32) -> dict:
    cluster = _cluster(num_keys, n_chains)
    lanes = n_chains * N_NODES * inject_capacity
    # every offered op gets one reply; a chain's mean is half its lanes per
    # tick, so a log of all its lanes over the run holds twice the mean
    sim = ChainSim(cluster, inject_capacity=inject_capacity,
                   reply_capacity=N_NODES * inject_capacity * ticks)
    gen = make_loadgen(cluster, qps=lanes / 2, write_fraction=0.25,
                       txn_fraction=0.05, seed=seed, backlog_capacity=lanes)
    # run_scenario asserts the drain (nothing in flight, no lock held),
    # replica convergence and the serial reference
    state, _, report = run_scenario(
        sim, gen, none_scenario(ticks, segment_ticks), lease_ticks=lease_ticks,
    )
    m = report["metrics"]
    return {
        "phase": "A_openloop",
        "keys": cluster.num_global_keys,
        "chains": n_chains,
        "replicas": N_NODES,
        "ticks": ticks,
        "qps_per_tick": lanes / 2,
        "ops_offered": m["offered"],
        "ops_shed": m["admission_drops"],
        "ops_completed": int(np.asarray(state.replies.cursor).sum()),
        "keys_checked_vs_serial_reference": cluster.num_global_keys,
        "keys_written": report["serial_keys"],
        "replicas_converged": True,
        "inflight": sim.inflight(state),
        "leaked_locks": report["leaked_locks"],
        "store_logical_bytes": _nbytes(state.stores),
    }


# -- phase B: cross-chain transactions through the wave coordinator --------
def phase_txn(num_keys: int, n_chains: int, n_txns: int, seed: int) -> dict:
    cluster = _cluster(num_keys, n_chains)
    sim = ChainSim(cluster, wave_depth=16)
    txns = make_txn_workload(cluster, TxnWorkloadConfig(
        n_txns=n_txns, keys_per_txn=2, cross_chain_fraction=1.0, seed=seed,
    ))
    state, results = TxnWaveDriver(sim, TxnPlanner(cluster)).run(
        sim.init_state(), txns)
    state = sim.drain(state, 4 * sim.n)

    assert sorted(r.txn_id for r in results) == sorted(t.txn_id for t in txns)
    commits = sum(r.committed for r in results)
    assert 2 * commits > len(txns), f"only {commits}/{len(txns)} committed"
    md = state.metrics.asdict()
    assert md["wave_commits"] + md["wave_aborts"] == len(txns)
    assert md["drops"] == 0, "wave control traffic was dropped"
    expected = check_serial(cluster, sim, state, txns, results)
    return {
        "phase": "B_txn",
        "keys": cluster.num_global_keys,
        "txns_offered": len(txns),
        "txns_resolved": len(results),
        "txns_committed": commits,
        "keys_checked_vs_serial_reference": cluster.num_global_keys,
        "keys_written": len(expected),
        "store_logical_bytes": _nbytes(state.stores),
    }


# -- phase C: one chain node per chip --------------------------------------
def op_stream(num_keys: int, steps: int, lanes: int, seed: int,
              drain: int = 16, gap: int = 8):
    """Client ops for ``steps`` steps then ``drain`` empty ones, as a host
    ``Msg`` of ``[steps + drain, N_NODES, lanes]`` arrays, and
    ``{qid: (op, key, value)}``.

    The head's lanes carry last step's COMMITs, two PREPAREs and plain
    writes, with one read; every other node's lanes carry reads, half of
    them of keys written in the last few steps.  A key written or locked
    in one step is not written again for ``gap`` steps, so no two
    writes of one key are in flight together and the outcome does not
    depend on the order of ops within a batch."""
    rng = np.random.default_rng(seed)
    T = steps + drain
    s = jax.tree.map(
        lambda x: np.tile(np.asarray(x)[None, None],
                          (T, N_NODES) + (1,) * x.ndim),
        Msg.empty(lanes),
    )
    ops: dict[int, tuple[int, int, tuple]] = {}
    last_write = np.full(num_keys, -gap, np.int64)
    qid, prepared = 0, []

    def put(t, node, slot, op, key, value, seq=-1):
        nonlocal qid
        client = CLIENT_BASE + qid % 1024
        for f, v in (("op", op), ("key", key), ("seq", seq), ("src", client),
                     ("dst", node), ("client", client), ("qid", qid),
                     ("t_inject", t)):
            getattr(s, f)[t, node, slot] = v
        s.value[t, node, slot] = value
        ops[qid] = (op, key, tuple(int(v) for v in value))
        qid += 1

    def fresh_key(t):
        while True:
            k = int(rng.integers(num_keys))
            if t - last_write[k] >= gap:
                last_write[k] = t
                return k

    def read_key():
        recent = np.nonzero(last_write >= 0)[0]
        if recent.size and rng.random() < 0.5:
            return int(rng.choice(recent))
        return int(rng.integers(num_keys))

    zero = np.zeros(4, np.int32)
    for t in range(steps):
        slot = 0
        for key, value, txn_id in prepared:
            put(t, 0, slot, OP_COMMIT, key, value, seq=txn_id)
            slot += 1
        prepared = []
        n_prep = 2 if t < steps - 1 else 0
        for _ in range(n_prep):
            key = fresh_key(t)
            value = rng.integers(1, 1 << 20, 4, dtype=np.int32)
            prepared.append((key, value, qid))
            put(t, 0, slot, OP_PREPARE, key, zero, seq=qid)
            slot += 1
        while slot < lanes - 1:
            put(t, 0, slot, OP_WRITE, fresh_key(t),
                rng.integers(1, 1 << 20, 4, dtype=np.int32))
            slot += 1
        put(t, 0, slot, OP_READ, read_key(), zero)
        for node in range(1, N_NODES):
            for slot in range(lanes):
                put(t, node, slot, OP_READ, read_key(), zero)
    return s, ops


def _acked_values(replies: list, ops: dict) -> dict:
    """{key: value} of the newest acknowledged write of every key: a
    WRITE_REPLY, or a TXN_REPLY whose commit stamped a seq."""
    best: dict[int, tuple[int, tuple]] = {}
    for r in replies:
        for q, op, seq in zip(r.qid.ravel(), r.op.ravel(), r.seq.ravel()):
            if op not in (OP_WRITE_REPLY, OP_TXN_REPLY) or seq < 0:
                continue
            _, key, value = ops[int(q)]
            if key not in best or seq > best[key][0]:
                best[key] = (int(seq), value)
    return {k: v for k, (_, v) in best.items()}


def phase_four_chips(devices, num_keys: int, seed: int, steps: int = 32,
                     lanes: int = 8, batch: int = 64) -> dict:
    mesh = Mesh(np.asarray(devices).reshape(N_NODES), ("chain",),
                axis_types=(AxisType.Auto,))
    cfg = ChainConfig(n_nodes=N_NODES, num_keys=num_keys, num_versions=4,
                      protocol="netcraq")
    dist = ChainDist(cfg, mesh, axis="chain")
    shard, rep = NamedSharding(mesh, P("chain")), NamedSharding(mesh, P())
    stream, ops = op_stream(num_keys, steps, lanes, seed)
    T = stream.op.shape[0]

    stores = jax.device_put(dist.init_state(), shard)
    roles = jax.device_put(dist.full_roles(), shard)
    pmap = jax.device_put(dist.default_pmap(), rep)
    locks = jax.device_put(dist.init_locks(), rep)
    inbox = jax.device_put(
        jax.tree.map(lambda x: np.tile(np.asarray(x)[None],
                                       (N_NODES,) + (1,) * x.ndim),
                     Msg.empty(batch)),
        shard,
    )
    step = dist.make_step(batch)
    feed = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.concatenate([x, y], axis=1), a, b))
    replies = []
    for t in range(T):
        inj = jax.device_put(jax.tree.map(lambda x: x[t], stream), shard)
        stores, inbox, out, locks = step(stores, feed(inbox, inj), roles,
                                         pmap, locks)
        replies.append(jax.device_get(out))
    shard_devices = [str(s.device) for s in stores.values.addressable_shards]
    assert len(set(shard_devices)) == N_NODES, shard_devices

    # drained: nothing in flight, no dirty version, every lock free
    assert int(jnp.sum(inbox.op != 0)) == 0, "ops still in flight"
    got = jax.device_get(stores)
    assert int(got.pending.sum()) == 0
    assert locks_all_free(locks), "a lock is held at drain"
    # every client op got exactly one reply
    qids = np.concatenate([r.qid[r.op != 0] for r in replies])
    assert sorted(qids.tolist()) == sorted(ops), "an op lost its reply"

    # the guarantee: every acknowledged write reads back from all replicas
    acked = _acked_values(replies, ops)
    keys = sorted(acked)
    reads = 0
    for lo in range(0, len(keys), batch):
        chunk = keys[lo:lo + batch]
        m = jax.tree.map(lambda x: np.tile(np.asarray(x)[None],
                                           (N_NODES,) + (1,) * x.ndim),
                         Msg.empty(batch + lanes))
        want = {}
        for node in range(N_NODES):
            for i, k in enumerate(chunk):
                q = len(ops) + reads
                want[q] = (node, acked[k])
                m.op[node, i], m.key[node, i] = OP_READ, k
                m.src[node, i] = m.client[node, i] = CLIENT_BASE
                m.dst[node, i], m.qid[node, i] = node, q
                reads += 1
        stores, _, out, locks = step(stores, jax.device_put(m, shard), roles,
                                     pmap, locks)
        out = jax.device_get(out)
        seen = {}
        for node in range(N_NODES):
            for j in np.nonzero(out.op[node] == OP_READ_REPLY)[0]:
                seen[int(out.qid[node, j])] = (
                    node, tuple(int(v) for v in out.value[node, j]))
        assert seen == want, "an acknowledged write did not read back"

    # the same stream through the one-chip engine: equal committed stores
    sim = ChainSim(ClusterConfig(chain=cfg, n_chains=1),
                   inject_capacity=lanes, route_capacity=batch,
                   reply_capacity=len(ops))
    st = sim.run(sim.init_state(), jax.tree.map(lambda x: x[:, None], stream),
                 extra_ticks=0)
    ref = jax.device_get(jax.tree.map(lambda x: x[0], st.stores))
    for f in ("values", "seqs"):
        a, b = getattr(got, f)[:, :, 0], getattr(ref, f)[:, :, 0]
        cells = int((a != b).reshape(a.shape[0], a.shape[1], -1)
                    .any(axis=-1).sum())
        assert cells == 0, (
            f"committed {f} differ from the one-chip engine in {cells} "
            "(node, key) cells"
        )
    assert int(ref.pending.sum()) == 0 and locks_all_free(st.locks)
    log = st.replies.merged()
    n = int(log.cursor)
    sim_replies = sorted(zip(np.asarray(log.qid)[:n].tolist(),
                             np.asarray(log.op)[:n].tolist(),
                             np.asarray(log.seq)[:n].tolist()))
    dist_replies = sorted(
        (int(q), int(o), int(s)) for r in replies
        for q, o, s in zip(r.qid.ravel(), r.op.ravel(), r.seq.ravel())
        if o != 0
    )
    assert sim_replies == dist_replies, "replies differ from the one-chip engine"
    return {
        "phase": "C_four_chips",
        "keys": num_keys,
        "replicas": N_NODES,
        "steps": T,
        "ops_offered": len(ops),
        "ops_completed": int(qids.size),
        "acked_keys_read_back": len(keys),
        "replica_reads_checked": reads,
        "keys_checked_vs_one_chip": num_keys,
        "shard_devices": shard_devices,
        "store_logical_bytes_per_chip": _nbytes(got) // N_NODES,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ChainDist phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
                 "this script has no CPU path")
    cache = enable_compile_cache()
    print(json.dumps({"jax": jax.__version__, "devices": len(devices),
                      "kind": dev.device_kind, "compile_cache": cache}),
          flush=True)

    def report(phase, *a, **kw):
        with _Clock() as clock:
            row = phase(*a, **kw)
        row.update(compile_s=clock.compile_s, bringup_wall_s=clock.wall_s,
                   peak_bytes_in_use=[d.memory_stats()["peak_bytes_in_use"]
                                      for d in devices],
                   memory_stats_device0=dev.memory_stats())
        print(json.dumps(row), flush=True)

    if args.four_chips:
        if len(devices) < N_NODES:
            sys.exit(f"chip_smoke: --four-chips needs {N_NODES} chips, JAX "
                     f"found {len(devices)}")
        report(phase_four_chips, devices[:N_NODES], DIST_KEYS, args.seed)
    else:
        print(json.dumps({"phase": "A_openloop", "keys_asked": KEYS_ASKED,
                          "keys": N_CHAINS * KEYS_PER_CHAIN, "cut": CUT}),
              flush=True)
        report(phase_openloop, KEYS_PER_CHAIN, N_CHAINS, args.seed)
        report(phase_txn, KEYS_PER_CHAIN, N_CHAINS, 256, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

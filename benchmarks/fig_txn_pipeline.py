"""Pipelined in-network transaction engine vs the host-driven coordinator.

The host-side ``TxnDriver`` (the correctness oracle) pays per-phase host
round trips: inject PREPAREs, poll replies, decide, inject COMMIT/ABORTs,
poll again - a handful of synchronization barriers per transaction wave.
The wave-table engine (``TxnWaveDriver`` + the in-tick coordinator stage
of core/chain.py) moves the whole 2PC state machine into the device
program; the host only batch-admits transactions into FREE coordinator
slots and reads the completion log once.  This figure measures what that
buys at the paper's scale axis - commit throughput in transactions per
simulated tick, with hundreds of transactions overlapping in flight.

Asserted acceptance criteria:

* headline: >= 5x commit throughput over the host driver at C=4, k=2,
  cross=1 (same workload, same cluster);
* admission only: host synchronization rounds per committed transaction
  stay << 1 (the admission loop syncs once per drain round, not per txn
  phase - vs the host driver's >= 2 barriers per wave of 6);
* correctness carried over: every config's final stores equal the serial
  reference replay of its committed subset, locks and wave slots drain,
  and the sized-to-worst-case control buffers drop nothing;
* zero recompiles across the whole sweep (admission is pure state swap).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import BenchRow
from repro.core import (ChainConfig, ChainSim, ClusterConfig, Coordinator,
                        Txn, TxnDriver, TxnPlanner, TxnWaveDriver,
                        TxnWorkloadConfig, committed_view, locks_all_free,
                        make_txn_workload, reference_execute, serial_order)


def check_serial(cluster, sim, state, txns, results) -> dict:
    """Locks/waves drained + serial-reference store equality (the same
    oracle fig_txn and the property tests run); returns the reference's
    ``{global_key: value}``."""
    assert locks_all_free(state.locks), "a transaction leaked a lock"
    assert int(state.stores.pending.sum()) == 0
    assert Coordinator.waves_drained(state)
    by_id = {t.txn_id: t for t in txns}
    committed_ids = {r.txn_id for r in results if r.committed}
    order = serial_order(results)
    tail = [t for t in sorted(committed_ids) if t not in set(order)]
    expected = reference_execute([by_id[t] for t in order + tail])
    view = committed_view(cluster, state)
    for gk in range(cluster.num_global_keys):
        assert view[gk] == expected.get(gk, 0), (
            f"non-atomic outcome at key {gk}: store={view[gk]} "
            f"reference={expected.get(gk, 0)}"
        )
    return expected


def _run_host(sim, cluster, txns, txns_per_wave=6):
    """Host-driven baseline: the oracle driver, one wave at a time (its
    planner batches a wave's phase-1/phase-2 round trips)."""
    state = sim.init_state()
    drv = TxnDriver(sim, TxnPlanner(cluster))
    results, rounds = [], 0
    for w in range(0, len(txns), txns_per_wave):
        state, res = drv.run(state, txns[w:w + txns_per_wave])
        results += res
        rounds += 2  # phase-1 + phase-2 host barriers per wave
    state = sim.drain(state, 4 * sim.n)
    check_serial(cluster, sim, state, txns, results)
    return results, int(state.t), rounds, state


def _run_wave(sim, cluster, txns):
    """Pipelined engine: batch admission into the wave table, then the
    device runs every transaction's 2PC concurrently."""
    state = sim.init_state()
    drv = TxnWaveDriver(sim, TxnPlanner(cluster))
    state, results = drv.run(state, txns)
    state = sim.drain(state, 4 * sim.n)
    check_serial(cluster, sim, state, txns, results)
    return results, drv.last_ticks, drv.last_rounds, state


def run(C: int = 4, n_nodes: int = 4, num_keys: int = 64, versions: int = 8,
        n_txns: int = 128, wave_depth: int = 16, seed: int = 0,
        ) -> list[BenchRow]:
    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=n_nodes, num_keys=num_keys,
                          num_versions=versions),
        n_chains=C,
    )
    host_sim = ChainSim(cluster, inject_capacity=24, route_capacity=256,
                        reply_capacity=16384)
    wave_sim = ChainSim(cluster, inject_capacity=24, route_capacity=256,
                        reply_capacity=16384, wave_depth=wave_depth,
                        wave_keys=4, wave_log_capacity=256)
    # a narrow engine for the occupancy point: 4 slots/chain instead of 16
    narrow_sim = ChainSim(cluster, inject_capacity=24, route_capacity=256,
                          reply_capacity=16384, wave_depth=4,
                          wave_keys=4, wave_log_capacity=256)
    rows: list[BenchRow] = []

    def workload(kpt, skew, s):
        return make_txn_workload(cluster, TxnWorkloadConfig(
            n_txns=n_txns, keys_per_txn=kpt, cross_chain_fraction=1.0,
            key_skew=skew, seed=seed + s, txn_id_base=1,
        ))

    # ---- warm every engine before snapshotting the (global) jit caches
    warm = workload(2, "uniform", 999)[:4]
    _run_host(host_sim, cluster, warm)
    _run_wave(wave_sim, cluster, warm)
    _run_wave(narrow_sim, cluster, warm)
    warm_tick = ChainSim.tick._cache_size()
    warm_drain = ChainSim.drain._cache_size()

    headline_speedup = None
    headline_tput = None
    for kpt in (1, 2, 4):
        for skew in ("uniform", "zipf"):
            txns = workload(kpt, skew, kpt * 10 + (skew == "zipf"))
            h_res, h_ticks, h_rounds, _ = _run_host(host_sim, cluster, txns)
            w_res, w_ticks, w_rounds, w_state = _run_wave(
                wave_sim, cluster, txns)
            h_commits = sum(r.committed for r in h_res)
            w_commits = sum(r.committed for r in w_res)
            h_tput = h_commits / max(h_ticks, 1)
            w_tput = w_commits / max(w_ticks, 1)
            speedup = w_tput / max(h_tput, 1e-9)
            rounds_per_commit = w_rounds / max(w_commits, 1)
            md = w_state.metrics.total().asdict()
            assert md["wave_commits"] + md["wave_aborts"] == len(txns)
            assert md["drops"] == 0, "wave control traffic was dropped"
            name = f"txn_pipeline/k{kpt}_{skew}"
            rows.append(BenchRow(
                name=name,
                us_per_call=0.0,
                derived=(f"wave_tput={w_tput:.3f}txn/tick;"
                         f"host_tput={h_tput:.3f};speedup={speedup:.1f}x;"
                         f"admit_rounds_per_commit={rounds_per_commit:.2f}"),
                data={"keys_per_txn": kpt, "key_skew": skew,
                      "wave_commits": w_commits, "host_commits": h_commits,
                      "wave_aborts": len(w_res) - w_commits,
                      "wave_ticks": w_ticks, "host_ticks": h_ticks,
                      "wave_tput_per_tick": w_tput,
                      "host_tput_per_tick": h_tput,
                      "speedup_vs_host": speedup,
                      "admit_rounds_per_commit": rounds_per_commit,
                      "host_rounds": h_rounds,
                      "mean_occupancy": md["wave_occupancy"] / max(w_ticks, 1),
                      "lock_conflicts": md["lock_conflicts"],
                      "conflict_heat": w_state.metrics.heat_per_bucket()},
            ))
            if kpt == 2 and skew == "uniform":
                headline_speedup, headline_tput = speedup, w_tput
                # the host's per-transaction sync cost is gone: admission
                # rounds amortize over the whole in-flight window
                assert rounds_per_commit < 0.5, rounds_per_commit

    # ---- coordinator-depth point: W=4 vs W=16 at k=2 (occupancy bound)
    txns = workload(2, "uniform", 77)
    n_res, n_ticks, _, n_state = _run_wave(narrow_sim, cluster, txns)
    n_commits = sum(r.committed for r in n_res)
    nmd = n_state.metrics.total().asdict()
    rows.append(BenchRow(
        name="txn_pipeline/depth4_k2_uniform",
        us_per_call=0.0,
        derived=(f"wave_tput={n_commits / max(n_ticks, 1):.3f}txn/tick;"
                 f"wave_depth=4"),
        data={"wave_depth": 4, "wave_commits": n_commits,
              "wave_ticks": n_ticks,
              "wave_tput_per_tick": n_commits / max(n_ticks, 1),
              "mean_occupancy": nmd["wave_occupancy"] / max(n_ticks, 1)},
    ))

    assert headline_speedup is not None and headline_speedup >= 5.0, (
        f"pipelined engine is only {headline_speedup:.1f}x the host driver "
        "(want >= 5x at C=4, k=2, cross=1)"
    )
    recompiles = (ChainSim.tick._cache_size() - warm_tick
                  + ChainSim.drain._cache_size() - warm_drain)
    assert recompiles == 0, (
        f"the pipeline sweep recompiled the data path {recompiles}x"
    )
    rows.append(BenchRow(
        name="txn_pipeline/headline",
        us_per_call=0.0,
        derived=(f"speedup_vs_host={headline_speedup:.1f}x;"
                 f"commit_tput={headline_tput:.3f}txn/tick;"
                 f"recompiles={recompiles}"),
        data={"speedup_vs_host": headline_speedup,
              "commit_tput_per_tick": headline_tput,
              "recompiles": recompiles},
    ))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())

"""The control for ``correct``: a reference store that breaks the stated
guarantee, put in the program's place, must come out not correct.

The configurations state strong consistency (a write is acknowledged only
once the tail has committed it, and a read never returns a value older
than an acknowledged write).  The control keeps the register semantics
but acknowledges a write as soon as the head has it and replicates it one
replica per tick (asynchronous replication); each read is answered by the
replica it entered, from what that replica has applied.  Its replies and
final replicas go through the same comparison as a run of the program
(``bench.oracle``).  ``strong=True`` gives the same store with the
guarantee kept (a write is applied everywhere before it is acknowledged),
which the comparison must accept.

    python3 bench/control.py --workload netcraq.ycsb_b --seeds 1,2,3 --ticks 448

draws the cell's own traffic at its own rate and size on the chip and
prints each seed's compared numbers for the control and for the store
that keeps the guarantee (the readings that set the limits).

    python3 bench/control.py --workload netcraq.ycsb_b --seeds 1,2,3 \
        --fault short_window --seconds 10

runs the cell itself once per seed in this one process, with a fault of
``bench/faults.py`` planted in the program (``none`` for sound runs), and
prints each run's compared numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = np.int64(1) << 32


def reference_replies(ops: dict, load: np.ndarray, n_nodes: int,
                      strong: bool) -> tuple[dict, dict]:
    """Replies and final replicas of the reference store serving ``ops``."""
    from bench.ycsb import OP_READ_REPLY, OP_WRITE_REPLY

    t, lane, key = ops["t"], ops["lane"], ops["gkey"]
    is_w = ops["is_write"]
    G, W = load.shape
    # writes: per-key seqs in arrival order (tick, lane)
    wi = np.nonzero(is_w)[0]
    order = wi[np.lexsort((lane[wi], t[wi], key[wi]))]
    wk, wt = key[order], t[order]
    start = np.ones(order.size, bool)
    start[1:] = wk[1:] != wk[:-1]
    run_start = np.maximum.accumulate(np.where(start, np.arange(order.size), 0))
    wseq = np.arange(order.size) - run_start + 1
    # a write sent in tick t is applied at replica e at tick t + 1 + e
    # (lazy) or everywhere at tick t + n (strong), and acknowledged then
    # (strong) or at tick t + 1 (lazy)
    w_done = wt + (n_nodes if strong else 1)
    # reads: entry replica by lane, answered at the end of their tick
    ri = np.nonzero(~is_w)[0]
    entry = lane[ri] % n_nodes
    lag = (n_nodes - 1) if strong else entry
    visible_by = t[ri] - 1 - lag          # latest send tick it can see
    code = wk * BIG + wt
    pos = np.searchsorted(code, key[ri] * BIG + visible_by, side="right") - 1
    hit = (pos >= 0) & (wk[np.clip(pos, 0, None)] == key[ri])
    pc = np.clip(pos, 0, None)
    r_seq = np.where(hit, wseq[pc], 0)
    r_val = np.where(hit, ops["value0"][order][pc], load[key[ri], 0])
    n = t.size
    rep = {k: np.zeros(n, np.int64) for k in
           ("qid", "op", "gkey", "seq", "value0", "t_inject", "t_done", "hops")}
    rep["qid"], rep["gkey"], rep["t_inject"] = ops["qid"], key, t
    rep["op"][order], rep["seq"][order] = OP_WRITE_REPLY, wseq
    rep["value0"][order], rep["t_done"][order] = ops["value0"][order], w_done
    rep["hops"][order] = n_nodes + 1
    rep["op"][ri], rep["seq"][ri], rep["value0"][ri] = OP_READ_REPLY, r_seq, r_val
    rep["t_done"][ri], rep["hops"][ri] = t[ri] + 1, 2
    final_val = np.array(load, np.int64)
    final_seq = np.zeros(G, np.int64)
    last = np.ones(order.size, bool)
    last[:-1] = start[1:]
    final_val[wk[last]] = 0
    final_val[wk[last], 0] = ops["value0"][order][last]
    final_seq[wk[last]] = wseq[last]
    final = {"value": np.broadcast_to(final_val, (n_nodes, G, W)),
             "seq": np.broadcast_to(final_seq, (n_nodes, G)),
             "pending": np.zeros((n_nodes, G), np.int64)}
    return rep, final


def evaluate(ops, load, n_nodes, strong, window, window_cells=None):
    from bench import oracle

    rep, final = reference_replies(ops, load, n_nodes, strong)
    res = oracle.evaluate(ops, rep, load, final,
                          {"offered": ops["qid"].size, "unresolved": 0,
                           "log_lost": 0}, window, window_cells, n_nodes)
    return oracle.check(res["numbers"])


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ticks", type=int, default=448)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    import jax
    import jax.numpy as jnp

    from bench import faults, harness, ycsb

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.fault is not None:
        for seed in seeds:
            with faults.FAULTS[args.fault]():
                out = harness.run(args.workload, seed, args.seconds, False,
                                  time.perf_counter(), log=lambda msg: None)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": args.fault, "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"],
                              "numbers": {k: v["value"]
                                          for k, v in out["checks"].items()},
                              "metrics": out["metrics"]}), flush=True)
        return 0
    found = harness.load_cell(args.workload)
    cfg, traffic = found["config"], found["traffic"]
    rate = found["rate"]["ops_per_tick"]
    G, W, n = cfg["keys"], cfg["value_words"], cfg["replicas"]
    width = cfg["chains"] * n * cfg["lanes_per_node"]
    for seed in seeds:
        ops = ycsb.Arrivals(seed, traffic, rate, G, width).draw(0, args.ticks)
        load = np.asarray(jax.device_get(ycsb.load_values(
            jnp.asarray(ycsb.seed32(seed), jnp.int32), G, W)))
        window = (args.ticks // 4, args.ticks)
        for strong in (False, True):
            correct, table = evaluate(ops, load, n, strong, window,
                                      harness.window_cells(cfg))
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": "strong" if strong else "lazy",
                              "correct": correct,
                              "numbers": {k: v["value"] for k, v in table.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

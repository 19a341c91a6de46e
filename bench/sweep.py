"""Find a one-chip cell's knee: the highest offered rate (ops per tick) at
which nothing is shed, no message is dropped in the fabric and the
generator's backlog does not grow.

    python3 bench/sweep.py --workloads netcraq.ycsb_b \
        --rates 200,400,800 --segments 4 --seed 11 --out sweep.json

Every rate runs through one compiled program: the rate and the update
share are traced leaves of the generator, swapped between points (the
``fig_hockey`` pattern).  Each point starts from a freshly loaded state.
Cells named together must share one configuration.  Run on the chip; the
knee is recorded in the cell's file by hand, with the rate the cell runs
at (0.8 of the knee).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--segments", type=int, default=4)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.core import loadgen
    from bench import harness, ycsb
    from bench.drivers.openloop import Engine

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    cells = [harness.load_cell(w) for w in args.workloads.split(",")]
    configs = {c["cell"]["config"] for c in cells}
    if len(configs) != 1:
        raise SystemExit("sweep: the workloads must share one configuration")
    rates = [float(r) for r in args.rates.split(",")]
    t0 = time.perf_counter()
    eng = Engine(cells[0]["config"], cells[0]["traffic"], rates[0], args.seed)
    rows = []
    for found in cells:
        cdf = ycsb.key_cdf(found["traffic"], eng.G)
        for rate in rates:
            eng.state = None
            eng.state = eng.build(jnp.asarray(eng.seed32, jnp.int32))
            # the generator's leaves are donated with it: build them anew
            eng.gen = loadgen.reset(eng.gen)._replace(
                key_cdf=jnp.asarray(cdf),
                write_fraction=jnp.asarray(found["traffic"]["updateproportion"],
                                           jnp.float32),
                qps=jnp.asarray(rate, jnp.float32))
            eng.t = 0
            answered, walls, backlog = 0, [], []
            for _ in range(args.segments):
                s = time.perf_counter()
                log = eng.segment()
                walls.append(time.perf_counter() - s)
                cur = np.asarray(jax.device_get(log.cursor))
                answered += int(cur.sum())
                backlog.append(eng.backlog())
            c = eng.counters()
            ticks = args.segments * eng.seg
            row = {
                "workload": found["cell"]["name"], "rate": rate,
                "offered": c["offered"], "shed": c["admission_drops"],
                "fabric_drops": c["fabric_drops"], "backlog": backlog,
                "answered": answered, "answered_per_tick": answered / ticks,
                "segment_s": walls, "tick_ms": 1e3 * np.median(walls[1:] or walls) / eng.seg,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    for found in cells:
        name = found["cell"]["name"]
        ok = [r["rate"] for r in rows if r["workload"] == name
              and r["shed"] == 0 and r["fabric_drops"] == 0
              and r["backlog"][-1] <= max(r["backlog"][0], 0)]
        print(json.dumps({"workload": name, "knee_ops_per_tick": max(ok) if ok else None,
                          "elapsed_s": time.perf_counter() - t0}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark once and print its result line.

    python3 bench/run_cell.py --workload netcraq.ycsb_b --seed 7 --seconds 10 --trace 0

Runs on the chips of the machine it is started on.  Without a TPU, with
fewer chips than the cell asks for, or on a chip that ``bench/peaks.json``
does not list, it exits non-zero and prints no result.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each compared number beside its limit, repeated as the last
lines of standard error).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, so only a cell's first run in a checkout compiles.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    # every program of the run is cached, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.Refused as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

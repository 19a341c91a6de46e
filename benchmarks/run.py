"""Benchmark harness - one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and persists each benchmark's rows
as machine-readable ``BENCH_<name>.json`` (throughput, latency, packets
per reply, plus each row's structured ``data`` dict) so the performance
trajectory is recorded run over run - nightly CI uploads the JSON files
as artifacts.  Also includes the raw engine measurement (the only
wall-clock-measured quantity; everything else derives from exact simulator
counts + the calibrated network model - see benchmarks/common.py and
EXPERIMENTS.md §Benchmarks).
"""
from __future__ import annotations

from benchmarks import (fig3_read_qps, fig4_latency, fig5_mixed,
                        fig6_scalability, fig7_multichain, fig_chaos,
                        fig_failover, fig_hockey, fig_latency_tail,
                        fig_rebalance, fig_tick_cost, fig_txn,
                        fig_txn_pipeline)
from benchmarks.common import (BenchRow, measure_engine_us_per_query,
                               write_bench_json)
from repro.compile_cache import enable_compile_cache


def engine_rows() -> list[BenchRow]:
    rows = []
    for proto in ("netcraq", "netchain"):
        us = measure_engine_us_per_query(proto)
        rows.append(BenchRow(
            name=f"engine/{proto}_us_per_query",
            us_per_call=us,
            derived=f"measured on this host ({1e6 / us:,.0f} q/s/node)",
            data={"us_per_query": us, "qps_per_node": 1e6 / us},
        ))
    return rows


def failover_rows() -> list[BenchRow]:
    return fig_failover.run() + fig_failover.run(detection="reply_timeout")


BENCHMARKS = [
    ("engine", engine_rows),
    ("fig3_read_qps", fig3_read_qps.run),
    ("fig4_latency", fig4_latency.run),
    ("fig5_mixed", fig5_mixed.run),
    ("fig6_scalability", fig6_scalability.run),
    ("fig7_multichain", fig7_multichain.run),
    ("fig_failover", failover_rows),
    ("fig_txn", fig_txn.run),
    ("latency_tail", fig_latency_tail.run),
    ("txn_pipeline", fig_txn_pipeline.run),
    ("rebalance", fig_rebalance.run),
    ("tick_cost", fig_tick_cost.run),
    ("hockey", fig_hockey.run),
    ("chaos", fig_chaos.run),
]


def main() -> None:
    enable_compile_cache()
    all_rows: list[BenchRow] = []
    for name, runner in BENCHMARKS:
        rows = runner()
        write_bench_json(name, rows)
        all_rows += rows
    print("name,us_per_call,derived")
    for r in all_rows:
        print(r.csv())


if __name__ == "__main__":
    main()

"""Device milliseconds per tick of each named stage of the tick program.

The program runs every stage of its tick under a named scope
(``repro.core.stages``), which XLA keeps as the ``op_name`` of each
instruction.  A device trace names an operation only after its instruction
(``fusion.1265``), so the stage of an operation is read from the program
itself: the cell's ``_openloop_scan`` is lowered again on abstract values
with the driver's static arguments (``bench/drivers/openloop.py``: segment
ticks, arrival width ``C * n * lanes_per_node``, ``extra_ticks=0``, the
same donation), compiled, which after the run is a hit in JAX's persistent
cache, and its optimized text is mapped by ``op_stages``.  Nothing is
allocated on the device.

Against a program without the scopes every reader reports nothing.
"""
from __future__ import annotations

import bisect
import sys
import time
from collections import defaultdict

from bench import tracing
from bench.readers.tick_ms import ticks_and_seconds

UNSCOPED = None


def _stages_lib():
    try:
        from repro.core import stages
    except ImportError:  # a program from before the scopes
        return None
    return stages


def scan_lowering(cfg: dict, segment_ticks: int):
    """The cell's ``_openloop_scan`` lowered on abstract values, built as
    the open-loop driver builds its engine."""
    import jax

    from repro.core import ChainConfig, ChainSim, ClusterConfig, make_loadgen

    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=cfg["replicas"],
                          num_keys=cfg["keys"] // cfg["chains"],
                          num_versions=cfg["num_versions"],
                          value_words=cfg["value_words"],
                          protocol=cfg["protocol"]),
        n_chains=cfg["chains"],
    )
    C, n, lanes = cluster.n_chains, cluster.n_nodes, cfg["lanes_per_node"]
    route_cap = cfg["route_capacity"]
    sim = ChainSim(cluster, inject_capacity=lanes, route_capacity=route_cap,
                   reply_capacity=segment_ticks * n * lanes + n * route_cap)
    width = C * n * lanes
    state = jax.eval_shape(sim.init_state)
    gen = jax.eval_shape(lambda: make_loadgen(cluster, qps=0.0,
                                              backlog_capacity=width))
    return ChainSim._openloop_scan.lower(sim, state, gen, segment_ticks,
                                        width, 0)


def stage_map(view) -> dict | None:
    """``{instruction: stage}`` of the cell's scan program, built once per
    run and kept in ``view``; None where the program names no stage."""
    if "stage_map" not in view:
        view["stage_map"] = None
        lib = _stages_lib()
        if lib is not None:
            t0 = time.perf_counter()
            lowered = scan_lowering(view["config"], view["rec"]["ticks_per_segment"])
            smap = lib.op_stages(lowered.compile().as_text())
            print(f"stage map: {len(smap)} instructions in "
                  f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
            if any(smap.values()):
                view["stage_map"] = smap
    return view["stage_map"]


def _scan_ops(lines: dict, fn_name: str, lo: float, hi: float):
    """Op events clipped to the executions of program ``fn_name`` whose
    midpoint lies in [lo, hi], leaving out ops that enclose others."""
    execs = sorted((s, e) for _, s, e in tracing.module_events(lines, fn_name)
                   if lo <= 0.5 * (s + e) <= hi)
    ops = sorted((ev for ev in tracing.op_events(lines)
                  if not tracing.op_name(ev[0]).startswith(tracing.ENCLOSING)),
                 key=lambda ev: ev[1])
    starts = [ev[1] for ev in ops]
    out = []
    for s, e in execs:
        # the ops of an execution start inside it
        for i in range(bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)):
            n, os_, oe = ops[i]
            out.append((n, os_, min(oe, e)))
    return out


def stage_times(view) -> dict | None:
    """Device ms per tick of each stage (``None`` key: ops with no stage,
    or missing from the map) and the total, averaged over chips, over the
    ticks ``tick_ms`` counts; None where the program names no stage."""
    if "stage_times" in view:
        return view["stage_times"]
    view["stage_times"] = None
    smap = stage_map(view)
    ticks, _ = ticks_and_seconds(view)
    chips = tracing.device_planes(view["planes"])
    if smap is None or not ticks or not chips:
        return None
    lo, hi = view["summary"]["window"]
    ns: dict = defaultdict(float)
    for p in chips:
        for n, s, e in _scan_ops(view["planes"][p], view["rec"]["fn_name"], lo, hi):
            ns[smap.get(tracing.op_name(n), UNSCOPED)] += e - s
    per_tick = {k: v * 1e-6 / len(chips) / ticks for k, v in ns.items()}
    view["stage_times"] = {"stages": per_tick, "total": sum(per_tick.values())}
    return view["stage_times"]


def stage_ms(view, name: str) -> float | None:
    t = stage_times(view)
    return None if t is None else t["stages"].get(name, 0.0)


def unscoped_pct(view) -> float | None:
    t = stage_times(view)
    if t is None or t["total"] <= 0:
        return None
    return 100.0 * t["stages"].get(UNSCOPED, 0.0) / t["total"]

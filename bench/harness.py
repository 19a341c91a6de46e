"""Runs one cell of ``BENCHMARK.json`` once and builds its result line.

Everything that belongs to one cell is found by name: the configuration
(``configs[].file``, whose ``driver`` names ``bench/drivers/<driver>.py``),
the traffic mix (``bench/traffic/<traffic>.json``), the cell's offered
rate (``bench/cells/<cell>.json``) and each per-layer
metric's reader (``bench/readers/<metric>.py``).  A new cell or metric is
new files and entries, never an edit of this one.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
HOST_SPANS = ("dispatch", "wait", "fetch_replies")


class Refused(RuntimeError):
    """The run cannot be made here (no chip, too few chips, unknown chip)."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return {
        "spec": spec, "cell": cell,
        "config": _json(os.path.join(root, conf["file"])),
        "traffic": _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")),
        "rate": _json(os.path.join(BENCH, "cells", name + ".json")),
    }


def _module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Context:
    """What a driver gets: the cell's data, the seed and window length, and
    hooks that stamp set-up, the window and memory."""

    def __init__(self, found: dict, seed: int, seconds: float, trace: bool,
                 devices, t_start: float):
        self.config = found["config"]
        self.traffic = found["traffic"]
        self.ops_per_tick = found["rate"]["ops_per_tick"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.t_start = t_start
        self.t_warm = None
        self.compiles_in_window = 0
        self.compile_s = 0.0
        self.memory_stats = None
        self.trace_dir = None

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.compile_s += secs
            if self._in_window:
                self.compiles_in_window += 1

    def __enter__(self):
        import jax

        self._in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def warm(self, t: float) -> None:
        """Set-up ends: the first timed tick starts at host time ``t``."""
        self.t_warm = t

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax

        self._in_window = True
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
        try:
            with self.annotate("bench_window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            self._in_window = False

    def memory(self) -> None:
        stats = [d.memory_stats() or {} for d in self.devices]
        self.memory_stats = stats


def window_cells(cfg: dict) -> int | None:
    """Unacknowledged updates of one key that fill its version window, or
    None where the configuration drops no update."""
    return cfg["num_versions"] - 1 if cfg["full_window_drop"] else None


def wall_of(stamps, ticks):
    """Host seconds of tick boundaries, interpolated inside segments."""
    b = np.array([s[0] for s in stamps], np.float64)
    w = np.array([s[1] for s in stamps], np.float64)
    return np.interp(np.asarray(ticks, np.float64), b, w)


def latencies(rec: dict, ok: np.ndarray) -> dict:
    """Per-reply latency of the success replies answered in the window."""
    t0, t1 = rec["window_ticks"]
    r = rec["replies"]
    done = r["t_done"]
    sel = ok & (done > t0) & (done <= t1)
    lat = wall_of(rec["stamps"], done[sel]) - wall_of(rec["stamps"], r["t_inject"][sel])
    is_write = r["op"][sel] == 5
    return {"sel": sel, "latency_s": lat, "is_write": is_write,
            "ticks": (done[sel] - r["t_inject"][sel]),
            "hops": r["hops"][sel],
            "unstamped": int((r["t_inject"][sel] < rec["stamps"][0][0]).sum())}


def end_to_end(rec: dict, lat: dict, ctx: Context) -> dict:
    """The cell's end-to-end metrics; a latency with no sample to take it
    from (a run that answered nothing) is left out."""
    t0, t1 = rec["window_ticks"]
    w0, w1 = wall_of(rec["stamps"], [t0, t1])
    ms = lat["latency_s"] * 1e3
    out = {"ops_per_s": {"value": float(lat["sel"].sum() / (w1 - w0)), "unit": "ops/s"}}
    for name, sample, q in (("p50_latency_ms", ms, 50), ("p99_latency_ms", ms, 99),
                            ("write_p99_latency_ms", ms[lat["is_write"]], 99)):
        if sample.size:
            out[name] = {"value": float(np.percentile(sample, q)), "unit": "ms"}
    out["setup_s"] = {"value": float(ctx.t_warm - ctx.t_start), "unit": "s"}
    return out


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        found: dict | None = None, require_tpu: bool = True, log=None) -> dict:
    """One run of cell ``name``.  Raises ``Refused`` before any work where
    the machine cannot run it.  ``found`` replaces the cell's files (tests
    run a cell at a size the CPU can hold)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    found = found or load_cell(name)
    peaks = _json(os.path.join(BENCH, "peaks.json"))["devices"]

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {dev.platform!r})")
    chips = found["cell"]["chips"]
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}")
    if require_tpu and dev.device_kind not in peaks:
        raise Refused(f"device kind {dev.device_kind!r} is not in bench/peaks.json")
    devices = devices[:chips]
    peak = peaks.get(dev.device_kind)

    driver = _module("drivers", found["config"]["driver"])
    with Context(found, seed, seconds, trace, devices, t_start) as ctx:
        rec = driver.run(ctx)
    log(json.dumps({"compiles_in_window": ctx.compiles_in_window,
                    "compile_s": ctx.compile_s,
                    "peak_bytes_reserved": [m.get("peak_bytes_reserved")
                                            for m in ctx.memory_stats],
                    "peak_bytes_in_use": [m.get("peak_bytes_in_use")
                                          for m in ctx.memory_stats],
                    "counters": rec["counters"]}))

    from bench import oracle

    cfg = found["config"]
    res = oracle.evaluate(rec["ops"], rec["replies"], rec["load"], rec["final"],
                          rec["counters"], rec["window_ticks"],
                          window_cells(cfg), cfg["replicas"])
    correct, table = oracle.check(res["numbers"])
    lat = latencies(rec, res["ok"])
    mem = [m.get("peak_bytes_reserved", m.get("peak_bytes_in_use", 0)) or 0
           for m in ctx.memory_stats]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(max(mem))}
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"]}
    if not trace:
        out["metrics"] = end_to_end(rec, lat, ctx)
    else:
        from bench import tracing

        planes = tracing.load(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        summary = tracing.summarize(planes, host_spans=HOST_SPANS)
        view = {"rec": rec, "lat": lat, "planes": planes, "summary": summary,
                "peak": peak, "config": found["config"]}
        metrics = {}
        for m in found["spec"]["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            value = _module("readers", m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in summary["device_ops"]],
                            "idle_gaps": [list(x) for x in summary["idle_gaps"]]}
    out["device"] = device
    ticks = lat["ticks"]
    log(json.dumps({"reads_checked": res["reads_checked"],
                    "writes_acked": res["writes_acked"],
                    "lane_wait_ticks": res["lane_wait"],
                    "window_ops": int(ticks.size),
                    "window_writes": int(lat["is_write"].sum()),
                    "latency_unstamped": lat["unstamped"],
                    "window_ticks": list(rec["window_ticks"]),
                    "ticks_cdf": {int(k): float((ticks <= k).mean())
                                  for k in np.unique(ticks)[:12]} if ticks.size else {}}))
    out["checks"] = table
    for k, v in table.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return out

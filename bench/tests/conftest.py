import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import time  # noqa: E402


def small_cell(name: str, **config) -> dict:
    """The cell's own files with the configuration shrunk to a size the CPU
    runs in seconds; everything else (protocol, traffic, limits) as
    committed."""
    from bench import harness

    found = harness.load_cell(name)
    cfg = found["config"]
    cfg.update(chains=2, keys=2 * 512, lanes_per_node=16, route_capacity=64)
    found["traffic"]["segment_ticks"] = 16
    found["rate"]["ops_per_tick"] = 24.0
    traffic = config.pop("traffic", {})
    found["traffic"].update(traffic)
    cfg.update(config)
    return found


def run_small(name: str, seconds: float = 2.0, trace: bool = False,
              seed: int = (1 << 33) + 17, **config) -> dict:
    from bench import harness

    return harness.run(name, seed, seconds, trace, time.perf_counter(),
                       found=small_cell(name, **config), require_tpu=False,
                       log=lambda msg: None)


"""Device milliseconds per tick of each named stage of the four-chip scan,
``ChainDist.run_openloop`` (``bench/drivers/dist4.py``).

``bench/stages.py`` maps a trace's ops to stages through the optimized
text of the one-chip scan.  This helper builds the same map from the
four-chip scan, lowered again on abstract values placed on the run's mesh
(the record's ``lower_scan``) and compiled, which after the run is a hit
in JAX's persistent cache; the per-stage arithmetic is ``bench/stages.py``'s,
averaged over the cell's chips.  Nothing is allocated on the device.
"""
from __future__ import annotations

import sys
import time

from bench import stages


def stage_ms(view, name: str) -> float | None:
    """Device ms per tick of stage ``name`` in the four-chip scan; None
    where the run has no four-chip scan or its program names no stage."""
    if "stage_map" not in view:
        view["stage_map"] = None
        lib = stages._stages_lib()
        lower = view["rec"].get("lower_scan")
        if lib is not None and lower is not None:
            t0 = time.perf_counter()
            smap = lib.op_stages(lower().compile().as_text())
            print(f"stage map: {len(smap)} instructions in "
                  f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
            if name in smap.values():
                view["stage_map"] = smap
    return stages.stage_ms(view, name)

"""The per-stage readers on hand-made traces and stage maps, and the stage
map's lowering against the driver's own."""
import pytest

from bench import harness, stages

STAGE_READERS = ("gen_ms", "ingress_ms", "node_step_ms", "store_ms",
                 "fabric_ms", "reply_log_ms", "telemetry_ms", "counters_ms")


def _view(stage_map):
    planes = {
        "/host:CPU": {"python": [("bench_window", 0, 1000)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit__openloop_scan(7)", 10, 110),
                            ("jit__openloop_scan(7)", 200, 300),
                            ("jit_other(3)", 120, 190),
                            ("jit__openloop_scan(7)", 990, 1100)],
            "XLA Ops": [("%while.4 = (s32[]) while(...)", 10, 300),
                        ("%fusion.1 = s32[4]{0} fusion(...)", 10, 60),
                        ("%fusion.2 = s32[4]{0} fusion(...)", 60, 110),
                        ("%fusion.9 = s32[4]{0} fusion(...)", 150, 180),
                        ("%fusion.3 = s32[4]{0} fusion(...)", 200, 230),
                        ("%fusion.4 = s32[4]{0} fusion(...)", 230, 290),
                        ("%fusion.5 = s32[4]{0} fusion(...)", 290, 320),
                        ("%fusion.1 = s32[4]{0} fusion(...)", 995, 1050)],
        },
        "/device:CUSTOM:Megascale Trace": {"XLA Ops": []},
        "/device:TPU:1": {
            "XLA Modules": [("jit__openloop_scan(7)", 20, 80),
                            ("jit__openloop_scan(7)", 210, 290)],
            "XLA Ops": [("fusion.1", 20, 50), ("fusion.2", 50, 80),
                        ("fusion.4", 210, 290)],
        },
    }
    view = {"planes": planes, "summary": {"window": (0, 1000)},
            "rec": {"fn_name": "_openloop_scan", "ticks_per_segment": 2}}
    view["stage_map"] = stage_map
    return view


MAP = {"fusion.1": "gen", "fusion.2": "store", "fusion.4": None,
       "fusion.5": "store", "fusion.9": "fabric"}


def _read(name, view):
    return harness._module("readers", name).read(view)


def test_stage_readers_on_two_chips():
    view = _view(dict(MAP))
    # chip 0 runs two scan executions in the window (the third ends past
    # it, the while encloses the rest), chip 1 two: 4 ticks.  Ops between
    # executions (fusion.9) do not count; fusion.5 is clipped at its
    # execution's end; fusion.3 is not in the map.
    # gen (50 + 30) / 2, store (50 + 10 + 30) / 2, unscoped (30 + 60 + 80) / 2
    per_tick_ms = lambda ns: ns * 1e-6 / 4
    assert _read("gen_ms", view) == pytest.approx(per_tick_ms(40))
    assert _read("store_ms", view) == pytest.approx(per_tick_ms(45))
    assert _read("fabric_ms", view) == 0.0
    for name in set(STAGE_READERS) - {"gen_ms", "store_ms"}:
        assert _read(name, view) == 0.0
    assert _read("unscoped_pct", view) == pytest.approx(100 * 85 / 170)
    # the stages and the unscoped time add up to the scan's device time
    t = stages.stage_times(view)
    assert t["total"] == pytest.approx(per_tick_ms((200 + 140) / 2))


def test_readers_report_nothing_without_scopes():
    view = _view(None)           # a program that names no stage
    for name in STAGE_READERS + ("unscoped_pct",):
        assert _read(name, view) is None


def test_readers_report_nothing_without_the_scan():
    view = _view(dict(MAP))
    view["rec"] = {"fn_name": "_missing", "ticks_per_segment": 2}
    for name in STAGE_READERS + ("unscoped_pct",):
        assert _read(name, view) is None


def test_stage_map_lowers_the_drivers_program():
    """The map's abstract lowering is the program the driver runs: the
    same text as lowering the driver's engine with its own arrays."""
    from bench.drivers import openloop
    from bench.tests.conftest import small_cell
    from repro.core import ChainSim

    found = small_cell("netcraq.ycsb_b")
    eng = openloop.Engine(found["config"], found["traffic"], 24.0, 3 << 31)
    ours = stages.scan_lowering(found["config"], eng.seg).as_text()
    theirs = ChainSim._openloop_scan.lower(
        eng.sim, eng.state, eng.gen, eng.seg, eng.width, 0).as_text()
    assert ours == theirs
    view = {"config": found["config"], "rec": {"ticks_per_segment": eng.seg}}
    smap = stages.stage_map(view)
    assert {"gen", "ingress", "node_step", "store", "fabric", "reply_log",
            "telemetry", "counters"} <= set(smap.values())
    assert stages.stage_map(view) is smap         # built once per run

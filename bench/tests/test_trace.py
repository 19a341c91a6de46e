"""The trace reduction and the latency interpolation, on a trace recorded
on the CPU and on hand-made intervals."""
import numpy as np
import pytest

from bench import harness, tracing


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((128, 128), jnp.float32)
    work(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                x = work(x)
            with jax.profiler.TraceAnnotation("wait"):
                x.block_until_ready()
    jax.profiler.stop_trace()
    return tracing.load(d)


def _as_device(planes):
    """The CPU trace with its XLA thread events moved onto one fake device
    plane, shaped as a TPU trace is (``XLA Ops`` and ``XLA Modules``)."""
    ops = []
    for lines in planes.values():
        for name, evs in lines.items():
            if name.startswith("tf_XLA"):
                ops.extend(ev for ev in evs if not ev[0].startswith(
                    ("Thread", "Slinky", "end:", "ThunkExecutor")) and ev[2] > ev[1])
    lo = min(ev[1] for ev in ops)
    hi = max(ev[2] for ev in ops)
    out = {k: v for k, v in planes.items() if k.startswith("/host:")}
    out["/device:TPU:0"] = {"XLA Ops": ops,
                            "XLA Modules": [("jit_work(1)", lo, hi)]}
    return out


def test_recorded_trace_has_window_and_work(cpu_trace):
    span = tracing.host_span(cpu_trace, "bench_window")
    assert span is not None and span[1] > span[0]
    spans = tracing.host_annotations(cpu_trace, ("dispatch", "wait"))
    assert len(spans) == 6
    planes = _as_device(cpu_trace)
    s = tracing.summarize(planes, host_spans=("dispatch", "wait"))
    assert s["chips"] == ["/device:TPU:0"]
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["device_ops"] and all(t > 0 for _, t in s["device_ops"])
    idle = sum(t for _, t in s["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)


def test_busy_is_the_union_of_intervals():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert tracing.union(evs) == [(0, 15), (20, 30)]
    assert tracing.union(tracing.clip(evs, 8, 22)) == [(8, 15), (20, 22)]
    assert tracing.gaps(tracing.union(evs), 0, 40) == [(15, 20), (30, 40)]


def test_program_time():
    planes = {
        "/host:CPU": {"python": [("bench_window", 0, 1000)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit__lambda(7)", 10, 110), ("jit__lambda(7)", 200, 300),
                            ("jit_other(3)", 400, 500), ("jit__lambda(7)", 990, 1100)],
            "XLA Ops": [("%while.4 = (s32[]) while(...)", 10, 300),
                        ("%fusion.1 = s32[4]{0} fusion(...)", 10, 60),
                        ("%all-gather.2 = s32[4]{0} all-gather(...)", 60, 110),
                        ("%collective-permute-done.1 = s32[4]{0} ...", 200, 230),
                        ("%fusion.3 = s32[4]{0} fusion(...)", 230, 300)],
        },
        "/device:CUSTOM:Megascale Trace": {"XLA Ops": []},
        "/device:TPU:1": {
            "XLA Modules": [("jit__lambda(7)", 20, 80), ("jit__lambda(7)", 210, 290)],
            "XLA Ops": [("all-gather.2", 20, 40)],
        },
    }
    n, secs = tracing.program_time(planes, "_lambda", 0, 1000)
    assert n == 2                                   # the last one runs past the window
    assert secs == pytest.approx((200 + 140) / 2 * 1e-9)
    s = tracing.summarize(planes)
    assert s["chips"] == ["/device:TPU:0", "/device:TPU:1"]
    assert s["busy_s"] == pytest.approx((290 + 20) / 2 * 1e-9)   # ops, not modules
    # the enclosing while is not counted twice, names are trimmed
    assert dict(s["device_ops"]) == pytest.approx(
        {"fusion.1": 25e-9, "all-gather.2": 35e-9, "fusion.3": 35e-9,
         "collective-permute-done.1": 15e-9})


def test_latency_interpolates_inside_segments():
    # segments of 4 ticks; the host stamps each segment's end
    stamps = [(4, 1.0), (8, 2.0), (12, 3.5)]
    assert harness.wall_of(stamps, [4, 6, 8, 10, 12]).tolist() == [1.0, 1.5, 2.0, 2.75, 3.5]
    rec = {"window_ticks": (4, 12), "stamps": stamps,
           "replies": {"t_done": np.array([5, 9, 12, 13]),
                       "t_inject": np.array([4, 7, 8, 12]),
                       "op": np.array([4, 5, 4, 4]), "hops": np.array([2, 5, 3, 2])}}
    ok = np.array([True, True, True, True])
    lat = harness.latencies(rec, ok)
    assert lat["sel"].tolist() == [True, True, True, False]    # 13 is after the window
    # an op that spans a segment boundary takes a share of both segments
    assert lat["latency_s"] == pytest.approx([0.25, 2.375 - 1.75, 1.5])
    assert lat["is_write"].tolist() == [False, True, False]
    assert lat["ticks"].tolist() == [1, 2, 4]

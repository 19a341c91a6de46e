"""The tick's named stages: the HLO parser on a toy program and on a
hand-written module, stage coverage of the compiled open-loop scan, and
that the scopes leave the compiled program as it is."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import ChainConfig, ChainSim, ClusterConfig, make_loadgen
from repro.core import stages
from repro.core.stages import stage
from tests.helpers import hlo_instruction_lines, stages_off

# instructions that move no data: no scope can name them and the device
# spends no time on them
FREE = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\(")


def test_stage_accepts_only_tick_stages():
    with stage("store"):
        pass
    with pytest.raises(ValueError):
        stage("stores")


@pytest.mark.parametrize("path, want", [
    ("jit(f)/while/body/closed_call/jit(tick)/vmap(node_step)/vmap(store)/add",
     "store"),
    ("jit(f)/while/body/closed_call/jit(tick)/vmap(node_step)/mul", "node_step"),
    ("jit(f)/while/body/closed_call/gen/jit(_uniform)/add", "gen"),
    ("jit(f)/vmap(vmap(fabric))/jit(cumsum)/reduce_window_sum", "fabric"),
    ("jit(f)/while/body/closed_call/jit(tick)/vmap()/add", None),
    ("jit(store_fn)/add", None),
    ("", None),
])
def test_path_stage(path, want):
    assert stages.path_stage(path) == want


def _toy_text() -> str:
    def chain(x):
        with stage("node_step"):
            y = jnp.sin(x) * 3.0
            with stage("store"):
                z = jnp.cumsum(y) + y.sum()
        return y * z

    @functools.partial(jax.jit, donate_argnums=0)
    def toy_scan(x):
        def body(c, _):
            with stage("gen"):
                c = c + jnp.cos(c)
            return jax.vmap(chain)(c), None

        return jax.lax.scan(body, x, None, length=3)[0]

    return toy_scan.lower(jnp.ones((4, 16))).compile().as_text()


def test_op_stages_on_a_toy_scan():
    text = _toy_text()
    smap = stages.op_stages(text)
    own = {}
    for line in text.splitlines():
        m = _OPCODE.match(line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op:
            own[m.group(1)] = op.group(1)
    # the innermost stage wins, under vmap as well
    in_store = [n for n, p in own.items() if "/vmap(node_step)/store/" in p]
    in_step = [n for n, p in own.items()
               if "/vmap(node_step)/" in p and "/store/" not in p]
    assert in_store and all(smap[n] == "store" for n in in_store)
    assert in_step and all(smap[n] == "node_step" for n in in_step)
    assert any(s == "gen" for s in smap.values())
    # every instruction with a stage in its own path takes that stage
    for n, p in own.items():
        if stages.path_stage(p) is not None:
            assert smap[n] == stages.path_stage(p)


HAND_MODULE = """\
HloModule m, is_scheduled=true

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/vmap(store)/add"}
  %mul.1 = s32[8]{0} multiply(%add.1, %add.1), metadata={op_name="jit(f)/vmap(store)/mul"}
  ROOT %sub.1 = s32[8]{0} subtract(%mul.1, %param_0), metadata={op_name="jit(f)/fabric/sub"}
}

%fused_computation.2 (param_0.2: s32[8]) -> s32[8] {
  %param_0.2 = s32[8]{0} parameter(0)
  ROOT %neg.2 = s32[8]{0} negate(%param_0.2)
}

ENTRY %main (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/vmap()/sub"}
  %fusion.1 = s32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/counters/sub"}
  %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.4 = s32[8]{0} copy(%p)
  %reshape.5 = s32[8]{0} reshape(%copy.4)
  %fusion.6 = s32[8]{0} fusion(%reshape.5), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/store/sub"}
  %negate.7 = s32[8]{0} negate(%p), metadata={op_name="jit(f)/jit(tick)/neg"}
  %add.8 = s32[8]{0} add(%negate.7, %fusion.6), metadata={op_name="jit(f)/fabric/add"}
  ROOT %copy.3 = s32[8]{0} copy(%fusion.2)
}
"""


def test_op_stages_fusion_majority_users_and_leftovers():
    smap = stages.op_stages(HAND_MODULE)
    # no stage in its own op_name: the stage most fused instructions name
    assert smap["fusion"] == "store"
    # its own op_name names a stage: that one
    assert smap["fusion.1"] == "counters"
    # made by XLA (no op_name): the stage of the instructions using it,
    # through a chain of such instructions
    assert smap["copy.4"] == "store" and smap["reshape.5"] == "store"
    # an op_name that names no stage keeps none, whatever uses it
    assert smap["negate.7"] is None
    # nothing names a stage: none
    assert smap["fusion.2"] is None and smap["copy.3"] is None
    assert smap["add.1"] == "store" and smap["sub.1"] == "fabric"


def _scan_text(protocol: str) -> str:
    """The one-chip open-loop scan at test size, compiled for this host."""
    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=4, num_keys=512, num_versions=4,
                          value_words=4, protocol=protocol),
        n_chains=2,
    )
    sim = ChainSim(cluster, inject_capacity=8, route_capacity=32,
                   reply_capacity=512)
    state = jax.eval_shape(sim.init_state)
    gen = jax.eval_shape(lambda: make_loadgen(
        cluster, qps=16.0, write_fraction=0.05, backlog_capacity=64))
    return ChainSim._openloop_scan.lower(
        sim, state, gen, 4, sim.C * sim.n * sim.c_in, 0).compile().as_text()


@pytest.fixture(scope="module", params=["netcraq", "netchain"])
def scan(request):
    return request.param, _scan_text(request.param)


def _scan_body(text: str) -> dict[str, str]:
    """``{instruction: opcode}`` of the scan's while body (the while of the
    entry computation)."""
    entry = text[text.index("\nENTRY"):]
    body = re.search(r"\bbody=%?([\w.\-]+)", entry).group(1)
    names = {ins.name for ins in stages.computations(text)[body]}
    out = {}
    for line in text.splitlines():
        m = _OPCODE.match(line)
        if m and m.group(1) in names:
            out[m.group(1)] = m.group(2)
    return out


def test_scan_stage_coverage(scan):
    protocol, text = scan
    smap = stages.op_stages(text)
    assert set(smap.values()) - {None} == set(stages.STAGES) - {"wave"}, protocol
    work = [n for n, op in _scan_body(text).items() if op not in FREE]
    unscoped = [n for n in work if smap[n] is None]
    assert work and len(unscoped) <= 0.1 * len(work), (protocol, unscoped)


def test_scopes_leave_the_compiled_scan_unchanged(scan):
    protocol, text = scan
    with stages_off():
        plain = _scan_text(protocol)
    assert "vmap(store)" in text and "vmap(store)" not in plain
    assert hlo_instruction_lines(text) == hlo_instruction_lines(plain)

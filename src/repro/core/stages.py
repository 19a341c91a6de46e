"""The stages of the served tick, by name, and where each instruction of a
compiled tick program belongs.

Every stage of ``ChainSim.run_openloop``'s scan runs under ``stage(name)``,
a ``jax.named_scope`` whose name is one of ``STAGES``:

    gen        the open-loop generator and its offered/shed counters
    ingress    entry stamps, dead-node masks, inbound lanes, stale-route
               admission, lease expiry and the head's lock stage
    node_step  the vmapped CRAQ / NetChain node step, apart from its store
    store      the store calls the node steps make (``core/store.py``)
    fabric     outbound lanes, fabric masks, hop accounting, the router
               and the packet sums
    reply_log  the exit mask and ``ReplyLog.append``
    telemetry  latency histogram, packet traces and the flight-recorder row
    counters   conflict heat and the per-tick ``Metrics``
    wave       the wave coordinator and its two cluster routes

A scope changes nothing but the ``op_name`` metadata XLA keeps on each
instruction, so the optimized program is the same with or without it.
``op_stages`` reads that metadata back from an optimized HLO module's text:
a device trace names each operation after its instruction, and the map
turns those names into stages.
"""
from __future__ import annotations

import collections
import re
from typing import NamedTuple

import jax

STAGES = ("gen", "ingress", "node_step", "store", "fabric", "reply_log",
          "telemetry", "counters", "wave")


def stage(name: str):
    """The named scope of tick stage ``name`` (one of ``STAGES``)."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not a tick stage {STAGES}")
    return jax.named_scope(name)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


class Instr(NamedTuple):
    name: str
    stage: str | None        # the stage its own op_name names
    has_op_name: bool        # False: XLA made it (a copy, a layout change)
    calls: list[str]         # computations it calls (a fusion's body)
    refs: list[str]          # names it refers to (its operands among them)


def path_stage(op_name: str) -> str | None:
    """The innermost stage an ``op_name`` path names.  A component names a
    stage when it is the stage's name, or the name under transformations
    (``vmap(node_step)``)."""
    for part in reversed(op_name.split("/")):
        while part not in STAGES:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        else:
            return part
    return None


def computations(hlo_text: str) -> dict[str, list[Instr]]:
    """Each computation of an HLO module's text as the list of its
    instructions, in the order the text gives them."""
    comps: dict[str, list[Instr]] = {}
    cur: list | None = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            head = line.strip()
            if head.endswith("{"):
                name = head.removeprefix("ENTRY ").split(" ", 1)[0].lstrip("%")
                cur = comps.setdefault(name, [])
            continue
        if cur is None:
            continue
        op = _OP_NAME.search(line)
        cur.append(Instr(
            m.group(1), path_stage(op.group(1)) if op else None, op is not None,
            _CALLS.findall(line), _REF.findall(m.group(2))))
    return comps


def op_stages(hlo_text: str) -> dict[str, str | None]:
    """``{instruction: stage}`` for every instruction of an optimized HLO
    module.  An instruction takes the innermost stage its own ``op_name``
    names; a fusion whose ``op_name`` names none takes the stage most of
    its fused instructions name; an instruction XLA made itself, with no
    ``op_name`` (a layout copy or reshape of the store, a piece of a split
    cumulative sum), takes the stage most of the instructions that use its
    result take; anything left has none (``None``)."""
    comps = computations(hlo_text)
    out: dict[str, str | None] = {}
    memo: dict[str, collections.Counter] = {}

    def named(comp: str) -> collections.Counter:
        """Stages of a called computation's instructions, nested calls
        counted through."""
        if comp not in memo:
            memo[comp] = count = collections.Counter()
            for ins in comps.get(comp, []):
                if ins.stage is not None:
                    count[ins.stage] += 1
                for c in ins.calls:
                    count.update(named(c))
        return memo[comp]

    def majority(count: collections.Counter) -> str | None:
        return count.most_common(1)[0][0] if count else None

    for instrs in comps.values():
        for ins in instrs:
            st = ins.stage
            if st is None and ins.calls:
                st = majority(sum((named(c) for c in ins.calls),
                                  collections.Counter()))
            out[ins.name] = st
    for instrs in comps.values():
        here = {ins.name for ins in instrs}
        users = collections.defaultdict(list)
        for ins in instrs:
            for r in set(ins.refs) & here:
                users[r].append(ins.name)
        # the text lists a computation's instructions with every operand
        # before its users: walking it backwards settles users first
        for ins in reversed(instrs):
            if out[ins.name] is None and not ins.has_op_name:
                out[ins.name] = majority(collections.Counter(
                    out[u] for u in users[ins.name] if out[u] is not None))
    return out

"""Faults planted under the timed path: with any of them in place a run
must come out not correct.  Each is a context manager that patches the
program for the runs made inside it.

* ``unchanged``: the tick returns its state unchanged.
* ``half_batch``: every node processes only half of its inbox.
* ``altered_reads``: every read reply's value is changed where it is made.
* ``short_window``: the store keeps 2 version cells, not the configured
  number, so a NetCRAQ head drops updates the stated window would take.

``bench/tests/test_faults.py`` runs each at a size the CPU holds;
``bench/control.py --fault <name>`` runs one at the cell's own size on the
chip.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _node_steps(wrap):
    from repro.core import chain

    steps = dict(chain.NODE_STEPS)
    return _patched(chain, "NODE_STEPS", {k: wrap(v) for k, v in steps.items()})


def unchanged():
    from repro.core.chain import ChainSim

    return _patched(ChainSim, "tick", lambda self, state, injected: state)


def half_batch():
    import jax.numpy as jnp

    def wrap(node_step):
        def step(cfg, store, roles, inbox, dense_rank=False):
            keep = jnp.arange(inbox.op.shape[0]) % 2 == 0
            return node_step(cfg, store, roles, inbox.mask(keep), dense_rank=dense_rank)
        return step
    return _node_steps(wrap)


def altered_reads():
    import jax.numpy as jnp

    from bench.ycsb import OP_READ_REPLY

    def wrap(node_step):
        def step(cfg, store, roles, inbox, dense_rank=False):
            st, out = node_step(cfg, store, roles, inbox, dense_rank=dense_rank)
            bump = (out.op == OP_READ_REPLY).astype(jnp.int32)
            return st, out._replace(value=out.value.at[:, 0].add(bump))
        return step
    return _node_steps(wrap)


def short_window(cells: int = 2):
    import repro.core as core

    orig = core.ChainConfig
    return _patched(core, "ChainConfig",
                    lambda **kw: orig(**{**kw, "num_versions": cells}))


FAULTS = {"none": contextlib.nullcontext, "unchanged": unchanged,
          "half_batch": half_batch, "altered_reads": altered_reads,
          "short_window": short_window}

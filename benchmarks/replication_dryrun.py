"""Hillclimb C - the paper's technique on TPU serving, quantified.

Lowers one decode step of chain-replicated KV-cache serving on a
(chain=4, data=4, model=16) mesh under both protocols and parses the
collective bytes of the replication traffic:

* NetCRAQ: committed pages are clean -> attention reads are LOCAL; the
  only chain traffic is the one-token page ppermute + the ack psum.
* NetChain: the tail is the only authoritative copy -> every step
  broadcasts the tail's page window to the readers (modeled as the
  tail-masked psum over the chain axis).

This is the paper's Fig 3/6 asymmetry reproduced as HLO bytes on the
production interconnect.  Run with 512 emulated devices:

    PYTHONPATH=src python -m benchmarks.replication_dryrun
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import json

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.roofline.analysis import ICI_BW, parse_collective_bytes
from repro.serve import kv_cache as KV

CHAIN = 4


def build(protocol: str, cfg, *, batch=32, page_tokens=1):
    """One replication step for one decode token across all layers."""
    L, KVh, D = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim

    def step(kv_new, seq_no, cache_page):
        if protocol == "netcraq":
            own, replica, ack = KV.netcraq_append(
                kv_new, seq_no, axis="chain", n=CHAIN)
            # reads are LOCAL: the attention consumes `own` + local cache
            return own, replica, ack
        fetched = KV.netchain_read(cache_page, axis="chain", n=CHAIN)
        committed, ack = KV.netchain_append(
            kv_new, seq_no, axis="chain", n=CHAIN)
        return fetched, committed, ack

    # per-replica shapes: new page [L, B, page, KV, D] (k and v), the read
    # window the tail must serve under CR = the page the readers need
    kv_new = jax.ShapeDtypeStruct(
        (CHAIN, L, batch, page_tokens, KVh, D), jnp.bfloat16)
    seq_no = jax.ShapeDtypeStruct((CHAIN,), jnp.int32)
    # CR read window: the most recent 128-token page span per sequence
    window = jax.ShapeDtypeStruct(
        (CHAIN, L, batch, 128, KVh, D), jnp.bfloat16)
    mesh = jax.make_mesh((CHAIN, 4, 16), ("chain", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    spec = P("chain")
    f = jax.jit(
        jax.shard_map(
            step, mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec, spec),
        )
    )
    lowered = f.lower(kv_new, seq_no, window)
    compiled = lowered.compile()
    return parse_collective_bytes(compiled.as_text())


def main():
    cfg = get_config("qwen2.5-3b")
    out = {}
    for proto in ("netcraq", "netchain"):
        coll = build(proto, cfg)
        out[proto] = coll["total"]
        print(f"{proto:9s}: replication collective bytes/step = "
              f"{coll['total'] / 1e6:10.3f} MB "
              f"({ {k: round(v / 1e6, 3) for k, v in coll.items() if k not in ('total', 'counts') and v} })")
    ratio = out["netchain"] / max(out["netcraq"], 1)
    print(f"\nread-path traffic amplification (CR vs CRAQ): {ratio:,.1f}x")
    print(f"per-step chain overhead at {ICI_BW / 1e9:.0f} GB/s/link: "
          f"CRAQ {out['netcraq'] / ICI_BW * 1e6:.1f} us vs "
          f"CR {out['netchain'] / ICI_BW * 1e6:.1f} us")
    with open("roofline_out3/replication_compare.json", "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()

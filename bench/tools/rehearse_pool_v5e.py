"""Compile a serve cell's slot-pool programs for a TPU v5e without the
chip: the decode step, the insert and the longest prefill, with what
``memory_analysis()`` says one chip must hold, and the largest ``copy``
ops of the step's HLO (a copy of pool size means the pool is not updated
in place).

    JAX_PLATFORMS=cpu python bench/tools/rehearse_pool_v5e.py <workload> ...

Any serve cell, whatever its kind (``serve``, ``serve_hybrid``): the kind
module's ``model_config`` builds the program's config where it has one.
The engine is ``rehearse_v5e``'s, with weights and pool as shapes only.
Nothing runs: bytes and compile success, never a time.  One JSON line per
cell.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "s8": 1, "u32": 4, "pred": 1,
               "f16": 2, "s16": 2, "u8": 1}
COPY = re.compile(r"=\s*(\w+)\[([\d,]*)\][^ ]*\s+copy(?:-start)?\(")


def copies(hlo: str, n: int = 3):
    """The ``n`` largest copies in an HLO text: [bytes, shape]."""
    out = []
    for m in COPY.finditer(hlo):
        dt, dims = m.group(1), m.group(2)
        size = DTYPE_BYTES.get(dt, 4)
        for d in filter(None, dims.split(",")):
            size *= int(d)
        out.append([size, f"{dt}[{dims}]"])
    return sorted(out, reverse=True)[:n]


def rehearse(workload: str, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from bench.harness import core

    tool = core.load_module(ROOT / "bench" / "tools" / "rehearse_v5e.py",
                            "bench_tool_rehearse_v5e")
    bench = core.load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    config = core.load_json(core.find((core.BENCH,), "configs",
                                      f"{wl['config']}.json"))
    traffic = core.load_json(core.find((core.BENCH,), "traffic",
                                       f"{wl['traffic']}.json"))
    kind = core.load_kind(traffic["kind"])
    if hasattr(kind, "model_config"):
        cfg = kind.model_config(config)
    else:
        from repro.configs.base import ModelConfig
        cfg = ModelConfig(**config["model"])
    n_slots, cache_len = traffic["n_slots"], traffic["cache_len"]
    prefill_len = max(traffic["prompt"]["grid"])
    _, engine = tool.shapes_engine(cfg, n_slots, cache_len)
    sh = SingleDeviceSharding(device)

    def on(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree)
    params, pool = on(engine.params), on(engine._pool)
    batch = {"tokens": jax.ShapeDtypeStruct((1, prefill_len), jnp.int32,
                                            sharding=sh)}
    prefill = engine._prefill_fn(prefill_len)
    one = on(jax.eval_shape(prefill, params, batch)[1])
    toks = jax.ShapeDtypeStruct((n_slots, 1, 1), jnp.int32, sharding=sh)
    poss = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=sh)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
    out = {"workload": workload, "pool_path": engine.pool_path,
           "n_slots": n_slots, "cache_len": cache_len,
           "prefill_len": prefill_len,
           "pool_bytes": sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(pool)),
           "slot_state_bytes": engine.slot_state_bytes,
           "slot_kv_bytes": engine.slot_kv_bytes}
    for name, fn, args in (("decode_step", engine._step,
                            (params, pool, toks, poss)),
                           ("insert", engine._insert, (pool, one, idx)),
                           ("prefill", prefill, (params, batch))):
        try:
            compiled = fn.lower(*args).compile()
        except Exception as e:      # the chip's compiler refuses it
            out[name] = {"error": str(e).splitlines()[0][:300]}
            continue
        ma = compiled.memory_analysis()
        out[name] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "total_bytes": (ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.temp_size_in_bytes
                            - ma.alias_size_in_bytes),
            "largest_copies": copies(compiled.as_text())}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workloads", nargs="+")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for w in args.workloads:
        print(json.dumps(rehearse(w, topo.devices[0])), flush=True)


if __name__ == "__main__":
    main()

"""Compile each serve cell's slot pool for a TPU v5e without the chip, and
print what ``memory_analysis()`` says one chip must hold.

    JAX_PLATFORMS=cpu python bench/tools/rehearse_v5e.py [<workload> ...]
        [--pool SLOTSxCACHE ...]

For each serve cell (or each ``--pool`` size of the first one named), it
lowers, for one described v5e chip, the two programs that hold the pool:
the engine's decode step over the whole pool and its longest prefill.  The
decode step is the engine's (``ContinuousBatcher``'s jitted step, taken
from an engine whose pool and weights are shapes only).  Nothing runs, so
this gives bytes and compile success, never a time.  One JSON line per
program.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def shapes_engine(cfg, n_slots: int, cache_len: int):
    """A ``ContinuousBatcher`` whose weights and pool are shape structs:
    the same jitted step, with nothing allocated."""
    import jax
    import jax.numpy as jnp
    from repro.models.lm import Model
    from repro.serve import ContinuousBatcher

    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    zeros = jnp.zeros
    try:
        # the engine allocates its pool with jnp.zeros: give it shapes
        jnp.zeros = lambda shape, dtype=None: jax.ShapeDtypeStruct(
            tuple(shape), jnp.dtype(dtype or jnp.float32))
        engine = ContinuousBatcher(model, params, n_slots=n_slots,
                                   cache_len=cache_len)
    finally:
        jnp.zeros = zeros
    return model, engine


def rehearse(cfg, n_slots: int, cache_len: int, prefill_len: int, device):
    """Compile the pool's decode step and one prefill for ``device``;
    returns memory_analysis() bytes per program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    model, engine = shapes_engine(cfg, n_slots, cache_len)

    def on(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree)
    params, pool = on(engine.params), on(engine._pool)
    toks = jax.ShapeDtypeStruct((n_slots, 1, 1), jnp.int32, sharding=sh)
    poss = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=sh)
    batch = {"tokens": jax.ShapeDtypeStruct((1, prefill_len), jnp.int32,
                                            sharding=sh)}
    out = {}
    for name, fn, args in (
            ("decode_step", engine._step, (params, pool, toks, poss)),
            ("prefill", engine._prefill_fn(prefill_len), (params, batch))):
        try:
            ma = fn.lower(*args).compile().memory_analysis()
        except Exception as e:      # the chip's compiler refuses it
            out[name] = {"error": str(e).splitlines()[0][:300]}
            continue
        out[name] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "total_bytes": (ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.temp_size_in_bytes
                            - ma.alias_size_in_bytes)}
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(engine._pool))
    return {"n_slots": n_slots, "cache_len": cache_len,
            "prefill_len": prefill_len, "pool_bytes": pool_bytes, **out}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--pool", action="append", default=[],
                    help="SLOTSxCACHE to compile instead of the cell's own")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.experimental import topologies
    from repro.configs.base import ModelConfig
    jax.config.update("jax_enable_compilation_cache", False)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w for w in bench["workloads"]
             if (w["name"] in args.workloads) or not args.workloads]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for w in cells:
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        if traffic.get("kind") != "serve":
            continue
        config = json.loads(
            (ROOT / "bench" / "configs" / f"{w['config']}.json").read_text())
        cfg = ModelConfig(**config["model"])
        pools = [tuple(map(int, p.split("x"))) for p in args.pool] or \
            [(traffic["n_slots"], traffic["cache_len"])]
        for n_slots, cache_len in pools:
            res = rehearse(cfg, n_slots, cache_len,
                           max(traffic["prompt"]["grid"]), topo.devices[0])
            print(json.dumps({"workload": w["name"], **res}), flush=True)
        if args.pool:
            break


if __name__ == "__main__":
    main()

"""The v5e compile rehearsal of a serve cell's slot pool
(``bench/tools/rehearse_v5e.py``), at a small size: it compiles the
engine's own decode step and a prefill for a described v5e chip and reads
their bytes.  The full-size readings are in PERF.md."""
import json
import os
from pathlib import Path

import pytest

from bench.harness import core

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def v5e_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def test_rehearsal_reads_the_pool_and_its_programs(v5e_chip):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.configs.base import ModelConfig
    tool = core.load_module(core.BENCH / "tools" / "rehearse_v5e.py",
                            "bench_tool_rehearse_v5e")
    cfg = ModelConfig(**json.loads(
        (DATA / "configs" / "tiny-lm.json").read_text())["model"])
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    res = tool.rehearse(cfg, n_slots=4, cache_len=64, prefill_len=32,
                        device=v5e_chip)
    # K and V of 2 layers x 4 slots x 64 positions x 2 heads x 32, bf16
    assert res["pool_bytes"] == 2 * 2 * 4 * 64 * 2 * 32 * 2
    step = res["decode_step"]
    assert step["argument_bytes"] >= res["pool_bytes"]
    assert step["output_bytes"] >= res["pool_bytes"]
    assert step["total_bytes"] > 0 and res["prefill"]["total_bytes"] > 0

"""CPU rehearsal: each kind of cell end to end at a tiny size through the
same harness, the chip look skipped.  Sound runs come out correct, the
lower-precision control is refused, and runs with the timed path broken
underneath come out not correct.  A cell added as new files is found by
name with no file of the benchmark edited."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.harness import core

DATA = Path(__file__).parent / "data"
DIRS = (DATA, core.BENCH)
SEED = 2 ** 40 + 12345          # more than 32 bits hold
SERVE, PLAN = "serve.tiny.chat", "plan.tdfir-small"


def run(workload, trace=False, seed=SEED, bench_file=DATA / "BENCHMARK.json",
        dirs=DIRS, trace_dir=None):
    return core.run_cell(workload, seed, 2.0, trace, bench_file=bench_file,
                         dirs=dirs, require_chip=False, cache_dir=None,
                         trace_dir=trace_dir or core.TRACE_DIR)


@pytest.fixture(scope="module")
def sound():
    return {wl: run(wl) for wl in (SERVE, PLAN)}


@pytest.mark.parametrize("workload", [SERVE, PLAN])
def test_sound_run_is_correct_and_reports_its_end_to_end_metrics(
        sound, workload):
    out, _ = sound[workload]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    want = {m["name"] for m in core.metric_names(bench, workload, False)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert json.loads(json.dumps(out)) == out


@pytest.mark.parametrize("workload", [SERVE, PLAN])
def test_traced_run_reports_per_layer_metrics(workload, tmp_path):
    out, _ = run(workload, trace=True, seed=SEED + 1, trace_dir=tmp_path)
    assert out["correct"] is True
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    names = {m["name"] for m in core.metric_names(bench, workload, True)}
    # on the CPU there is no device trace: only host and counter metrics
    assert set(out["metrics"]) <= names and out["metrics"]


@pytest.mark.parametrize("workload", [SERVE, PLAN])
def test_the_control_is_refused(sound, workload):
    out, r = sound[workload]
    drv = core.load_kind(r.traffic["kind"], DIRS)
    ctl = core.verdict(drv.control(r))
    assert out["correct"] is True and ctl["correct"] is False
    (name, chk), = out["checks"].items()
    assert set(ctl["checks"]) == {name}
    assert chk["value"] <= chk["limit"] == ctl["checks"][name]["limit"] \
        < ctl["checks"][name]["value"]
    assert (ctl["attempted"], ctl["failed"]) == (out["attempted"],
                                                 out["failed"])


def _decode_state_unchanged(monkeypatch):
    from repro.models.lm import Model
    orig = Model.decode_step

    def step(self, params, cache, tokens, pos):
        logits, _ = orig(self, params, cache, tokens, pos)
        return logits, cache
    monkeypatch.setattr(Model, "decode_step", step)


def _decode_token_altered(monkeypatch):
    import jax.numpy as jnp
    from repro.models.lm import Model
    orig = Model.decode_step

    def step(self, params, cache, tokens, pos):
        logits, new = orig(self, params, cache, tokens, pos)
        return jnp.roll(logits, 1, axis=-1), new
    monkeypatch.setattr(Model, "decode_step", step)


def _answer_altered(monkeypatch):
    drv = core.load_kind("plan", DIRS)
    orig = drv.destination

    def destination(app, choice):
        fn = orig(app, choice)
        return lambda inputs: fn(inputs) * 1.1
    monkeypatch.setattr(drv, "destination", destination)


@pytest.mark.parametrize("workload,fault", [
    (SERVE, _decode_state_unchanged),
    (SERVE, _decode_token_altered),
    (PLAN, _answer_altered),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    out, _ = run(workload, seed=SEED + 2)
    assert out["correct"] is False


def _tree_digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    before = _tree_digest(core.BENCH)
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    cfg = json.loads((DATA / "configs" / "tiny-lm.json").read_text())
    cfg["model"]["n_kv_heads"] = 4
    (tmp_path / "configs" / "tiny-lm-mha.json").write_text(json.dumps(cfg))
    tr = json.loads((DATA / "traffic" / "tiny-chat.json").read_text())
    tr["rate_per_s"] = 4.0
    (tmp_path / "traffic" / "tiny-slow.json").write_text(json.dumps(tr))
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "serve.tiny-mha.slow",
                               "config": "tiny-lm-mha",
                               "traffic": "tiny-slow", "chips": 1,
                               "why": "added as files"})
    for m in bench["end_to_end"]:
        if "workloads" in m and SERVE in m["workloads"]:
            m["workloads"].append("serve.tiny-mha.slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out, r = run("serve.tiny-mha.slow", bench_file=tmp_path / "BENCHMARK.json",
                 dirs=(tmp_path,) + DIRS)
    assert out["correct"] is True
    assert r.config["model"]["n_kv_heads"] == 4
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert _tree_digest(core.BENCH) == before


def test_no_chip_means_no_result_and_a_nonzero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(core.BENCH / "run.py"),
                        "--workload", "serve.granite-3-2b.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=core.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _tiny_params():
    import jax
    from repro.configs.base import ModelConfig
    from repro.models.lm import Model
    from bench.harness.seeds import jax_key

    cfg = json.loads((DATA / "configs" / "tiny-lm.json").read_text())
    model, key = Model(ModelConfig(**cfg["model"])), jax_key(SEED)
    ref = core.load_kind("serve").reference_config(cfg)
    return model, key, ref, jax.eval_shape(model.init, key)


def test_the_reference_draws_the_programs_weights():
    """The reference imports nothing of the program and draws its weights
    again from the seed; here, at a small size, they are the weights the
    program was handed, leaf for leaf where the program keeps them (XLA
    may round a rare element one bfloat16 step apart, see
    lm_reference)."""
    from bench.harness import lm_params, lm_reference as R

    _, key, ref, like = _tiny_params()
    params = lm_params.program_params(ref, key, like)
    assert np.array_equal(params["embed"], R.embedding(ref, key))
    for tree in (params["final_norm"], params["blocks"]["attn_norm"],
                 params["blocks"]["ffn_norm"]):
        assert np.all(np.asarray(tree["scale"], np.float32) == 1.0)
    for layer in range(ref["n_layers"]):
        w = R.layer_weights(ref, key, layer)
        for name, got in w.items():
            group = "attn" if name in lm_params.ATTN else "ffn"
            want = np.asarray(params["blocks"][group][name][layer],
                              np.float32)
            step = 2.0 ** -7 * np.maximum(np.abs(want), 1e-30)
            assert np.all(np.abs(np.asarray(got, np.float32) - want)
                          <= step), (layer, name)


def test_a_changed_program_layout_is_refused():
    """Weights that do not fit the program's tree are not served."""
    import jax
    from bench.harness import lm_params

    _, key, ref, like = _tiny_params()
    fused = dict(like, blocks=dict(like["blocks"], attn={
        "wqkv": jax.ShapeDtypeStruct((2, 128, 8, 32), like["embed"].dtype),
        "wo": like["blocks"]["attn"]["wo"]}))
    with pytest.raises(ValueError, match="lm_params"):
        lm_params.program_params(ref, key, fused)


def test_a_sweep_runs_the_cell_at_each_rate():
    sweep = core.load_module(core.BENCH / "tools" / "sweep.py",
                             "bench_tool_sweep").sweep
    rows = list(sweep(SERVE, [4.0, 8.0], [SEED], 2.0,
                      bench_file=DATA / "BENCHMARK.json", dirs=DIRS,
                      require_chip=False, cache_dir=None))
    assert [(r["rate_per_s"], r["requests"]) for r in rows] == [(4.0, 8),
                                                               (8.0, 16)]
    assert all(r["correct"] and r["first_tokens"] == r["requests"]
               for r in rows)

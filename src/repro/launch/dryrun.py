import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"     # models the pod; never takes a chip

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell the full-size step function (train_step / prefill / serve_step)
is lowered with ShapeDtypeStruct inputs and compiled for the production mesh;
``memory_analysis()`` proves the per-device footprint, ``cost_analysis()`` +
HLO collective parsing feed the §Roofline terms.  Results are cached as JSON
under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all             # driver: subprocess/cell
  python -m repro.launch.dryrun --all --mesh multi
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def default_plan(cfg, shape, plan_name: str = "auto",
                 overrides: dict = None):
    """Baseline per-cell plan (recorded in EXPERIMENTS.md as the baseline).

    `overrides` (from --plan-json) patches arbitrary Plan fields on top of
    the auto baseline — the §Perf hillclimb mechanism.
    """
    from repro.dist.plan import Plan
    import dataclasses as dc
    if plan_name not in ("auto", "baseline"):
        from repro.dist import plan as plan_mod
        named = {p.name: p for p in vars(plan_mod).values()
                 if isinstance(p, Plan)}
        if plan_name in named:
            # overrides (--plan-json / --schedule) patch the named plan,
            # they must not silently replace it with the auto baseline
            base = named[plan_name]
            return dc.replace(base, **overrides) if overrides else base
    kw = {}
    if shape.kind != "train":
        kw["remat"] = "none"
    if shape.kind == "decode":
        kw["decode_kv_seq_shard"] = True
    if cfg.padded_vocab >= 100_000:
        kw["vocab_chunk"] = 512
    name = "auto-baseline"
    if overrides:
        kw.update(overrides)
        name = plan_name if plan_name not in ("auto", "baseline") \
            else "override"
    return Plan(name=name, **kw)


def build_step(cfg, shape, mesh, plan):
    """Returns (fn, example_args_SDS, in_shardings, donate)."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as SDS

    from repro.configs.base import TrainConfig
    from repro.dist.sharding import Rules, tree_shardings
    from repro.launch import specs
    from repro.models.lm import Model, param_axes, cache_axes
    from repro.train import optimizer, train_step as ts

    rules = Rules(mesh, plan)
    model = Model(cfg, plan, rules)
    key_sds = SDS((2,), jnp.uint32)
    params_sds = jax.eval_shape(
        lambda k: model.init(k), key_sds)
    p_axes = param_axes(cfg)
    params_sh = tree_shardings(rules, p_axes, params_sds)
    batch_sds = specs.batch_specs(cfg, shape)
    b_axes = specs.logical_batch_axes(cfg, shape)
    batch_sh = {k: rules.sharding(b_axes[k], batch_sds[k].shape)
                for k in batch_sds}

    if shape.kind == "train":
        tcfg = TrainConfig(microbatches=plan.microbatches,
                           master_dtype=plan.opt_state_dtype)
        opt_sds = jax.eval_shape(lambda p: optimizer.init(p, tcfg),
                                 params_sds)
        o_axes = optimizer.opt_state_axes(p_axes, tcfg)
        opt_sh = tree_shardings(rules, o_axes, opt_sds)
        fn = ts.make_train_step(model, tcfg)
        args = (params_sds, opt_sds, batch_sds, SDS((), jnp.int32))
        shardings = (params_sh, opt_sh, batch_sh, None)
        return fn, args, shardings, (0, 1)
    if shape.kind == "prefill":
        fn = ts.make_prefill_step(model, cache_len=shape.seq_len)
        args = (params_sds, batch_sds)
        return fn, args, (params_sh, batch_sh), ()
    # decode
    cache_sds = specs.cache_specs(cfg, shape, plan)
    c_axes = cache_axes(cfg, quant=plan.kv_cache_quant)
    cache_sh = tree_shardings(rules, c_axes, cache_sds)
    fn = ts.make_serve_step(model)
    args = (params_sds, cache_sds, batch_sds["tokens"], SDS((), jnp.int32))
    shardings = (params_sh, cache_sh, batch_sh["tokens"], None)
    return fn, args, shardings, (1,)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             plan_name: str = "auto", out_dir: Path = OUT_DIR,
             overrides: dict = None, policy: str = "host-time",
             use_cache: bool = True) -> dict:
    """One dry-run cell, wrapped in a ``dryrun/cell`` span (repro.obs)."""
    from repro.obs import get_tracer
    with get_tracer().span("cell", cat="dryrun", track="dryrun",
                           arch=arch, shape=shape_name, mesh=mesh_kind,
                           plan=plan_name) as span:
        result = _run_cell(arch, shape_name, mesh_kind, plan_name, out_dir,
                           overrides, policy, use_cache)
        span.set(skipped="skip" in result, pruned="lint" in result
                 and "error" in result, cache_hit=result.get("cache_hit"),
                 compile_s=result.get("compile_s"),
                 verify_s=result.get("verify_s"))
    return result


def _run_cell(arch: str, shape_name: str, mesh_kind: str,
              plan_name: str = "auto", out_dir: Path = OUT_DIR,
              overrides: dict = None, policy: str = "host-time",
              use_cache: bool = True) -> dict:
    import jax
    from repro.configs import get_config, get_shape, cell_runnable
    from repro.core import cost_model
    from repro.core import search_cache as sc
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    result: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "plan": plan_name, "policy": policy}
    if not cell_runnable(cfg, shape):
        result["skip"] = ("long_500k needs sub-quadratic attention; "
                          f"{arch} is pure full-attention (see DESIGN.md)")
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    plan = default_plan(cfg, shape, plan_name, overrides)
    result["plan_detail"] = dataclasses.asdict(plan)

    # static plan lint (repro.analysis): findings ride the cell JSON so a
    # sweep over cells doubles as a lint sweep; an error-severity finding
    # prunes the cell before any lowering or XLA compile is spent on it
    from repro.analysis import findings_to_json, has_errors, lint_plan
    pipelined = bool(overrides and "pipeline_schedule" in overrides)
    lint = lint_plan(plan, mesh=mesh, cfg=cfg, shape=shape,
                     pipelined=pipelined)
    result["lint"] = findings_to_json(lint)
    if has_errors(lint):
        result["error"] = "statically pruned: " + "; ".join(
            f"{f.rule_id}: {f.message}" for f in lint
            if f.severity == "error")
        return result

    # structure-keyed compile cache: cells whose plans differ only in
    # model-only genes (e.g. --schedule variants of the same baseline)
    # share one compiled artifact, and repeat invocations skip XLA entirely
    cache = sc.SearchCache((out_dir / "search_cache.json") if use_cache
                           else None)
    cache_key = ("dryrun", arch, shape_name, mesh_kind,
                 sc.mesh_fingerprint(mesh), plan.structural_key())
    cache.stats.candidates += 1
    t0 = time.time()
    payload = cache.lookup(cache_key)
    cache_hit = (payload is not None and "error" not in payload
                 and isinstance(payload.get("extra"), dict)
                 and "memory" in payload["extra"])
    if cache_hit:
        analyzed = payload["analysis"]
        t_lower = payload["extra"].get("lower_s", 0.0)
        t_compile = payload.get("compile_s", 0.0)
        ca = payload["extra"].get("xla_cost_analysis", {})
        memory = payload["extra"]["memory"]
        verify_s = time.time() - t0        # actual cost this run: a lookup
    else:
        fn, args, shardings, donate = build_step(cfg, shape, mesh, plan)
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        verify_s = t_lower + t_compile

        ca_raw = compiled.cost_analysis() or {}
        ca = {k: float(v) for k, v in ca_raw.items()
              if isinstance(v, (int, float))
              and ("flops" in k or k == "bytes accessed")}
        ma = compiled.memory_analysis()
        memory = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        }
        analyzed = sc.analyze_compiled(compiled)  # loop-aware per-device
        cache.put(cache_key, analyzed, t_compile,
                  extra={"lower_s": round(t_lower, 2),
                         "memory": memory, "xla_cost_analysis": ca})
    mf = cost_model.model_flops_for(cfg, shape)
    # pipeline-schedule genes stretch the step by the schedule's bubble —
    # but only for cells that explicitly request a pipeline (--schedule /
    # --plan-json): the baseline step is data-parallel over "pod", and the
    # default Plan genes must not shift every cached multi-mesh roofline
    pipe_ranks = mesh.shape["pod"] if "pod" in mesh.axis_names else 1
    bubble = (cost_model.plan_bubble_fraction(plan, pipe_ranks)
              if pipelined else 0.0)
    rl = cost_model.roofline_terms(
        analyzed["flops"], analyzed["bytes"],
        analyzed["collective_bytes"],
        n_chips=n_chips, model_flops=mf, bubble_fraction=bubble)

    result.update({
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "verify_s": round(verify_s, 3),
        "cache_hit": cache_hit,
        "xla_cost_analysis": ca,
        "hlo_analysis": {k: float(v) for k, v in analyzed.items()},
        "memory": memory,
        "collectives": {k.replace("coll_", ""): v
                        for k, v in analyzed.items()
                        if k.startswith("coll_")},
        "collective_counts": {k.replace("count_", ""): v
                              for k, v in analyzed.items()
                              if k.startswith("count_")},
        "roofline": rl.to_dict(),
        "fits_16GiB": memory["peak_estimate_bytes"] < 16 * 1024**3,
    })
    # modeled energy of the cell (repro.power): the slice's chip envelope
    # at the roofline's utilization — what --policy power | edp rank
    from repro.power import cell_energy
    e_rep = cell_energy(rl, n_chips)
    result["energy"] = e_rep.to_dict() if e_rep is not None else None
    # selection-policy score (repro.backends.policy): the ranking key the
    # cost policy assigns this cell — host-time / modeled rank pure step
    # time; price-weighted ranks step_time x chip count (throughput per
    # relative dollar); power ranks the cell's modeled joules per step and
    # edp its energy-delay product.  A cell enters ranking as a Candidate
    # (repro.core.candidates) like every other selection site.
    from repro.backends import get_policy
    from repro.core.candidates import Candidate
    pol = get_policy(policy)
    result["policy_score"] = pol.score_candidate(Candidate.from_cell(
        rl.step_time_s, n_chips=float(n_chips), backend=mesh_kind,
        arch=str(arch), energy=result["energy"]))
    return result


def cell_path(out_dir: Path, arch, shape, mesh_kind, plan_name) -> Path:
    tag = f"{arch}__{shape}__{mesh_kind}"
    if plan_name not in ("auto", "baseline"):
        tag += f"__{plan_name}"
    return out_dir / f"{tag}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--plan", default="auto")
    ap.add_argument("--plan-json", default=None,
                    help='JSON dict of Plan field overrides')
    ap.add_argument("--schedule", default=None,
                    choices=["gpipe", "one_f_one_b", "interleaved"],
                    help="pipeline schedule gene (repro.dist.schedules); "
                         "overrides Plan.pipeline_schedule and folds the "
                         "schedule's bubble fraction into the roofline on "
                         "meshes with a pod axis")
    ap.add_argument("--virtual-stages", type=int, default=None,
                    help="chunks per rank for --schedule interleaved")
    ap.add_argument("--policy", default="host-time",
                    help="selection policy ranking the compiled cells "
                         "(repro.backends.policy): host-time | modeled "
                         "rank pure modeled step time; price-weighted "
                         "ranks step_time x chip count; power ranks the "
                         "cell's modeled joules per step (repro.power: "
                         "TPU chip envelope x roofline utilization) and "
                         "edp its energy-delay product. With --all, the "
                         "best mesh per (arch, shape) under the policy "
                         "is printed.")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-search-cache", action="store_true",
                    help="bypass the structure-keyed compile cache "
                         "(<out>/search_cache.json) and always recompile")
    ap.add_argument("--timeout", type=int, default=3000)
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a repro.obs trace of this invocation's "
                         "cells; writes JSONL events if PATH ends in "
                         ".jsonl, else a Perfetto-loadable Chrome trace "
                         "(single-cell mode only — the --all driver runs "
                         "each cell in a subprocess)")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    # schedule flags ride the Plan-override mechanism; pipelined cells cache
    # under their own tag so they never shadow the baseline plan's JSON —
    # whether the pipeline genes arrive via --schedule or --plan-json
    sched_overrides = {}
    if args.schedule:
        sched_overrides["pipeline_schedule"] = args.schedule
    if args.virtual_stages:
        if not args.schedule:
            ap.error("--virtual-stages requires --schedule")
        sched_overrides["virtual_stages"] = args.virtual_stages
    try:
        json_overrides = json.loads(args.plan_json) if args.plan_json else {}
    except json.JSONDecodeError as e:
        ap.error(f"--plan-json is not valid JSON: {e}")
    all_overrides = dict(json_overrides, **sched_overrides)
    plan_tag = args.plan
    if "pipeline_schedule" in all_overrides:
        plan_tag = f"{args.plan}-{all_overrides['pipeline_schedule']}"
        if all_overrides.get("virtual_stages"):
            plan_tag += f"-v{all_overrides['virtual_stages']}"

    if args.all:
        from repro.configs import ARCHS, SHAPES
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        todo = [(a, s, m) for a in ARCHS for s in SHAPES for m in meshes]
        ok = fail = skip = 0
        for arch, shape, mesh_kind in todo:
            path = cell_path(out_dir, arch, shape, mesh_kind, plan_tag)
            if path.exists() and not args.force:
                prev = json.loads(path.read_text())
                ok += ("error" not in prev and "skip" not in prev)
                skip += "skip" in prev
                fail += "error" in prev
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                   "--plan", args.plan, "--policy", args.policy,
                   "--out", str(out_dir)]
            if args.schedule:
                cmd += ["--schedule", args.schedule]
            if args.virtual_stages:
                cmd += ["--virtual-stages", str(args.virtual_stages)]
            if args.plan_json:
                cmd += ["--plan-json", args.plan_json]
            if args.no_search_cache:
                cmd += ["--no-search-cache"]
            print(f"[dryrun] {arch} × {shape} × {mesh_kind} ...",
                  flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout,
                                   capture_output=True, text=True)
                if r.returncode != 0:
                    path.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mesh_kind,
                         "error": (r.stderr or r.stdout)[-4000:]}, indent=1))
                    fail += 1
                    print(f"  FAIL (rc={r.returncode})", flush=True)
                else:
                    res = json.loads(path.read_text())
                    if "skip" in res:
                        skip += 1
                        print("  skip", flush=True)
                    else:
                        ok += 1
                        rl = res["roofline"]
                        e = res.get("energy") or {}
                        e_tag = (f" energy={e['energy_j']:.1f}J"
                                 f"@{e['avg_watts']:.0f}W" if e else "")
                        print(f"  ok compile={res['compile_s']}s "
                              f"dominant={rl['dominant']} "
                              f"step={rl['step_time_s']:.4f}s{e_tag}",
                              flush=True)
            except subprocess.TimeoutExpired:
                path.write_text(json.dumps(
                    {"arch": arch, "shape": shape, "mesh": mesh_kind,
                     "error": f"timeout after {args.timeout}s"}, indent=1))
                fail += 1
                print("  TIMEOUT", flush=True)
        # policy selection across meshes: for each (arch, shape) with more
        # than one compiled mesh cell, report the one the cost policy picks
        from repro.backends import get_policy
        pol = get_policy(args.policy)
        by_cell: dict = {}
        for arch, shape, mesh_kind in todo:
            path = cell_path(out_dir, arch, shape, mesh_kind, plan_tag)
            if not path.exists():
                continue
            r = json.loads(path.read_text())
            if "error" in r or "skip" in r or "roofline" not in r:
                continue
            # always rescore from the stored roofline: a cell JSON written
            # by an older build may carry a policy_score in different
            # units (or no energy block at all), and min() must compare
            # one unit across cells — recompute the energy when absent
            energy = r.get("energy")
            if energy is None and "roofline" in r:
                from repro.power import cell_energy
                e_rep = cell_energy(r["roofline"], r["n_chips"])
                energy = e_rep.to_dict() if e_rep is not None else None
                r["energy"] = energy
            from repro.core.candidates import Candidate
            score = pol.score_candidate(Candidate.from_cell(
                r["roofline"]["step_time_s"], n_chips=float(r["n_chips"]),
                backend=mesh_kind, arch=str(arch), energy=energy, ref=r))
            by_cell.setdefault((arch, shape), []).append((score, mesh_kind, r))
        for (arch, shape), cells in sorted(by_cell.items()):
            if len(cells) < 2:
                continue
            score, mesh_kind, r = min(cells, key=lambda c: c[0])
            e = r.get("energy") or {}
            e_tag = (f", {e['energy_j']:.1f} J/step "
                     f"@ {e['avg_watts']:.0f} W" if e else "")
            print(f"[policy={pol.name}] {arch} x {shape}: {mesh_kind} "
                  f"({r['n_chips']} chips, "
                  f"step={r['roofline']['step_time_s']:.4f}s{e_tag}, "
                  f"score={score:.4f})")
        print(f"[dryrun] done: {ok} ok, {skip} skip, {fail} fail")
        sys.exit(1 if fail else 0)

    # single cell (in-process)
    assert args.arch and args.shape
    path = cell_path(out_dir, args.arch, args.shape, args.mesh, plan_tag)
    from repro import obs
    tracer = obs.Tracer() if args.trace else obs.NULL_TRACER
    try:
        with obs.use_tracer(tracer):
            res = run_cell(args.arch, args.shape, args.mesh, args.plan,
                           out_dir, all_overrides or None,
                           policy=args.policy,
                           use_cache=not args.no_search_cache)
    except Exception:
        res = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "error": traceback.format_exc()[-6000:]}
        path.write_text(json.dumps(res, indent=1))
        print(json.dumps(res, indent=1))
        sys.exit(1)
    finally:
        if args.trace:
            if args.trace.endswith(".jsonl"):
                obs.write_jsonl(tracer.records, args.trace)
            else:
                obs.write_chrome_trace(tracer.records, args.trace)
    path.write_text(json.dumps(res, indent=1))
    print(json.dumps({k: v for k, v in res.items()
                      if k in ("arch", "shape", "mesh", "compile_s",
                               "verify_s", "cache_hit", "roofline",
                               "energy", "fits_16GiB", "skip")}, indent=1))


if __name__ == "__main__":
    main()

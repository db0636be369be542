import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"     # models the mesh; never takes a chip

"""Framework-side offload search: the paper's GA over *execution-plan*
genes (sharding / remat / microbatching / compression) for an LM training
step, with the compiled-artifact roofline as the fitness measurement —
DESIGN.md §2's CompiledCostRunner verification environment.

    python examples/autoplan_model.py [--arch h2o-danube-1.8b]
"""
import argparse
import sys

sys.path.insert(0, "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--generations", type=int, default=4)
    ap.add_argument("--population", type=int, default=5)
    ap.add_argument("--compile-workers", type=int, default=4,
                    help="threads tracing+compiling one generation's "
                         "unique structural candidates")
    ap.add_argument("--cache-dir", default="experiments/search_cache",
                    help="directory for the on-disk search-cache JSON "
                         "(repro.core.search_cache); a warm cache scores "
                         "repeat searches with zero XLA compiles")
    ap.add_argument("--no-disk-cache", action="store_true",
                    help="keep the search cache in memory only")
    ap.add_argument("--policy", default="modeled",
                    help="plan-selection policy (repro.backends.policy): "
                         "modeled / host-time rank pure modeled step time; "
                         "price-weighted weights each plan's per-device "
                         "memory traffic (a machine-size proxy); power / "
                         "edp rank the modeled joules per step of each "
                         "candidate's roofline under the mesh's TPU chip "
                         "envelope (repro.power)")
    args = ap.parse_args()

    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from repro.backends import get_policy
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.core import search_cache as sc
    from repro.core.ga import GAConfig, run_ga
    from repro.core.measure import CompiledCostRunner
    from repro.dist.plan import Plan
    from repro.dist.sharding import Rules, tree_shardings
    from repro.launch import specs
    from repro.launch.mesh import make_test_mesh
    from repro.models.lm import Model, param_axes
    from repro.train import optimizer, train_step as ts

    cfg = get_config(args.arch).reduced()
    shape = ShapeConfig("plan-search", 64, 16, "train")
    # a pod axis so the pipeline-schedule genes have a destination.  The
    # schedule genes are scored by *model*: the compiled artifact stays the
    # dp/tp step (the verification machine cannot execute a pod-scale
    # pipeline — CompiledCostRunner's charter), and each candidate's step
    # time is stretched by the bubble its declared schedule would impose on
    # the pod ranks, so schedule/virtual_stages/microbatches trade off
    # inside one consistent modeled objective
    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    pipe_ranks = mesh.shape["pod"]
    tcfg = TrainConfig()
    runner = CompiledCostRunner(mesh)
    pol = get_policy(args.policy)

    def lower_plan(plan):
        """Trace + lower one plan candidate (no XLA compilation yet).

        Runs on the evaluator's worker pool: tracing is no longer a serial
        prefix of the generation, and only one candidate per unique
        structural key is ever traced.
        """
        rules = Rules(mesh, plan)
        model = Model(cfg, plan, rules)
        params_sds = jax.eval_shape(
            model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_sh = tree_shardings(rules, param_axes(cfg), params_sds)
        opt_sds = jax.eval_shape(lambda p: optimizer.init(p, tcfg),
                                 params_sds)
        batch_sds = specs.batch_specs(cfg, shape)   # arch-aware (mm extras)
        fn = ts.make_train_step(model, tcfg)
        jitted = jax.jit(fn, in_shardings=(p_sh, None, None, None))
        return jitted.lower(params_sds, opt_sds, batch_sds,
                            jax.ShapeDtypeStruct((), jnp.int32))

    # structure-keyed search cache: candidates are deduped by
    # Plan.structural_key() before tracing (the 3x2 schedule combinations
    # per structural plan share one compile), and the on-disk layer lets a
    # repeat search over the same (arch, shape, mesh) run with zero compiles
    cache_path = None if args.no_disk_cache else (
        Path(args.cache_dir) / f"autoplan-{args.arch}.json")
    cache = sc.SearchCache(cache_path)
    evaluate_batch = sc.make_cached_batch_evaluator(
        lower_plan, runner, cache,
        key_extra=("autoplan", args.arch, shape.name,
                   sc.mesh_fingerprint(mesh)),
        pipe_ranks=pipe_ranks, workers=args.compile_workers)

    cards = Plan.gene_cardinalities()
    cfg_ga = GAConfig(population=args.population,
                      generations=args.generations, seed=0,
                      cardinalities=cards)
    res = run_ga(len(cards), evaluate_batch.evaluate, cfg_ga,
                 evaluate_batch=evaluate_batch)

    # policy selection over every compiled candidate: price is proxied by
    # the plan's per-device memory traffic (relative to the leanest
    # candidate), so price-weighted prefers memory-lean plans when modeled
    # step time is close; power / edp rerank the GA front by the modeled
    # energy of each candidate's roofline (utilization x the mesh slice's
    # TPU chip envelope — a comm/bubble-heavy plan burns idle watts over a
    # longer step and loses even when its host ranking was close)
    from repro.core.candidates import Candidate
    from repro.power import cell_energy
    valid_bytes = [x.info["roofline"]["bytes_per_device"]
                   for x in res.evaluations.values()
                   if x.correct and "roofline" in x.info]
    base_bytes = max(min(valid_bytes), 1.0) if valid_bytes else 1.0

    def price_proxy(e):
        return e.info["roofline"]["bytes_per_device"] / base_bytes

    def cand_score(e):
        return pol.score_candidate(Candidate.from_roofline(
            e.info["roofline"], n_chips=mesh.size, price=price_proxy(e),
            time_s=e.time_s, backend="mesh", arch=args.arch, ref=e))

    scored = [(cand_score(e), genes, e)
              for genes, e in res.evaluations.items()
              if e.correct and "roofline" in e.info]
    if scored:
        _, best_genes, best_eval = min(scored, key=lambda s: s[0])
    else:
        best_genes, best_eval = res.best_genes, res.best_eval
    best = Plan.from_genes(list(best_genes))
    best_energy = ("roofline" in best_eval.info
                   and cell_energy(best_eval.info["roofline"], mesh.size))
    e_tag = (f", {best_energy.energy_j:.1f} J/step "
             f"@ {best_energy.avg_watts:.0f} W" if best_energy else "")
    print(f"\nbest plan for {args.arch} under policy={pol.name} "
          f"(modeled step {best_eval.time_s*1e6:.1f} us{e_tag} "
          f"on {mesh.shape}):")
    for gene in Plan.GENE_SPACE:
        tag = "" if gene.structural else "   [model-only]"
        print(f"  {gene.field:22s} = {getattr(best, gene.field)}{tag}")
    st = cache.stats
    print(f"scored {res.n_measurements} candidates | "
          f"unique compiles {st.unique_compiles} | "
          f"cache hit rate {st.hit_rate:.0%} "
          f"(disk {st.disk_hits}) | "
          f"compile time {st.compile_s:.1f}s")
    if cache_path is not None:
        print(f"search cache: {cache_path}")


if __name__ == "__main__":
    main()

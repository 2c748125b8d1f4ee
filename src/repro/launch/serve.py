"""Serving driver — thin CLI over the :mod:`repro.serve` subsystem.

Continuous-batching by default: requests are admitted into decode slots as
they free up, under the byte budget the Planner turns into a slot count.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3_4b \
      --preset reduced --requests 8 --traffic poisson --gen 32 \
      --budget-gb 0.5

Old one-shot flags still work (`--batch 4 --prompt-len 64 --gen 32` serves
a static batch of identical-length prompts arriving together).
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="reduced", choices=["reduced", "full"])
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots when --budget-gb is 0 (old flag; "
                         "also the default --requests count)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-gb", type=float, default=0.0,
                    help="serving byte budget: sizes the decode cache pool "
                         "(slot count) and bounds each prompt's chunked "
                         "prefill")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests (default: --batch)")
    ap.add_argument("--traffic", default="static",
                    choices=["static", "poisson", "bursty"])
    ap.add_argument("--mean-interarrival", type=float, default=2.0,
                    help="poisson/bursty mean inter-arrival, in scheduler "
                         "ticks")
    ap.add_argument("--burst", type=int, default=4,
                    help="bursty traffic: mean requests per arrival clump")
    ap.add_argument("--mixed-prompts", action="store_true",
                    help="sample prompt lengths from {P/4, P/2, P} instead "
                         "of a fixed --prompt-len P")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="device mesh spec (e.g. data=2): the global "
                         "--budget-gb is divided by the data extent into "
                         "per-device slices and decode slots shard across "
                         "the data axis")
    ap.add_argument("--residency", default="",
                    choices=["", "device", "host", "recompute"],
                    help="boundary-cache residency policy recorded on "
                         "each prompt's budget-chunked prefill plan")
    ap.add_argument("--cache-kind", default="full",
                    choices=["full", "paged_kv", "quant_kv"],
                    help="decode cache pool layout: contiguous worst-case "
                         "slots, paged KV behind a block table, or int8 "
                         "quantised KV")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page (paged_kv)")
    ap.add_argument("--decode-residency", default="",
                    choices=["", "device", "host"],
                    help="decode-state residency: 'host' keeps pool "
                         "buffers in host memory and fetches the decode "
                         "cohort one tick ahead")
    ap.add_argument("--decode-batch", type=int, default=0,
                    help="cap the per-tick decode cohort (0 = whole pool)")
    ap.add_argument("--preemptible-prefill", action="store_true",
                    help="chunked prefill spends one tick per row chunk "
                         "and can be evicted by higher-priority arrivals")
    ap.add_argument("--priority-levels", type=int, default=1,
                    help="sample request priorities from [0, levels)")
    ap.add_argument("--slo-p50", type=float, default=0.0,
                    help="p50 latency SLO target, in scheduler ticks")
    ap.add_argument("--slo-p95", type=float, default=0.0,
                    help="p95 latency SLO target, in scheduler ticks")
    ap.add_argument("--out", default="",
                    help="write a serve artefact JSON (args + resolved "
                         "pool plan + cache kind/decode residency + "
                         "summary) to this directory")
    from repro.exec.plancache import add_plan_cache_arg
    from repro.obs.cli import add_obs_args, configure_from_args, profiled
    add_plan_cache_arg(ap)
    add_obs_args(ap)
    args = ap.parse_args()

    import jax

    from repro import obs
    from repro.configs import get_config, get_reduced
    from repro.exec import MeshSpec
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.lm import encdec as ED
    from repro.models.lm import model as LM
    from repro.serve import SLO, make_requests, serve

    enable_compile_cache()
    configure_from_args(args, tool="serve", arch=args.arch,
                        cache_kind=args.cache_kind, traffic=args.traffic)

    mesh_spec = MeshSpec.parse(args.mesh) if args.mesh else None
    cfg = get_reduced(args.arch) if args.preset == "reduced" \
        else get_config(args.arch)
    n_requests = args.requests or args.batch
    budget = int(args.budget_gb * 2**30)

    prompt_len = args.prompt_len
    if args.mixed_prompts:
        # a list is a choice set for make_requests even when the buckets
        # collapse to 2 distinct lengths (only a tuple means a range)
        prompt_len = sorted({max(4, args.prompt_len // 4),
                             max(4, args.prompt_len // 2), args.prompt_len})
    feature = {}
    enc_len = 0
    if cfg.frontend == "vision":
        feature = {"frontend": "vision",
                   "n_feature_tokens": cfg.n_frontend_tokens}
    elif cfg.family == "encdec":
        enc_len = args.prompt_len
        feature = {"frontend": "audio", "n_feature_tokens": enc_len,
                   "feature_dim": cfg.d_model}

    priority = 0 if args.priority_levels <= 1 \
        else (0, args.priority_levels - 1)
    requests = make_requests(
        n_requests, cfg.vocab, seed=args.seed, traffic=args.traffic,
        prompt_len=prompt_len, max_new_tokens=args.gen,
        mean_interarrival=args.mean_interarrival,
        temperature=args.temperature, top_k=args.top_k,
        priority=priority, burst_size=args.burst, **feature)

    key = jax.random.PRNGKey(args.seed)
    params = ED.init_encdec(key, cfg) if cfg.family == "encdec" \
        else LM.init_lm(key, cfg)

    slo = None
    if args.slo_p50 or args.slo_p95:
        slo = SLO(p50_latency=args.slo_p50, p95_latency=args.slo_p95)

    t0 = time.perf_counter()
    with profiled(args):
        report, plan = serve(params, cfg, requests, budget=budget,
                             n_slots=0 if budget else args.batch,
                             enc_len=enc_len, prefill_budget=budget,
                             mesh=mesh_spec, residency=args.residency,
                             cache_kind=args.cache_kind,
                             page_size=args.page_size,
                             decode_residency=args.decode_residency,
                             decode_batch=args.decode_batch,
                             preemptible_prefill=args.preemptible_prefill,
                             slo=slo, walltime_fn=time.perf_counter,
                             plan_cache=args.plan_cache)
    wall = time.perf_counter() - t0

    print("pool plan:", plan.describe())
    if report.plan_audit is not None:
        a = report.plan_audit
        print(f"plan audit: {a['audited_term']} {a['est_bytes_per_device']} "
              f"measured pool {a['measured']['peak_bytes']}"
              + (f" ratio {a['ratio']:.3f}"
                 if a['ratio'] is not None else ""))
    s = report.summary()
    print(f"arch={cfg.name} requests={s['requests']} traffic={args.traffic} "
          f"cache_kind={args.cache_kind} slots={plan.n_rows}")
    print(f"generated {s['generated_tokens']} tokens in {wall:.2f}s "
          f"({s['generated_tokens'] / max(wall, 1e-9):.1f} tok/s wall); "
          f"{s['prefills']} prefills, {s['decode_steps']} decode steps, "
          f"max_active={s['max_active']}, "
          f"preemptions={s['preemptions']}")
    print(f"latency ticks: p50={s['p50_latency_ticks']:.1f} "
          f"p95={s['p95_latency_ticks']:.1f} "
          f"ttft p50={s['p50_ttft_ticks']:.1f} "
          f"p95={s['p95_ttft_ticks']:.1f}")
    if "slo" in s:
        print(f"SLO: met={s['slo']['met']} "
              f"attainment={s['slo']['attainment']}")
    for st in report.states[:4]:
        print(f"  request {st.rid}: prompt={st.request.prompt_len} "
              f"slot={st.slot} chunks={st.prefill_chunks} "
              f"tokens={st.generated[:8]}...")
    # numeric health is enforced inside the engine: ServeEngine.sample
    # raises FloatingPointError on non-finite logits, so reaching this
    # point means every generated token came from finite logits
    assert all(st.done for st in report.states)
    if args.out:
        # the serve artefact fully pins how the run executed — the pool
        # plan (cache kind, page geometry, decode residency included) the
        # same way dry-run artefacts pin kernel policy
        os.makedirs(args.out, exist_ok=True)
        rec = {
            "arch": cfg.name, "preset": args.preset,
            "traffic": args.traffic, "requests": n_requests,
            "budget_bytes": budget, "mesh": args.mesh,
            "cache_kind": args.cache_kind,
            "prefill_residency": args.residency,
            "decode_residency": (plan.residency.describe()
                                 if plan.residency is not None else ""),
            "exec_plan": plan.to_dict(),
            "exec_plan_per_device": plan.per_device().to_dict(),
            "slo": s.get("slo"),
            "summary": s,
            "plan_audit": report.plan_audit,
        }
        tag = f"{cfg.name}_{args.cache_kind}_{args.traffic}"
        path = os.path.join(args.out, tag + ".json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"artefact: {path}")
    obs.shutdown()
    print("serve OK")


if __name__ == "__main__":
    main()

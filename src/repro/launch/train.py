"""Training driver.

Two modes:
* LM:   PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b \
            --preset reduced --steps 50 --batch 8 --seq 128
* CNN:  PYTHONPATH=src python -m repro.launch.train --arch vgg16 \
            --preset reduced --steps 100 --strategy twophase --rows 4
* auto: PYTHONPATH=src python -m repro.launch.train --arch vgg16 \
            --preset reduced --steps 2 --budget-gb 0.01
        (Planner.for_budget picks engine + N under the byte budget and
        prints the resolved ExecutionPlan; works for LM archs too, where
        the budget drives the sequence-chunk count)
* sharded: add --mesh data=8 (with XLA_FLAGS=--xla_force_host_platform_\
            device_count=8 on CPU hosts): the Planner solves the SAME
            budget per-device (batch and budget divided by the data
            extent), the resolved plan carries the mesh, and execution
            shards the batch across it — CNN via the registry's shard
            wrapper, LM via in_shardings from launch.steps.
* pallas:  add --kernel pallas: the resolved plan is kernelized — its
            engine swapped for the Pallas-backed alternate (rows as VMEM
            grid steps; interpret mode off-TPU, compiled on a TPU) with
            automatic lax fallback when the tiling is
            infeasible.  Composes with --mesh: kernel-backed engines
            inherit their kind's shard wrapper.  Both paths execute the
            swap where the plan's engine runs — the CNN trunk via
            build_apply, the LM stack via the rowexec hooks inside the
            jitted step (e.g. gemma's local layers run the flash-SWA op
            under a kernelized seq_swa_pallas plan).
* residency: add --residency host (or recompute): the resolved plan
            carries a ResidencySpec and the carry-based engines place
            their inter-row boundary caches accordingly — host offload
            with double-buffered prefetch, or BP-side recomputation.
            Executes on both paths: the CNN row-program executor applies
            the policy to the SD caches, and the LM carried chunk scans
            (SSD / xLSTM state) route through the same executor, with
            fp_row/bp_row spans in the obs trace to show for it.
            Composes with --mesh and --kernel.

Checkpoints + metrics land in --out.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ckpt import store
from repro.data.pipeline import (
    ImageDataset, ImageDatasetConfig, TokenDataset, TokenDatasetConfig,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import convnext, resnet, vgg
from repro.obs.audit import measure_step, plan_audit
from repro.obs.cli import add_obs_args, configure_from_args, profiled
from repro.obs.steplog import StepLog
from repro.optim.adamw import (
    AdamWConfig, SGDConfig, adamw_init, adamw_update, sgd_init, sgd_update,
    warmup_cosine,
)


def _resolve_plan(args, key_fields, solve):
    """Resolve a plan through the persistent cache when ``--plan-cache``
    is set, else solve directly.

    ``solve(table)`` performs the actual planner solve; ``table`` is the
    calibrated :class:`CostTable` under the cache directory (None without
    a cache — behaviour then matches the static pre-cost-model path).  A
    cache hit replays the stored plan JSON without calling ``solve`` at
    all (zero planner solves, visible in the obs counters); a stale
    cost-table version is a miss, so cached decisions never outlive the
    measurements they were priced with."""
    if not getattr(args, "plan_cache", ""):
        return solve(None)
    from repro.exec import cached_plan, load_or_calibrate
    from repro.exec.costmodel import hardware_fingerprint
    table = load_or_calibrate(args.plan_cache)
    key_fields = dict(key_fields, fingerprint=hardware_fingerprint())
    plan, hit, key = cached_plan(args.plan_cache, key_fields,
                                 lambda: solve(table),
                                 cost_version=table.version())
    print(f"plan cache: {'hit' if hit else 'miss'} key={key}")
    return plan


def _audit_step(step_fn, plan, source_extra, *step_args,
                source="train_step", est_bytes=None):
    """Measure the compiled step's peak bytes against the plan estimate
    (obs sessions only — AOT-lowering the step is a real compile).
    ``est_bytes`` overrides the plan's per-device estimate when the
    comparable quantity includes terms outside the plan's solve (the LM
    path adds the paper's ξ — params/grads/optimizer state — so the
    train_step_lm ratio carries pricing signal and can be gated)."""
    if plan is None or not obs.enabled():
        return None
    measured = measure_step(step_fn, *step_args)
    if measured is None:
        return None
    rec = plan_audit(plan, measured, source, extra=source_extra,
                     est_bytes=est_bytes)
    ratio = rec["ratio"]
    print(f"plan audit: est/dev {rec['est_bytes_per_device']} "
          f"measured peak {measured['peak_bytes']}"
          + (f" ratio {ratio:.3f}" if ratio is not None else ""))
    return rec


def _lm_batch(ds, cfg, args, step: int) -> dict:
    """The LM trainer's batch for ``step``, on the device."""
    hb = ds.batch_at(step)
    batch = {"tokens": jnp.asarray(hb["tokens"]),
             "labels": jnp.asarray(hb["labels"])}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros(
            (args.batch, cfg.n_frontend_tokens, cfg.frontend_dim),
            jnp.float32)
    if cfg.family == "encdec":
        batch = {"frames": jnp.asarray(
                    np.random.default_rng((args.seed, step)).normal(
                        0, 1, (args.batch, args.seq, cfg.d_model))
                    .astype(np.float32)),
                 "tokens": batch["tokens"], "labels": batch["labels"]}
    return batch


def train_lm(args):
    if args.batch is None:
        args.batch = 8
    from repro.configs import get_config, get_reduced
    from repro.exec import MeshSpec, Planner, ResidencySpec
    from repro.models.lm import model as LM
    from repro.models.lm import encdec as ED
    from repro.launch.steps import make_train_step

    mesh_spec = MeshSpec.parse(args.mesh) if args.mesh else None
    cfg = get_reduced(args.arch) if args.preset == "reduced" \
        else get_config(args.arch)
    if args.row_chunks:
        cfg = dataclasses.replace(cfg, row_chunks=args.row_chunks)
    plan = None
    wants_plan = args.budget_gb is not None or args.residency or args.kernel
    if wants_plan and not args.row_chunks:  # explicit --row-chunks wins
        # budget-driven sequence-axis plan: pick the chunk count (Eq. 7
        # along the token axis, per-device under --mesh) and engine from
        # the layer pattern; --kernel kernelizes the same plan.  The step
        # below executes it via build_apply — no cfg mutation here.
        residency_spec = ResidencySpec.parse(args.residency)
        plan = _resolve_plan(
            args,
            dict(mode="lm", arch=cfg.name, preset=args.preset,
                 batch=args.batch, seq=args.seq, budget_gb=args.budget_gb,
                 mesh=args.mesh, residency=args.residency,
                 kernel=args.kernel),
            lambda table: Planner.for_model(
                cfg, args.batch, args.seq,
                budget=int((args.budget_gb or 0.0) * 2**30),
                mesh=mesh_spec, residency=residency_spec,
                kernel=args.kernel or None))
        print("plan:", plan.describe())
    key = jax.random.PRNGKey(args.seed)
    init = ED.init_encdec if cfg.family == "encdec" else LM.init_lm
    params = init(key, cfg)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    row_chunks = plan.n_rows if plan is not None else cfg.row_chunks
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"row_chunks={row_chunks} remat={cfg.remat}"
          + (f" mesh={mesh_spec.describe()}" if mesh_spec else ""))

    opt_cfg = AdamWConfig(lr=args.lr)
    state = {"params": params, "opt": adamw_init(params)}
    if mesh_spec is not None:
        # sharded step: params/opt by the LM rules, batch over the data
        # axis — the same spec trees the dry-run lowers with
        from repro.launch.mesh import build_mesh
        from repro.launch.steps import (
            ShapeSpec, batch_sharding, batch_specs, make_shape_ctx,
            state_sharding,
        )
        mesh = build_mesh(mesh_spec)
        shape_spec = ShapeSpec("cli", "train", args.seq, args.batch)
        ctx = make_shape_ctx(mesh, cfg, shape_spec)
        st_shard = state_sharding(ctx, state)
        b_shard = batch_sharding(ctx, batch_specs(cfg, shape_spec))
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, ctx=ctx, plan=plan),
                          in_shardings=(st_shard, b_shard),
                          out_shardings=(st_shard, None),
                          donate_argnums=(0,))
    else:
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, plan=plan),
                          donate_argnums=(0,))

    ds = TokenDataset(TokenDatasetConfig(vocab=cfg.vocab, seq_len=args.seq,
                                         batch=args.batch, seed=args.seed))
    os.makedirs(args.out, exist_ok=True)
    steplog = StepLog("train")
    audit = None
    t0 = time.time()
    for step in range(args.steps):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=step):
            with obs.scope("data"):
                batch = _lm_batch(ds, cfg, args, step)
            if step == 0:
                # audit before the first call: donated state buffers are
                # still live, and lowering only reads avals anyway.  The
                # plan prices the activation / sequence-chunk term;
                # adding the paper's ξ (params + grads + optimizer
                # moments, all fp32 beside the activations) makes the
                # estimate comparable to the step's measured peak, so
                # train_step_lm is a gated source now that the plan is
                # what actually executes
                est = None
                if plan is not None:
                    xi = 4 * sum(l.nbytes
                                 for l in jax.tree.leaves(state["params"]))
                    est = plan.est_bytes_per_device + xi
                audit = _audit_step(step_fn, plan,
                                    {"arch": cfg.name, "batch": args.batch,
                                     "seq": args.seq}, state, batch,
                                    source="train_step_lm", est_bytes=est)
            with obs.scope("dispatch"):
                state, metrics = step_fn(state, batch)
            if step == 0:
                # step 0 pays the compile: log it separately and restart
                # the clock so elapsed_s tracks steady-state step time
                with obs.scope("sync"):
                    jax.block_until_ready(metrics)
                compile_s = round(time.time() - t0, 1)
                t0 = time.time()
            if step % args.log_every == 0 or step == args.steps - 1:
                with obs.scope("sync"):
                    m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["elapsed_s"] = round(time.time() - t0, 1)
                if step == 0:
                    m["compile_s"] = compile_s
                steplog.log(m)
    if args.save:
        # sharded leaves save per-shard; the executed plan rides along as
        # a JSON sidecar so the checkpoint replays its own policy
        store.save(args.out, args.steps, state["params"], state["opt"],
                   {"arch": cfg.name}, plan=plan)
    steplog.dump(os.path.join(args.out, "train_log.json"),
                 arch=cfg.name, mode="lm",
                 plan=plan.to_dict() if plan is not None else None,
                 plan_audit=audit)
    return steplog.records


@dataclasses.dataclass
class CNNRun:
    """A CNN trainer ready to step: the resolved plan, the trainer's
    ``loss_fn(params, images, labels)``, the jitted SGD step
    ``step_fn(params, opt, images, labels) -> (params, opt, loss,
    metrics)``, its initial state and the dataset feeding it."""
    arch: str
    plan: object
    loss_fn: object
    step_fn: object
    params: dict
    opt: dict
    dataset: ImageDataset
    batch: int

    def batch_at(self, step: int):
        hb = self.dataset.batch_at(step)
        return jnp.asarray(hb["images"]), jnp.asarray(hb["labels"])


#: CNN arch -> ``(init(key, shape, width_mult, n_classes=, **options) ->
#: (modules, params), head_apply(head, feats))``; ``options`` are the
#: ``--layer-scale-init`` kind, which only some trunks take
CNN_TRUNKS = {
    "vgg16": (vgg.init_vgg16, vgg.head_apply),
    "resnet50": (resnet.init_resnet50, resnet.head_apply),
    "convnext_t": (convnext.init_convnext, convnext.head_apply),
}


def setup_cnn(args) -> CNNRun:
    """Build the CNN trainer: config, params, plan, the planned trunk and
    the jitted SGD step."""
    import importlib
    if args.arch not in CNN_TRUNKS:
        raise ValueError(f"unknown CNN arch {args.arch!r}; known: "
                         f"{sorted(CNN_TRUNKS)}")
    mod = importlib.import_module(f"repro.configs.{args.arch}")
    ccfg = mod.reduced() if args.preset == "reduced" else mod.CONFIG

    from repro.exec import MeshSpec, Planner, build_apply
    mesh_spec = MeshSpec.parse(args.mesh) if args.mesh else None
    key = jax.random.PRNGKey(args.seed)
    shape = (ccfg.image, ccfg.image, ccfg.channels)
    init, head_apply = CNN_TRUNKS[ccfg.arch]
    options = {}
    if args.layer_scale_init is not None:
        if ccfg.arch != "convnext_t":
            raise ValueError(f"--layer-scale-init: {ccfg.arch} has no layer "
                             "scale")
        options["layer_scale"] = args.layer_scale_init
    mods, params = init(key, shape, ccfg.width_mult, n_classes=ccfg.n_classes,
                        **options)

    # resolve the plan request: --budget-gb auto-selects engine+N via
    # Planner.for_budget; --strategy/--rows pin them; else the config's
    # PlanRequest decides.  None-sentinel checks: an explicit zero (e.g.
    # --rows 0 = planner's choice, --budget-gb 0 = unconstrained) is a
    # real override, only an omitted flag falls through to the config
    batch = args.batch or ccfg.batch
    req = ccfg.plan
    if args.budget_gb is not None:
        req = dataclasses.replace(req, engine="", n_rows=0,
                                  budget_gb=args.budget_gb)
    if args.strategy is not None:
        req = dataclasses.replace(req, engine=args.strategy)
    if args.rows is not None:
        req = dataclasses.replace(req, n_rows=args.rows)
    if args.kernel:
        req = dataclasses.replace(req, kernel=args.kernel)
    if args.residency:
        req = dataclasses.replace(req, residency=args.residency)
    # the paper's ξ: params + grads + optimizer state live beside activations
    xi = 3 * sum(int(np.prod(l.shape)) * 4 for l in jax.tree.leaves(params))
    plan = _resolve_plan(
        args,
        dict(mode="cnn", arch=ccfg.arch, preset=args.preset,
             image=ccfg.image, channels=ccfg.channels, batch=batch, xi=xi,
             engine=req.engine, n_rows=req.n_rows,
             budget_gb=req.budget_gb, n_segments=req.n_segments,
             mesh=args.mesh or req.mesh, kernel=req.kernel,
             residency=req.residency),
        lambda table: Planner(mods, shape, batch, xi=xi, mesh=mesh_spec,
                              cost_table=table).resolve(req))
    print("plan:", plan.describe())
    # plan.mesh makes build_apply wrap the engine in the data-parallel
    # shard wrapper; no sharding code in the trainer itself
    trunk_apply = build_apply(mods, plan)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={ccfg.arch} engine={plan.engine} N={plan.n_rows} "
          f"params={n_params/1e6:.1f}M image={ccfg.image}")

    def loss_fn(p, images, labels):
        with obs.scope("trunk"):
            feats = trunk_apply(p["trunk"], images)
        with obs.scope("head_loss"):
            logits = head_apply(p["head"], feats)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    opt_cfg = SGDConfig(lr=args.lr if args.lr != 3e-4 else 0.05)

    @jax.jit
    def step_fn(p, opt, images, labels):
        loss, g = jax.value_and_grad(loss_fn)(p, images, labels)
        with obs.scope("sgd_update"):
            p, opt, m = sgd_update(p, g, opt, opt_cfg)
        return p, opt, loss, m

    ds = ImageDataset(ImageDatasetConfig(
        h=ccfg.image, w=ccfg.image, c=ccfg.channels,
        n_classes=ccfg.n_classes, batch=batch,
        seed=args.seed))
    return CNNRun(ccfg.arch, plan, loss_fn, step_fn, params,
                  sgd_init(params), ds, batch)


def train_cnn(args):
    run = setup_cnn(args)
    params, opt = run.params, run.opt
    os.makedirs(args.out, exist_ok=True)
    steplog = StepLog("train")
    audit = None
    t0 = time.time()
    for step in range(args.steps):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=step):
            with obs.scope("data"):
                images, labels = run.batch_at(step)
            if step == 0:
                audit = _audit_step(run.step_fn, run.plan,
                                    {"arch": run.arch, "batch": run.batch},
                                    params, opt, images, labels)
            with obs.scope("dispatch"):
                params, opt, loss, m = run.step_fn(params, opt, images,
                                                   labels)
            if step % args.log_every == 0 or step == args.steps - 1:
                with obs.scope("sync"):
                    loss = float(loss)
                steplog.log({"step": step, "loss": loss,
                             "elapsed_s": round(time.time() - t0, 1)})
    steplog.dump(os.path.join(args.out, "train_log.json"),
                 arch=run.arch, mode="cnn", plan=run.plan.to_dict(),
                 plan_audit=audit)
    return steplog.records


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="reduced", choices=["reduced", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: the CNN config's own, "
                         "8 for LM archs)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--row-chunks", type=int, default=0)
    ap.add_argument("--layer-scale-init", type=float, default=None,
                    help="ConvNeXt: gamma's initial value (default the "
                         "published 1e-6)")
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--budget-gb", type=float, default=None,
                    help="activation byte budget; Planner.for_budget "
                         "auto-selects engine and granularity under it "
                         "(per-device when combined with --mesh)")
    ap.add_argument("--mesh", default="",
                    help="device mesh spec, e.g. data=8 or data=4,model=2: "
                         "batch and budget divide over the data axis and "
                         "the resolved plan is sharded")
    ap.add_argument("--kernel", default="", choices=["", "lax", "pallas"],
                    help="kernel backend policy: 'pallas' swaps the "
                         "resolved engine for its Pallas-backed alternate "
                         "(rows as VMEM grid steps) when the tiling is "
                         "feasible, with automatic lax fallback otherwise; "
                         "executes on both paths — the CNN trunk via "
                         "build_apply, the LM stack via its rowexec hooks")
    ap.add_argument("--residency", default="",
                    choices=["", "device", "host", "recompute"],
                    help="boundary-cache residency policy for the carry-"
                         "based engines: 'host' offloads the inter-row "
                         "caches with double-buffered prefetch, "
                         "'recompute' regenerates them in BP; executes "
                         "on both paths — CNN SD caches and the LM "
                         "carried chunk scans (SSD / xLSTM state)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default="experiments/train")
    ap.add_argument("--save", action="store_true")
    from repro.exec.plancache import add_plan_cache_arg
    add_plan_cache_arg(ap)
    add_obs_args(ap)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    configure_from_args(args, tool="train", arch=args.arch,
                        preset=args.preset)
    try:
        with profiled(args):
            if args.arch in CNN_TRUNKS:
                train_cnn(args)
            else:
                train_lm(args)
    finally:
        obs.shutdown()


if __name__ == "__main__":
    main()

"""The Planner: Eqs. 7-16 as a policy solver producing ExecutionPlans.

Wraps the analytic memory model and N-solvers in :mod:`repro.core.rowplan`
and adds the two pieces the raw solvers don't have:

* segment-aware estimates for the checkpointed engines (Ckp / 2PS-H /
  OverL-H): live bytes = segment-input checkpoints + the worst segment's
  inner-strategy peak;
* strategy *selection* under a byte budget (``Planner.for_budget``),
  ordered by the paper's Table I / Fig. 8 trade-offs — prefer the engine
  with the least runtime overhead that fits:
  Base (no overhead) -> 2PS (no redundant compute, sequential rows) ->
  OverL (redundant halo compute, independent rows) -> 2PS-H / OverL-H
  (checkpointing admits larger N at extra recompute) -> Ckp (fallback).

Sequence-side planning (``Planner.for_model`` / ``for_budget_seq``) applies
the same Eq. 7 logic along the token axis: the live set of a chunked block
is the residual stream plus one chunk's widest sub-layer working set.

Sharded planning (``mesh=`` on the constructor and every ``for_*``): the
paper's budget M is *per accelerator*, so under a :class:`MeshSpec` the
solver divides batch and budget by the data-axis extent and solves the
same Eqs. 7-16 for what ONE device holds.  The emitted plan records global
numbers plus ``est_bytes_per_device`` and carries the mesh, so a logged
plan replays identically on any host (``plan.per_device()`` is the
single-device projection).

Residency-aware planning (``residency=`` on ``estimate`` / ``plan`` /
``solve`` / ``for_budget``): with a :class:`ResidencySpec` whose default
policy moves the 2PS boundary caches off-device, the Eq. 12 SD term —
the whole FP->BP pinned cache volume — is replaced by a *transit buffer*
(the largest single row's caches, times ``1 + prefetch_depth`` live
fetches for ``host`` or the 2-row recompute working set for
``recompute``), which flattens the skewed per-row profile the paper's
"two solutions" target.  :meth:`Planner.residencize` is the fallback
pass: given a budget the device-only solve rejects, it retries the
carry-based engines under host then recompute residency and records the
chosen policy and why under the ``residencized`` extra (the
``kernel_fallback`` pattern, in the fitting direction).  Pricing applies
the offloaded terms only when every cache leaves the device: a per-cache
override back to ``device`` keeps the full device-resident estimate, so
the planner is never optimistic about what stays pinned.
"""

from __future__ import annotations

import math
from dataclasses import replace as dataclasses_replace
from typing import Callable, Dict, Optional, Sequence, Tuple

from repro.core import rowplan as _rp
from repro.exec.plan import (
    ExecutionPlan, KernelSpec, MeshSpec, PlanRequest, ResidencySpec,
    StageSpec, batch_shards,
)

CNN_ENGINES = ("base", "ckp", "overlap", "twophase", "overlap_h",
               "twophase_h")
#: auto-selection order under a budget (least runtime overhead first)
BUDGET_PREFERENCE = ("base", "twophase", "overlap", "twophase_h",
                     "overlap_h", "ckp")
#: per-segment strategy of each checkpointed engine
INNER_STRATEGY = {"ckp": "column", "overlap_h": "overlap",
                  "twophase_h": "twophase"}
#: engines whose device-byte estimate changes under an offloading
#: ResidencySpec — the carry-based CNN engines (OverL replicates its halo
#: instead of carrying it, so residency cannot shrink it)
RESIDENCY_ENGINES = ("twophase", "twophase_h")


def _offloads(residency: Optional[ResidencySpec]) -> bool:
    """True when the spec moves EVERY cache off-device (default host /
    recompute with no per-cache override back to device).  Pricing must
    never be optimistic: a spec that pins some caches on device keeps the
    full device-resident estimate — the offloaded pricing applies only
    when the whole SD volume actually leaves."""
    return residency is not None and residency.default != "device" \
        and all(p != "device" for _, p in residency.placements)


def _count_solve() -> None:
    """Bump the ``planner.solves`` obs counter (a no-op without an active
    obs session).  Every public solve entry point calls this, which is
    what lets CI assert "plan-cache hit => zero planner solves" from the
    metrics dump alone."""
    from repro import obs
    obs.counter("planner.solves").inc()

#: lax engine -> its pallas-backed alternate with the SAME call signature
#: (base and overlap both map to overlap_pallas: the kernel's row tiling is
#: internal, so its full-tensor apply is a drop-in for either)
PALLAS_ALTERNATE = {"base": "overlap_pallas", "overlap": "overlap_pallas",
                    "seq_swa_overlap": "seq_swa_pallas"}
PALLAS_ENGINES = ("overlap_pallas", "seq_swa_pallas", "seq_ssd_pallas")
#: per-row-block working-set ceiling (one TPU core's VMEM)
PALLAS_VMEM_LIMIT = 16 * 2**20


def derive_segments(modules: Sequence, h0: int, inner: str, n_rows: int,
                    n_segments: Optional[int]
                    ) -> Tuple[Tuple[int, int, int], ...]:
    """The one segmentation rule shared by planner estimates and engine
    builders: sqrt(L) even cuts with per-segment granularity caps
    (Table I).  Returns (start, end, n_rows) triples."""
    from repro.core.hybrid import auto_segments, max_rows_per_segment
    cuts = auto_segments(len(modules), n_segments)
    if inner == "column":
        return tuple((a, b, 1) for a, b in cuts)
    caps = max_rows_per_segment(modules, h0, cuts, inner)
    return tuple((a, b, max(1, min(n_rows, cap)))
                 for (a, b), cap in zip(cuts, caps))


# ---------------------------------------------------------------------------
# Kernel-execution policy: lax <-> pallas engine selection under VMEM
# ---------------------------------------------------------------------------


def _pallas_infeasible(target: str, plan: ExecutionPlan, spec: KernelSpec,
                       modules: Optional[Sequence],
                       vmem_limit: int) -> Tuple[str, dict]:
    """``(reason, pricing)``: why ``target`` cannot run ``spec``'s tiling
    ("" when it can) plus the VMEM pricing extras to record on the plan.

    CNN pricing walks the trunk's shape chain (``conv_tiles``) once: a
    conv layer counts as pallas-eligible when the halo precondition holds
    and its per-row-block working set fits ``vmem_limit``; MXU alignment
    (``good_tiling``) is additionally required when the spec resolves to a
    compiled (non-interpret) run — on the interpreter there is no MXU, so
    alignment stays advisory and CPU CI exercises the kernels regardless
    of toy channel counts.  Sequence pricing checks tile divisibility
    against the plan's ``seq`` extra (required: the kernels *assert*
    divisibility at call time, so an unvalidated spec must fall back
    rather than crash inside jit) and the swa working set via the plan's
    ``head_dim``.
    """
    from repro.kernels.ops import resolve_interpret

    if target == "overlap_pallas":
        if plan.in_shape is None:
            return "plan has no in_shape to tile over", {}
        if modules is None:
            return "module list unavailable for VMEM pricing", {}
        from repro.exec.pallas_engines import conv_tiles
        from repro.kernels.conv2d_rows import good_tiling
        need_aligned = not resolve_interpret(spec.interpret)
        n_ok, n_aligned, worst = 0, 0, 0
        for m, shape, out, eligible, vmem in conv_tiles(
                modules, plan.in_shape, spec, plan.dtype_bytes):
            if not eligible:
                continue
            n_ok += 1
            worst = max(worst, vmem)
            n_aligned += good_tiling(shape[2], out[2])
        pricing = {"kernel_vmem_bytes": worst, "kernel_layers": n_ok}
        if not n_ok:
            return (f"no conv layer admits the halo precondition at "
                    f"block_h={spec.block_h}"), {}
        if worst > vmem_limit:
            return (f"row-block VMEM {worst} exceeds the "
                    f"{vmem_limit}-byte working-set limit"), {}
        if need_aligned and not n_aligned:
            return ("no MXU-aligned conv layer (good_tiling) for a "
                    "compiled run"), {}
        return "", pricing
    seq = int(plan.get("seq", 0))
    if not seq:
        return (f"plan has no 'seq' extra to validate {target!r} tiling "
                f"against"), {}
    if target == "seq_swa_pallas":
        bq, bk = min(spec.bq, seq), min(spec.bk, seq)
        if seq % bq or seq % bk or bk > bq or bq % bk:
            return (f"swa tiling bq={bq} bk={bk} does not tile seq={seq} "
                    f"(need seq % bq == seq % bk == bq % bk == 0, "
                    f"bk <= bq)"), {}
        d = int(plan.get("head_dim", 0))
        if d:
            from repro.kernels.swa_attention import vmem_bytes as swa_vmem
            if swa_vmem(bq, bk, d) > vmem_limit:
                return (f"swa row-block VMEM {swa_vmem(bq, bk, d)} "
                        f"exceeds the {vmem_limit}-byte working-set "
                        f"limit"), {}
            return "", {"kernel_vmem_bytes": swa_vmem(bq, bk, d)}
        return "", {}
    if target == "seq_ssd_pallas":
        if seq % min(spec.chunk, seq):
            return (f"ssd chunk={min(spec.chunk, seq)} does not divide "
                    f"seq={seq}"), {}
        return "", {}
    return f"engine {plan.engine!r} has no pallas alternate", {}


#: pallas engine -> candidate_tiles() enumeration kind
_TILE_KIND = {"overlap_pallas": "conv", "seq_swa_pallas": "swa",
              "seq_ssd_pallas": "ssd"}


def _tile_candidates(target: str, plan: ExecutionPlan) -> tuple:
    """The deterministic tile search space for ``target`` against this
    plan's geometry — one enumeration (``repro.kernels.ops.
    candidate_tiles``) shared by kernelize's retile pass and
    :meth:`Planner.autotune_kernel`, so both walk the same candidates in
    the same tie-break order."""
    from repro.kernels.ops import candidate_tiles
    kind = _TILE_KIND[target]
    if kind == "conv":
        h = plan.in_shape[0] if plan.in_shape else 0
        return candidate_tiles(kind, h_out=h)
    return candidate_tiles(kind, seq=int(plan.get("seq", 0)))


def kernelize_plan(plan: ExecutionPlan, spec, modules: Optional[Sequence]
                   = None, vmem_limit: int = PALLAS_VMEM_LIMIT
                   ) -> ExecutionPlan:
    """Apply a kernel-execution policy to a resolved plan.

    ``spec`` may be a :class:`KernelSpec` or a bare backend string.  With
    the lax backend the spec is simply attached.  With the pallas backend
    the plan's engine is swapped for its kernel-backed alternate
    (``PALLAS_ALTERNATE``) when the tiling is feasible; otherwise the plan
    keeps its lax engine (or, for an engine that is already pallas, flips
    the spec's backend to lax — every pallas engine carries the reference
    path internally) and records why under the ``kernel_fallback`` extra.

    A bare ``"pallas"`` string means "any feasible tiling": when the
    default tiles are rejected, the deterministic ``candidate_tiles``
    enumeration is searched and the first feasible candidate wins,
    recorded under the ``kernel_retile`` extra.  An explicit
    :class:`KernelSpec` pins its tiles exactly — infeasible means lax
    fallback, never a silent re-tile.  Estimates are untouched: kernel
    tiling changes *where* a row's working set lives (VMEM vs HBM), not
    the Eq. 7 activation accounting.
    """
    retile = isinstance(spec, str)
    if retile:
        spec = KernelSpec(backend=spec)
    if spec.backend != "pallas":
        return dataclasses_replace(plan, kernel=spec)
    target = PALLAS_ALTERNATE.get(plan.engine, plan.engine)
    if target not in PALLAS_ENGINES:
        return _kernel_fallback(
            plan, spec, f"engine {plan.engine!r} has no pallas alternate")
    reason, pricing = _pallas_infeasible(target, plan, spec, modules,
                                         vmem_limit)
    if reason and retile:
        for tiles in _tile_candidates(target, plan):
            cand = dataclasses_replace(spec, **tiles)
            if cand == spec:
                continue  # the default already failed above
            r2, p2 = _pallas_infeasible(target, plan, cand, modules,
                                        vmem_limit)
            if not r2:
                out = dataclasses_replace(plan, engine=target, kernel=cand)
                return out.with_extras(
                    kernel_retile=(f"default tiling infeasible ({reason}); "
                                   f"first feasible candidate "
                                   f"{_fmt_tiles(tiles)}"),
                    **p2)
        return _kernel_fallback(
            plan, spec, f"{reason}; no candidate tiling feasible either")
    if reason:
        return _kernel_fallback(plan, spec, reason)
    out = dataclasses_replace(plan, engine=target, kernel=spec)
    if pricing:
        out = out.with_extras(**pricing)
    return out


def _fmt_tiles(tiles: dict) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(tiles.items()))


def _kernel_fallback(plan: ExecutionPlan, spec: KernelSpec,
                     reason: str) -> ExecutionPlan:
    lax_spec = dataclasses_replace(spec, backend="lax")
    return dataclasses_replace(
        plan.with_extras(kernel_fallback=reason), kernel=lax_spec)


# ---------------------------------------------------------------------------
# Serving-side estimates: decode-slot bytes (policy half of repro.serve)
# ---------------------------------------------------------------------------

#: per-layer-kind decode cache byte estimators: fn(cfg, max_len, db) -> bytes
#: for ONE slot (one batch element).  repro.serve.cache_pool registers the
#: matching init mechanism; a new cache kind plugs into serving by adding an
#: entry to both (see ROADMAP "Paged + quantised serving").
#:
#: Keys come in two forms: a bare layer kind ("attn", "mamba", ...) prices
#: that layer's cache under the default contiguous ("full") pool, and a
#: qualified "<cache_kind>/<layer_kind>" key ("paged_kv/attn",
#: "quant_kv/attn") overrides it under an alternative pool cache kind —
#: lookups try the qualified key first and fall back to the bare one, so a
#: pool kind only overrides the layers it actually changes (ring-window
#: 'local' caches and SSM states stay slot-resident under paging).
SERVE_CACHE_BYTES: Dict[str, Callable] = {}


def register_cache_bytes(kind: str, fn: Optional[Callable] = None):
    """Register a per-slot byte estimator for a decode cache kind."""
    def _do(f):
        if kind in SERVE_CACHE_BYTES:
            raise ValueError(f"cache kind {kind!r} already registered")
        SERVE_CACHE_BYTES[kind] = f
        return f

    if fn is not None:
        return _do(fn)
    return _do


def _kv_bytes(cfg, cache_len: int, db: int) -> int:
    # k + v (cache_len, KV, hd) each, + the int32 "pos" scalar per slot
    return 2 * cache_len * cfg.n_kv_heads * cfg.head_dim * db + 4


register_cache_bytes(
    "attn", lambda cfg, max_len, db: _kv_bytes(cfg, max_len, db))
for _k in ("global", "shared_attn", "moe"):
    register_cache_bytes(_k, SERVE_CACHE_BYTES["attn"])
register_cache_bytes(
    "local", lambda cfg, max_len, db: _kv_bytes(
        cfg, min(cfg.sliding_window, max_len), db))


@register_cache_bytes("mamba")
def _mamba_state_bytes(cfg, max_len, db):
    inner = cfg.ssm_expand * cfg.d_model
    heads = cfg.ssm_heads or cfg.n_heads
    state_n = cfg.ssm_state or 64
    h = heads * (inner // heads) * state_n * 4          # fp32 state
    conv = (cfg.conv_k - 1) * (inner + 2 * state_n) * db
    return h + conv


@register_cache_bytes("mlstm")
def _mlstm_state_bytes(cfg, max_len, db):
    H = cfg.n_heads
    hd = (cfg.ssm_expand * cfg.d_model) // H
    return 4 * (H * hd * hd + H * hd + H)               # C, n, m (fp32)


register_cache_bytes(
    "slstm", lambda cfg, max_len, db: 4 * 4 * cfg.d_model)  # c,n,h,m fp32


# -- paged_kv: full-attention K/V rows live in the shared page pool, so a
#    slot's *resident* decode state shrinks to the int32 "pos" scalar (the
#    block-table row is host-side numpy bookkeeping, not device bytes);
#    per-page bytes are priced separately by Planner.page_bytes
for _k in ("attn", "global", "shared_attn", "moe"):
    register_cache_bytes(f"paged_kv/{_k}", lambda cfg, max_len, db: 4)


def _quant_kv_bytes(cfg, max_len, db):
    # int8 k + v codes, one fp32 scale per (position, kv-head) block, + pos
    rows = max_len * cfg.n_kv_heads
    return 2 * rows * cfg.head_dim + 2 * rows * 4 + 4


for _k in ("attn", "global", "shared_attn", "moe"):
    register_cache_bytes(f"quant_kv/{_k}", _quant_kv_bytes)


def serve_cache_kinds() -> Tuple[str, ...]:
    """Registered pool cache kinds: "full" plus every qualified prefix —
    a third-party kind becomes known the moment it registers a
    "<kind>/<layer>" estimator."""
    kinds = {"full"}
    kinds.update(k.split("/", 1)[0] for k in SERVE_CACHE_BYTES if "/" in k)
    return tuple(sorted(kinds))


class _ServePlannerMixin:
    """decode_slot_bytes / for_serve, mixed into :class:`Planner` below
    (kept separate only to keep the CNN solver block readable)."""

    @staticmethod
    def decode_slot_bytes(cfg, max_len: int, enc_len: int = 0,
                          cache_kind: str = "full") -> int:
        """Decode-state bytes ONE request pins for its whole lifetime: KV
        rows for attention kinds (ring-capped for 'local'), recurrent state
        for SSM kinds, + cross-attention K/V for enc-dec.  This is the
        Eq. 7 accounting applied to serving — decode slots are the rows,
        and the slot count is the granularity N the budget buys.

        ``cache_kind`` routes each layer kind through its qualified
        "<cache_kind>/<layer_kind>" estimator when one is registered
        (falling back to the contiguous estimator otherwise), so under
        ``"paged_kv"`` this is the slot's *resident* bytes — the shared
        page pool is priced separately via :meth:`page_bytes`."""
        db = 2 if cfg.dtype == "bfloat16" else 4
        if cfg.family == "encdec":
            if cache_kind != "full":
                raise ValueError(
                    f"cache kind {cache_kind!r} does not support enc-dec "
                    f"pools (cross-attention caches are precomputed "
                    f"whole); use cache_kind='full'")
            # decoder layers: self-attn KV + precomputed cross K/V (no pos)
            cross = 2 * enc_len * cfg.n_kv_heads * cfg.head_dim * db
            return cfg.n_layers * (_kv_bytes(cfg, max_len, db) + cross)
        total = 0
        for kind in cfg.layer_kinds():
            fn = SERVE_CACHE_BYTES.get(f"{cache_kind}/{kind}") \
                if cache_kind != "full" else None
            if fn is None:
                try:
                    fn = SERVE_CACHE_BYTES[kind]
                except KeyError:
                    raise KeyError(
                        f"no decode-cache byte estimator for layer kind "
                        f"{kind!r}; register one with "
                        f"repro.exec.planner.register_cache_bytes") from None
            total += fn(cfg, max_len, db)
        return total

    @staticmethod
    def page_bytes(cfg, page_size: int) -> int:
        """Marginal device bytes ONE page adds to a ``paged_kv`` pool: a
        (page_size, kv_heads, head_dim) K and V tile per paged layer —
        layers whose kind has a "paged_kv/<kind>" estimator registered;
        ring-window and state kinds stay slot-resident and contribute
        nothing.  Exact against ``jax.eval_shape`` of the pool init (the
        ``decode_slot_bytes`` contract, per page)."""
        db = 2 if cfg.dtype == "bfloat16" else 4
        n = sum(1 for kind in cfg.layer_kinds()
                if f"paged_kv/{kind}" in SERVE_CACHE_BYTES)
        return n * 2 * page_size * cfg.n_kv_heads * cfg.head_dim * db

    @classmethod
    def for_serve(cls, cfg, max_len: int, budget: int = 0,
                  enc_len: int = 0, n_slots: int = 0,
                  n_max: int = 256, mesh=None, cache_kind: str = "full",
                  page_size: int = 16, avg_len: int = 0, n_pages: int = 0,
                  decode_residency=None,
                  decode_batch: int = 0) -> ExecutionPlan:
        """Size the decode cache pool: the largest slot count whose pinned
        decode state fits ``budget`` (or an explicit ``n_slots``).  Returns
        an ``engine="serve_pool"`` plan; ``extras`` carry the pool geometry
        the mechanism side (repro.serve.cache_pool) honours verbatim.

        ``cache_kind`` picks the pool's storage layout (any kind from
        :func:`serve_cache_kinds`): ``"full"`` is the contiguous
        worst-case pool, ``"quant_kv"`` shrinks each slot to int8 codes +
        scales, and ``"paged_kv"`` splits a slot into tiny resident state
        plus pages from a shared pool — the budget then buys
        ``avg_len``-sized page shares (ceil(avg_len / page_size) pages per
        expected request) instead of ``max_len`` worst cases, which is
        exactly why a paged pool admits more concurrent requests at mixed
        lengths.  ``n_pages`` pins the page-pool size explicitly
        (default: worst case under pinned ``n_slots``, the budget
        remainder otherwise).

        ``decode_residency`` (a :class:`ResidencySpec` or its string form)
        extends the residency vocabulary to decode state: under ``"host"``
        the pool buffers live in host memory and only the hot decode
        cohort — ``decode_batch`` slots, fetched one tick ahead — is
        device-resident, so the device estimate becomes the transit
        working set (``(1 + prefetch_depth) * decode_batch`` dense slots)
        and the budget stops bounding the slot count (host bytes are
        recorded under the ``host_bytes`` extra).

        With ``mesh=`` decode slots shard across the data axis: the global
        ``budget`` is divided by the batch extent to get each device's
        slice, each device pins the ``slots_per_device`` slots that slice
        buys, and the global slot count is their product (rounded up to a
        multiple of the extent when ``n_slots`` is pinned explicitly, so
        the pool's slot axis always divides evenly).  Paged/quant pools
        and decode-state residency are single-host for now."""
        _count_solve()
        known = serve_cache_kinds()
        if cache_kind not in known:
            raise KeyError(
                f"unknown pool cache kind {cache_kind!r}; known: "
                f"{list(known)} — register a '<kind>/<layer>' estimator "
                f"with repro.exec.planner.register_cache_bytes and the "
                f"matching init/pool with repro.serve.cache_pool")
        if isinstance(decode_residency, str):
            decode_residency = ResidencySpec.parse(decode_residency)
        if decode_residency is not None \
                and decode_residency.default == "recompute":
            raise ValueError("decode state cannot be recomputed (tokens "
                             "depend on it); use 'host' or 'device' "
                             "decode residency")
        shards = mesh.batch_extent if mesh is not None else 1
        if shards > 1 and (cache_kind != "full"
                           or decode_residency is not None):
            raise ValueError(
                f"cache kind {cache_kind!r} / decode-state residency "
                f"pools are single-host; drop mesh= or use the default "
                f"contiguous kind")
        host = decode_residency is not None \
            and decode_residency.default == "host"
        slot = cls.decode_slot_bytes(cfg, max_len, enc_len,
                                     cache_kind=cache_kind)
        dev_budget = budget // shards
        extras = {"max_len": max_len, "slot_bytes": slot,
                  "cache_kind": cache_kind}
        if decode_batch:
            extras["decode_batch"] = int(decode_batch)
        if cache_kind == "paged_kv":
            pb = cls.page_bytes(cfg, page_size)
            if not pb:
                raise ValueError(
                    f"{cfg.name}: no paged-eligible layer kinds "
                    f"({sorted(set(cfg.layer_kinds()))}) — every cache is "
                    f"slot-resident, so paging buys nothing; use "
                    f"cache_kind='full'")
            mp = -(-max_len // page_size)
            avg = int(avg_len) or max_len
            app = max(1, -(-avg // page_size))  # expected pages per request
            if n_slots:
                per_dev = n_slots
                n_pages = n_pages or n_slots * mp    # worst case: no sharing
            elif budget:
                per_req = slot + app * pb
                per_dev = max(1, min(n_max, dev_budget // per_req))
                n_pages = n_pages or max(per_dev * app,
                                         (dev_budget - per_dev * slot) // pb)
            else:
                per_dev = 1
                n_pages = n_pages or mp
            n_pages = max(1, int(n_pages))
            per_dev_est = per_dev * slot + n_pages * pb
            n_slots = per_dev * shards               # shards == 1 here
            extras.update(page_size=int(page_size), n_pages=n_pages,
                          page_bytes=pb, avg_len=avg)
        else:
            if not n_slots:
                if budget:
                    per_dev = max(1, min(max(1, n_max // shards),
                                         dev_budget // slot))
                else:
                    per_dev = 1
                n_slots = per_dev * shards
            else:
                per_dev = -(-n_slots // shards)   # ceil: even slot sharding
                n_slots = per_dev * shards
            per_dev_est = per_dev * slot
        if host:
            # the pool lives in host memory; the device holds the hot
            # cohort's dense transit view (current fetch + prefetch_depth
            # in flight), so that is what the budget must cover
            dense_slot = cls.decode_slot_bytes(cfg, max_len, enc_len)
            hot = int(decode_batch) or per_dev
            extras["host_bytes"] = per_dev_est
            per_dev_est = min(per_dev, hot * (
                1 + decode_residency.prefetch_depth)) * dense_slot
        extras["slots_per_device"] = per_dev
        if cfg.family == "encdec":
            extras["enc_len"] = enc_len
        return ExecutionPlan(
            engine="serve_pool", n_rows=n_slots, in_shape=None,
            batch=n_slots, dtype_bytes=2 if cfg.dtype == "bfloat16" else 4,
            est_bytes=per_dev_est * shards, est_bytes_per_device=per_dev_est,
            budget=budget,
            feasible=(budget == 0 or per_dev_est < dev_budget),
            mesh=mesh, residency=decode_residency,
            extras=tuple(extras.items()))


class Planner(_ServePlannerMixin):
    """Solves (engine, N, segments) for a CNN trunk under a byte budget.

    With ``mesh=`` the solve is per-device: estimates use the per-device
    batch (``batch // mesh.batch_extent``, the pod x data axes) and
    feasibility compares against the per-device budget
    (``budget // mesh.batch_extent``).  ``batch``/``est_bytes``/``budget``
    on the emitted plans stay global.
    """

    def __init__(self, modules: Sequence, in_shape: Tuple[int, int, int],
                 batch: int, dtype_bytes: int = 4, xi: int = 0,
                 n_max: int = 64, mesh: Optional[MeshSpec] = None,
                 cost_table=None):
        self.modules = list(modules)
        self.in_shape = tuple(in_shape)
        self.batch = batch
        self.dtype_bytes = dtype_bytes
        self.xi = xi                      # params/grads/workspace constant
        self.n_max = n_max
        self.mesh = mesh
        #: optional repro.exec.costmodel.CostTable: when set, budget-driven
        #: selection ranks feasible candidates by predicted step time
        #: (roofline) instead of the static Table-I order
        self.cost_table = cost_table
        shards = mesh.batch_extent if mesh is not None else 1
        if shards > 1 and batch % shards:
            raise ValueError(
                f"global batch {batch} does not divide over the mesh batch "
                f"axes ({'x'.join(mesh.batch_axes)}={shards}); pick a "
                f"divisible batch or a smaller data extent")
        #: what ONE device holds — every estimate below is denominated in
        #: this batch (xi is NOT divided: params/grads/opt replicate under
        #: pure data parallelism)
        self.dev_batch = batch // shards
        self.shards = shards

    # ------------------------------------------------------------------
    # estimates
    # ------------------------------------------------------------------
    def _shapes(self):
        return _rp.shape_chain(self.modules, self.in_shape)

    def _segments(self, n_rows: int, inner: str,
                  n_segments: Optional[int]) -> Tuple[Tuple[int, int, int], ...]:
        return derive_segments(self.modules, self.in_shape[0], inner,
                               n_rows, n_segments)

    def _twophase_offloaded(self, modules, in_shape, n_rows: int,
                            residency: ResidencySpec) -> int:
        """Device bytes of a 2PS block when its SD caches leave device
        memory: the Eq. 8 BP baseline plus the transit buffer — the
        largest single row's caches times the number of rows' worth that
        are concurrently device-resident (``1 + prefetch_depth`` in-flight
        fetches for host residency; producer + consumer of the serialized
        recompute chain for recompute)."""
        base = _rp.omega_bp(modules, in_shape, self.dev_batch, n_rows,
                            self.dtype_bytes)
        rows = _rp.twophase_cache_row_bytes(modules, in_shape,
                                            self.dev_batch, n_rows,
                                            self.dtype_bytes)
        buf = max(rows) if rows else 0
        # transit rows by policy, summed when a mixed spec uses both (the
        # in-flight fetches and the recompute chain's regenerated carry
        # can be live together — price the union, never the optimistic
        # default alone)
        policies = {residency.default} | {p for _, p in
                                          residency.placements}
        mult = 0
        if "host" in policies:
            mult += 1 + residency.prefetch_depth
        if "recompute" in policies:
            mult += 2
        # never price more transit rows than exist (N-1 importing rows):
        # at that point every cache is device-resident anyway
        mult = min(mult, max(1, n_rows - 1))
        return base + mult * buf

    def _estimate_segmented(self, segments, inner: str,
                            residency: Optional[ResidencySpec]
                            = None) -> int:
        """Checkpoint bytes (segment-input maps stay live FP->BP) + worst
        per-segment peak under the inner strategy.  Per-device bytes."""
        shapes = self._shapes()
        db, B = self.dtype_bytes, self.dev_batch
        ckpt = sum(B * shapes[a][0] * shapes[a][1] * shapes[a][2] * db
                   for a, _, _ in segments if a > 0)
        worst = 0
        for a, b, n in segments:
            sub = self.modules[a:b]
            sub_shape = shapes[a]
            if inner == "column":
                est = _rp.omega_column(sub, sub_shape, B, db)
            elif inner == "twophase" and _offloads(residency):
                est = self._twophase_offloaded(sub, sub_shape, n, residency)
            else:
                est = _rp.estimate_bytes(sub, sub_shape, B, inner, n, db)
            worst = max(worst, est)
        return ckpt + worst

    def estimate(self, engine: str, n_rows: int,
                 n_segments: Optional[int] = None,
                 segments: Tuple[Tuple[int, int, int], ...] = (),
                 residency: Optional[ResidencySpec] = None,
                 stage: Optional[StageSpec] = None) -> int:
        """Peak activation bytes ONE device holds (== global bytes when no
        mesh is set).  ``residency`` re-prices the carry-based engines'
        SD caches (see the module docstring); the other engines carry
        nothing, so their estimate is residency-invariant.  ``stage``
        routes ``"pipeline_rows"`` through the per-stage accounting
        (:meth:`estimate_staged`)."""
        if engine == "pipeline_rows":
            return self.estimate_staged(
                n_rows, stage or self._default_stage_spec())
        if engine in ("base",):
            return _rp.omega_column(self.modules, self.in_shape,
                                    self.dev_batch,
                                    self.dtype_bytes) + self.xi
        if engine in ("overlap", "twophase"):
            if engine == "twophase" and _offloads(residency):
                return self._twophase_offloaded(
                    self.modules, self.in_shape, n_rows, residency) + self.xi
            return _rp.estimate_bytes(self.modules, self.in_shape,
                                      self.dev_batch, engine, n_rows,
                                      self.dtype_bytes, self.xi)
        if engine in INNER_STRATEGY:
            inner = INNER_STRATEGY[engine]
            segs = segments or self._segments(n_rows, inner, n_segments)
            return self._estimate_segmented(segs, inner, residency) + self.xi
        raise ValueError(f"unknown CNN engine {engine!r}; known: "
                         f"{list(CNN_ENGINES)}")

    # ------------------------------------------------------------------
    # explicit plans
    # ------------------------------------------------------------------
    def plan(self, engine: str, n_rows: int = 1,
             n_segments: Optional[int] = None, budget: int = 0,
             residency: Optional[ResidencySpec] = None,
             stage: Optional[StageSpec] = None,
             **extras) -> ExecutionPlan:
        """Resolve an explicit (engine, N) request into a full plan with
        estimates and (for checkpointed engines) pinned segments.
        ``residency`` is both priced (carry-based engines) and recorded on
        the plan, so the emitted policy replays verbatim.  For
        ``"pipeline_rows"`` this delegates to :meth:`plan_staged` —
        ``stage`` pins the partition, default :meth:`_default_stage_spec`."""
        n_rows = max(1, n_rows)
        if engine == "pipeline_rows":
            return self.plan_staged(n_rows, stage, budget=budget,
                                    residency=residency, **extras)
        segments: Tuple[Tuple[int, int, int], ...] = ()
        if engine in INNER_STRATEGY:
            segments = self._segments(n_rows, INNER_STRATEGY[engine],
                                      n_segments)
        dev_est = self.estimate(engine, n_rows, n_segments, segments,
                                residency)
        dev_budget = budget // self.shards
        return ExecutionPlan(
            engine=engine, n_rows=n_rows, in_shape=self.in_shape,
            batch=self.batch, dtype_bytes=self.dtype_bytes,
            n_segments=n_segments, segments=segments,
            est_bytes=dev_est * self.shards, est_bytes_per_device=dev_est,
            budget=budget, feasible=(budget == 0 or dev_est < dev_budget),
            mesh=self.mesh, residency=residency,
            extras=tuple(extras.items()))

    # ------------------------------------------------------------------
    # staged (pipelined) plans: Eqs. 7-16 per stage over the model axis
    # ------------------------------------------------------------------
    def _default_stage_spec(self, n_stages: Optional[int] = None
                            ) -> StageSpec:
        """Even partition with S = the mesh's model extent when it has one
        (one stage per model shard), else 2 — capped at the module count."""
        if n_stages is None:
            model = self.mesh.model if self.mesh is not None else 1
            n_stages = model if model > 1 else 2
        return StageSpec.even(len(self.modules),
                              max(1, min(n_stages, len(self.modules))))

    def estimate_staged(self, n_rows: int, stage: StageSpec) -> int:
        """Per-device bytes of the pipelined schedule: the worst stage's
        peak.  A stage holds (a) its GPipe stash — the stage-input
        boundary activation, one full feature map at the stage's input
        level (stage 0 reads the batch input, which every engine already
        charges, so its stash is 0); (b) the OverL working set of its own
        sub-trunk at granularity N (rows are replicated-halo microbatches,
        Eq. 16 applied to the stage's module range); (c) its share of the
        params/grads/opt constant — xi divides by the model extent because
        each model shard holds only its stages' params."""
        if stage.n_modules != len(self.modules):
            raise ValueError(
                f"StageSpec covers {stage.n_modules} modules but the trunk "
                f"has {len(self.modules)}")
        shapes = self._shapes()
        db, B = self.dtype_bytes, self.dev_batch
        model = self.mesh.model if self.mesh is not None else 1
        xi_s = self.xi // max(1, model)
        worst = 0
        for a, b in stage.stages:
            stash = (B * shapes[a][0] * shapes[a][1] * shapes[a][2] * db
                     if a > 0 else 0)
            work = _rp.estimate_bytes(self.modules[a:b], shapes[a], B,
                                      "overlap", n_rows, db)
            worst = max(worst, stash + work + xi_s)
        return worst

    def plan_staged(self, n_rows: int, stage: Optional[StageSpec] = None,
                    budget: int = 0,
                    residency: Optional[ResidencySpec] = None,
                    **extras) -> ExecutionPlan:
        """Explicit ``pipeline_rows`` plan: N row microbatches through the
        given stage partition (default :meth:`_default_stage_spec`), with
        per-stage, per-device feasibility."""
        n_rows = max(1, n_rows)
        stage = stage or self._default_stage_spec()
        dev_est = self.estimate_staged(n_rows, stage)
        dev_budget = budget // self.shards
        return ExecutionPlan(
            engine="pipeline_rows", n_rows=n_rows, in_shape=self.in_shape,
            batch=self.batch, dtype_bytes=self.dtype_bytes,
            est_bytes=dev_est * self.shards, est_bytes_per_device=dev_est,
            budget=budget, feasible=(budget == 0 or dev_est < dev_budget),
            mesh=self.mesh, residency=residency, stage=stage,
            extras=tuple(extras.items()))

    def solve_staged(self, n_stages: Optional[int] = None, budget: int = 0,
                     residency: Optional[ResidencySpec] = None
                     ) -> ExecutionPlan:
        """min N s.t. the worst stage fits the per-device budget, at the
        even S-stage partition — the staged counterpart of :meth:`solve`;
        the smallest-estimate loser when nothing fits."""
        stage = self._default_stage_spec(n_stages)
        best: Optional[ExecutionPlan] = None
        for n in range(1, self.n_max + 1):
            try:
                p = self.plan_staged(n, stage, budget=budget,
                                     residency=residency)
            except ValueError:
                break  # N exceeds a stage's row-split bound; larger N too
            if p.feasible:
                return p
            if best is None or p.est_bytes < best.est_bytes:
                best = p
        return best

    def stagedize(self, plan: Optional[ExecutionPlan],
                  budget: Optional[int] = None,
                  residency: Optional[ResidencySpec] = None
                  ) -> Optional[ExecutionPlan]:
        """Fit a single-stage-infeasible plan by pipelining stages over
        the model axis — the model-parallel counterpart of
        :meth:`residencize`, run after it in ``for_budget``.

        Only fires when the mesh actually has a model extent to shard
        stages onto; tries S = 2 .. min(model extent, L) and returns the
        first feasible staged solve, recording the decision under the
        ``pipeline`` extra (the ``residencized`` pattern).  A feasible
        plan, a zero budget, or a data-only mesh return ``plan``
        unchanged."""
        if plan is None or plan.feasible:
            return plan
        budget = plan.budget if budget is None else budget
        model = self.mesh.model if self.mesh is not None else 1
        if not budget or model <= 1:
            return plan
        dev_budget = budget // self.shards
        for n_stages in range(2, min(model, len(self.modules)) + 1):
            p = self.solve_staged(n_stages, budget, residency=residency)
            if p is not None and p.feasible:
                return p.with_extras(pipeline=(
                    f"single-stage solve infeasible (best {plan.engine} "
                    f"needs {plan.est_bytes_per_device} B/device > budget "
                    f"{dev_budget}); S={n_stages} pipeline stages over the "
                    f"model axis fit at N={p.n_rows}"))
        return plan

    def kernelize(self, plan: ExecutionPlan, spec,
                  vmem_limit: int = PALLAS_VMEM_LIMIT) -> ExecutionPlan:
        """Apply a kernel backend to a plan, priced against this planner's
        module list — see :func:`kernelize_plan`."""
        return kernelize_plan(plan, spec, modules=self.modules,
                              vmem_limit=vmem_limit)

    def autotune_kernel(self, plan: ExecutionPlan, *, time_fn=None,
                        vmem_limit: int = PALLAS_VMEM_LIMIT,
                        base_spec: Optional[KernelSpec] = None
                        ) -> ExecutionPlan:
        """Search the KernelSpec tile geometry for ``plan``'s pallas
        alternate and return the plan kernelized with the fastest tiling.

        Candidates come from the same deterministic enumeration kernelize
        retiles over (``repro.kernels.ops.candidate_tiles``), filtered by
        the same ``vmem_bytes`` / halo / ``good_tiling`` pricers
        (:func:`_pallas_infeasible`), then *timed*: ``time_fn(candidate
        plan) -> us`` (default: an AOT ``measure_step`` wall-clock of the
        planner's own trunk forward at batch 1).  The minimum measured
        time wins; exact ties break toward the earlier candidate —
        enumeration order IS the tie-break, so the search is
        deterministic for a deterministic timer.  The winning plan
        records the search under the ``autotune`` / ``autotune_us``
        extras; when no candidate passes the pricers the plan falls back
        to lax with the usual ``kernel_fallback`` reason."""
        spec0 = base_spec or plan.kernel or KernelSpec(backend="pallas")
        spec0 = dataclasses_replace(spec0, backend="pallas")
        target = PALLAS_ALTERNATE.get(plan.engine, plan.engine)
        if target not in PALLAS_ENGINES:
            return _kernel_fallback(
                plan, spec0,
                f"engine {plan.engine!r} has no pallas alternate")
        feasible = []
        seen = set()
        for tiles in _tile_candidates(target, plan):
            spec = dataclasses_replace(spec0, **tiles)
            if spec in seen:
                continue
            seen.add(spec)
            reason, pricing = _pallas_infeasible(target, plan, spec,
                                                 self.modules, vmem_limit)
            if not reason:
                feasible.append((spec, pricing, tiles))
        if not feasible:
            return _kernel_fallback(
                plan, spec0,
                f"autotune: no tile candidate feasible for {target}")
        timer = time_fn if time_fn is not None \
            else self._default_kernel_timer()
        scored = []
        for idx, (spec, pricing, tiles) in enumerate(feasible):
            cand = dataclasses_replace(plan, engine=target, kernel=spec)
            scored.append((float(timer(cand)), idx, cand, pricing, tiles))
        scored.sort(key=lambda t: (t[0], t[1]))
        us, _, cand, pricing, tiles = scored[0]
        return cand.with_extras(
            autotune=(f"timed {len(feasible)} feasible of "
                      f"{len(seen)} tile candidates for {target}; best "
                      f"{_fmt_tiles(tiles)} at {us:.1f}us"),
            autotune_us=round(us, 3), **pricing)

    def _default_kernel_timer(self):
        """Wall-clock timer over this planner's own trunk: synthesized
        params, batch-1 forward, timed via the AOT ``measure_step`` path
        (compile once, median of the executed iterations)."""
        import jax
        import jax.numpy as jnp

        from repro.exec.registry import build_apply
        from repro.models.cnn.layers import init_trunk
        from repro.obs.audit import measure_step

        params, _ = init_trunk(self.modules, jax.random.PRNGKey(0),
                               self.in_shape)
        x = jnp.zeros((1,) + self.in_shape, jnp.float32)

        def timer(cand: ExecutionPlan) -> float:
            fn = build_apply(self.modules,
                             dataclasses_replace(cand, mesh=None))
            m = measure_step(fn, params, x, time_iters=2) or {}
            return float(m.get("wall_us", 0.0))

        return timer

    def resolve(self, request: PlanRequest) -> ExecutionPlan:
        """Turn a config-level :class:`PlanRequest` into a plan.  A
        ``request.mesh`` string ("data=8[,model=2]") overrides the
        planner's own mesh; ``request.kernel`` ("pallas"/"lax") applies
        the kernel-backend policy to whatever plan resolves;
        ``request.residency`` ("host"/"recompute"/"device") pins the
        boundary-cache residency policy (estimates re-priced for the
        carry-based engines)."""
        _count_solve()
        if request.mesh:
            mesh = MeshSpec.parse(request.mesh)
            if mesh != self.mesh:
                return Planner(self.modules, self.in_shape, self.batch,
                               self.dtype_bytes, self.xi, self.n_max,
                               mesh=mesh,
                               cost_table=self.cost_table).resolve(
                                   dataclasses_replace(request, mesh=""))
        plan = self._resolve(request, ResidencySpec.parse(request.residency))
        if request.kernel:
            plan = self.kernelize(plan, request.kernel)
        return plan

    def _resolve(self, request: PlanRequest,
                 residency: Optional[ResidencySpec] = None) -> ExecutionPlan:
        budget = int(request.budget_gb * 2**30)
        if request.engine and request.n_rows:
            return self.plan(request.engine, request.n_rows,
                             request.n_segments, budget=budget,
                             residency=residency)
        if request.engine:
            return self.solve(request.engine, budget,
                              n_segments=request.n_segments,
                              residency=residency)
        if request.n_rows:
            # engine auto, N pinned: first engine (Table I order) feasible
            # at exactly this granularity
            best: Optional[ExecutionPlan] = None
            from repro.core import twophase as _tp
            for engine in BUDGET_PREFERENCE:
                if engine in ("base", "ckp") and request.n_rows > 1:
                    continue  # granularity-free engines can't honour N
                try:
                    if engine == "twophase" and not _tp.validate_plan(
                            _tp.module_boundaries(self.modules,
                                                  self.in_shape[0],
                                                  request.n_rows)):
                        continue  # exceeds the 2PS granularity bound
                    p = self.plan(engine, request.n_rows,
                                  request.n_segments, budget=budget,
                                  residency=residency)
                except ValueError:  # N invalid for this engine's bounds
                    continue
                if p.feasible:
                    return p
                if best is None or p.est_bytes < best.est_bytes:
                    best = p
            if best is not None:
                return best
        return self.for_budget(self.modules, self.in_shape, self.batch,
                               budget, dtype_bytes=self.dtype_bytes,
                               xi=self.xi, n_max=self.n_max, mesh=self.mesh,
                               residency=residency,
                               cost_table=self.cost_table)

    # ------------------------------------------------------------------
    # budget-driven solving
    # ------------------------------------------------------------------
    def solve(self, engine: str, budget: int,
              n_segments: Optional[int] = None,
              residency: Optional[ResidencySpec] = None) -> ExecutionPlan:
        """min N s.t. estimate(engine, N) < budget (Eqs. 9/10/12/16 plus
        the Sec. IV validity bounds), as a plan.  Under a mesh the solve is
        per-device: per-device batch against per-device budget.  Under an
        offloading ``residency`` the 2PS estimates use the repriced SD
        terms, so the minimal N can be smaller than the device-only one."""
        if engine == "pipeline_rows":
            return self.solve_staged(budget=budget, residency=residency)
        if engine == "twophase" and _offloads(residency):
            # the repriced solve: the same validity-bounded scan solve_n
            # does, against the offloaded estimate
            return self._scan_n(engine, self._valid_twophase_ns(), budget,
                                residency=residency)
        if engine in ("base", "overlap", "twophase"):
            r = _rp.solve_n(self.modules, self.in_shape, self.dev_batch,
                            budget // self.shards, engine, self.dtype_bytes,
                            self.xi, self.n_max)
            return self.plan(engine, max(1, r.n_rows), budget=budget,
                             residency=residency)
        if engine == "ckp":  # granularity-free: one estimate
            return self.plan(engine, 1, n_segments, budget=budget,
                             residency=residency)
        # hybrid engines: per-segment granularity caps bound the search
        inner = INNER_STRATEGY[engine]
        caps = [cap for _, _, cap in segment_row_capacity(
            self.modules, self.in_shape[0], inner, n_segments)]
        return self._scan_n(engine,
                            range(1, min(self.n_max, max(caps)) + 1),
                            budget, n_segments, residency)

    def _valid_twophase_ns(self):
        """N = 1, 2, ... while the 2PS granularity bound admits N (the
        validity scan solve_n performs, factored out for the repriced
        residency solve)."""
        from repro.core import twophase as _tp
        for n in range(1, self.n_max + 1):
            if n > 1:
                try:
                    if not _tp.validate_plan(_tp.module_boundaries(
                            self.modules, self.in_shape[0], n)):
                        return
                except ValueError:
                    return
            yield n

    def _scan_n(self, engine: str, ns, budget: int,
                n_segments: Optional[int] = None,
                residency: Optional[ResidencySpec] = None
                ) -> Optional[ExecutionPlan]:
        """First feasible plan over the candidate granularities ``ns``;
        otherwise the smallest-estimate loser (estimates need not be
        monotonic in N — segment boundaries move and the residency
        transit multiplier saturates)."""
        best: Optional[ExecutionPlan] = None
        for n in ns:
            p = self.plan(engine, n, n_segments, budget=budget,
                          residency=residency)
            if p.feasible:
                return p
            if best is None or p.est_bytes < best.est_bytes:
                best = p
        return best

    def residencize(self, plan: ExecutionPlan,
                    budget: Optional[int] = None) -> ExecutionPlan:
        """Fit a device-infeasible plan by moving boundary caches off
        device — the fallback pass ``for_budget`` runs when the device-
        only solve rejects a budget.

        Retries the carry-based engines (the plan's own engine first when
        it is one) under ``host`` then ``recompute`` residency, in that
        order: host costs copies the inter-row prefetch hides, recompute
        costs O(N^2) extra row steps — the paper's "two solutions with
        different favorite scenarios".  The first feasible re-solve wins
        and records the chosen policy and why under the ``residencized``
        extra (the ``kernel_fallback`` pattern); if nothing fits, the
        original plan is returned unchanged."""
        budget = plan.budget if budget is None else budget
        if plan.feasible or not budget or _offloads(plan.residency):
            return plan
        candidates = list(RESIDENCY_ENGINES)
        if plan.engine in candidates:  # the rejected engine gets first try
            candidates.remove(plan.engine)
            candidates.insert(0, plan.engine)
        dev_budget = budget // self.shards
        for policy in ("host", "recompute"):
            spec = ResidencySpec(default=policy)
            for engine in candidates:
                p = self.solve(engine, budget, residency=spec)
                if p is not None and p.feasible:
                    return p.with_extras(residencized=(
                        f"device-only solve infeasible (best "
                        f"{plan.engine} needs {plan.est_bytes_per_device} "
                        f"B/device > budget {dev_budget}); {policy} "
                        f"residency of {engine} boundary caches fits at "
                        f"N={p.n_rows}"))
        return plan

    @classmethod
    def for_budget(cls, modules: Sequence, in_shape: Tuple[int, int, int],
                   batch: int, budget: int, dtype_bytes: int = 4,
                   xi: int = 0, n_max: int = 64,
                   candidates: Sequence[str] = BUDGET_PREFERENCE,
                   mesh: Optional[MeshSpec] = None,
                   residency: Optional[ResidencySpec] = None,
                   cost_table=None) -> ExecutionPlan:
        """Auto-select strategy *and* granularity under a byte budget.

        Without a ``cost_table``, tries ``candidates`` in order of
        increasing runtime overhead (Table I / Fig. 8) and returns the
        first feasible plan.  If no device-resident plan fits (and the
        caller didn't pin a residency policy), the :meth:`residencize`
        pass retries the carry-based engines with their boundary caches
        moved off device — the budgets the device-only solve rejects are
        exactly the ones host offload / recompute exist for.  When the
        mesh has a model extent, a still-infeasible result then goes
        through :meth:`stagedize`: S pipeline stages over the model axis,
        each holding 1/S of the params and one stage's working set.
        Failing everything, returns the infeasible plan with the smallest
        estimate so the caller can see how far over budget it is.

        With a ``cost_table`` (a :class:`repro.exec.costmodel.CostTable`)
        the static orders are replaced by a measured roofline: every
        feasible candidate — each engine under the pinned residency,
        plus the host- and recompute-offloaded carry engines when no
        residency is pinned — is priced via :meth:`predict_plan_us`
        (device-only compute vs offload copy bytes vs O(N^2) recompute
        FLOPs) and the minimum predicted step time wins, ties broken by
        the static preference order then smaller N.  The decision is
        recorded under the ``cost_model`` / ``predicted_step_us`` /
        ``cost_table_version`` extras (the ``kernel_fallback`` /
        ``residencized`` pattern).

        With ``mesh=`` both the batch and the budget are divided over the
        data axis (per-device solve); the returned plan carries the mesh.
        """
        _count_solve()
        planner = cls(modules, in_shape, batch, dtype_bytes, xi, n_max,
                      mesh=mesh, cost_table=cost_table)
        if cost_table is not None:
            return planner._for_budget_costed(budget, candidates,
                                              residency, cost_table)
        best: Optional[ExecutionPlan] = None
        for engine in candidates:
            p = planner.solve(engine, budget, residency=residency)
            if p.feasible:
                return p
            if best is None or p.est_bytes < best.est_bytes:
                best = p
        if residency is None:
            best = planner.residencize(best, budget)
        # the model-axis fallback: budgets neither the device-only solve
        # nor residency offload can fit may still pipeline into S stages
        return planner.stagedize(best, budget, residency)

    # ------------------------------------------------------------------
    # measured-cost selection (roofline over a calibrated CostTable)
    # ------------------------------------------------------------------
    def predict_plan_us(self, plan: ExecutionPlan, table) -> dict:
        """Roofline step-time prediction for ``plan`` under ``table``:
        ``{"us", "compute_us", "copy_us", "flops", "copy_bytes"}``.

        Compute side: one forward + ~2x backward over the trunk
        (:func:`repro.exec.costmodel.trunk_fwd_flops`), plus one extra
        forward for the checkpointed engines (segment recompute), plus
        the replicated-halo fraction for the OverL family, plus the
        O(N^2) forward-chain term — ``fwd * (N-1)/2`` — under recompute
        residency.  Copy side: the 2PS SD volume crosses the PCIe both
        ways under host residency, scaled by the audit-seeded
        byte-honesty ratio for the matching plan group.  A pipelined plan
        additionally stretches its compute by the GPipe fill/drain bubble
        ``1 + (S-1)/N``.  The step pays ``max(compute, copy)`` (prefetch
        hides copies behind the adjacent row) plus per-row dispatch
        overhead."""
        from repro.exec.costmodel import audit_ratio_key, trunk_fwd_flops

        fwd = trunk_fwd_flops(self.modules, self.in_shape, self.dev_batch)
        flops = 3.0 * fwd
        n = max(1, plan.n_rows)
        engine = plan.engine
        if engine in INNER_STRATEGY:  # segment recompute: one extra FP
            flops += fwd
        if engine in ("overlap", "overlap_h", "overlap_pallas",
                      "pipeline_rows") and n > 1:
            halo = _rp.overlap_halo_bytes(self.modules, self.in_shape,
                                          self.dev_batch, n,
                                          self.dtype_bytes)
            feat = sum(_rp.feature_bytes(self.modules, self.in_shape,
                                         self.dev_batch, self.dtype_bytes))
            if feat:
                flops += 3.0 * fwd * (halo / feat)  # redundant halo compute
        d2h = h2d = 0.0
        res = plan.residency
        if _offloads(res) and engine in RESIDENCY_ENGINES:
            policies = {res.default} | {p for _, p in res.placements}
            sd = _rp.twophase_cache_bytes(self.modules, self.in_shape,
                                          self.dev_batch, n,
                                          self.dtype_bytes)
            if "host" in policies:
                d2h += sd   # FP exports every boundary cache ...
                h2d += sd   # ... and BP prefetches it back
            if "recompute" in policies:
                # regenerating row r's caches replays rows 0..r-1's FP:
                # sum over importing rows ~= fwd * (N-1)/2
                flops += fwd * (n - 1) / 2.0
        key = audit_ratio_key("train_step", engine,
                              res.describe() if res is not None
                              else "device", "")
        scale = table.ratio(key)
        compute = table.compute_us(flops)
        if engine == "pipeline_rows" and plan.stage is not None:
            # GPipe fill/drain bubble: (S-1) of (N+S-1) ticks run below
            # full stage occupancy, charged as compute stretch
            compute *= 1.0 + (plan.stage.n_stages - 1) / n
        copy = table.copy_us(d2h * scale, h2d * scale)
        return {"us": max(compute, copy) + table.row_overhead_us * n,
                "compute_us": compute, "copy_us": copy, "flops": flops,
                "copy_bytes": d2h + h2d}

    def _for_budget_costed(self, budget: int, candidates: Sequence[str],
                           residency: Optional[ResidencySpec],
                           table) -> ExecutionPlan:
        """Collect every feasible candidate plan, rank by predicted step
        time, record the decision — the measured replacement for both the
        Table-I order and residencize's host-before-recompute order."""
        pool = []
        for engine in candidates:
            p = self.solve(engine, budget, residency=residency)
            if p is not None:
                pool.append(p)
        device_pool = list(pool)
        if residency is None:
            # the offload alternatives enter the SAME ranked pool instead
            # of a fixed host-then-recompute retry order
            for policy in ("host", "recompute"):
                spec = ResidencySpec(default=policy)
                for engine in RESIDENCY_ENGINES:
                    p = self.solve(engine, budget, residency=spec)
                    if p is not None:
                        pool.append(p)
        model = self.mesh.model if self.mesh is not None else 1
        if model > 1:
            # staged alternates join the pool too: the roofline's bubble
            # term prices their fill/drain ramp against the offload copies
            for n_stages in range(2, min(model, len(self.modules)) + 1):
                p = self.solve_staged(n_stages, budget, residency=residency)
                if p is not None:
                    pool.append(p)
        feasible = [p for p in pool if p.feasible]
        if not feasible:
            best = min(device_pool, key=lambda p: p.est_bytes)
            if residency is None:
                best = self.residencize(best, budget)
            return self.stagedize(best, budget, residency)
        pref = {e: i for i, e in enumerate(BUDGET_PREFERENCE)}
        scored = [(self.predict_plan_us(p, table), p) for p in feasible]
        scored.sort(key=lambda cp: (cp[0]["us"],
                                    pref.get(cp[1].engine, len(pref)),
                                    cp[1].n_rows))
        cost, chosen = scored[0]
        res_desc = chosen.residency.describe() \
            if chosen.residency is not None else "device"
        chosen = chosen.with_extras(
            cost_model=(f"ranked {len(feasible)} feasible candidates by "
                        f"roofline step time; {chosen.engine} N="
                        f"{chosen.n_rows} ({res_desc}) predicted "
                        f"{cost['us']:.1f}us (compute "
                        f"{cost['compute_us']:.1f}us, copy "
                        f"{cost['copy_us']:.1f}us)"),
            predicted_step_us=round(cost["us"], 3),
            cost_table_version=table.version())
        if _offloads(chosen.residency) \
                and not any(p.feasible for p in device_pool):
            dev_budget = budget // self.shards
            chosen = chosen.with_extras(residencized=(
                f"no device-resident candidate fits budget {dev_budget} "
                f"B/device; {chosen.residency.default} residency of "
                f"{chosen.engine} boundary caches fits at "
                f"N={chosen.n_rows}"))
        return chosen

    # ------------------------------------------------------------------
    # sequence-side planning (the LM transplant)
    # ------------------------------------------------------------------
    @staticmethod
    def seq_estimate(seq_len: int, d_model: int, batch: int, n_chunks: int,
                     d_ff: int = 0, window: int = 0,
                     dtype_bytes: int = 4) -> int:
        """Eq. 7 along the token axis: residual stream (always live) + one
        chunk's widest sub-layer working set (+ the SWA halo)."""
        width = max(3 * d_model, 2 * (d_ff or 4 * d_model))
        chunk_tokens = -(-seq_len // n_chunks) + window
        stream = batch * seq_len * d_model * dtype_bytes
        return stream + batch * chunk_tokens * width * dtype_bytes

    # graceful per-device shard count (mesh batch extent if it divides the
    # batch, else replicate) — ONE rule, shared with ExecutionPlan.data_shards
    _seq_shards = staticmethod(batch_shards)

    @classmethod
    def for_budget_seq(cls, seq_len: int, d_model: int, batch: int,
                       budget: int, d_ff: int = 0,
                       engine: str = "seq_chunked", window: int = 0,
                       axis: int = 1, dtype_bytes: int = 4,
                       n_max: int = 64, head_dim: int = 0,
                       mesh: Optional[MeshSpec] = None,
                       residency: Optional[ResidencySpec] = None
                       ) -> ExecutionPlan:
        """Smallest chunk count (dividing ``seq_len``) that fits ``budget``
        (per-device under a mesh); infeasible plan at the largest divisor
        otherwise.  ``residency`` rides along on the plan (the sequence
        carries — recurrent states — are small, so the Eq. 7 estimate is
        not re-priced; the row-program executor still honours the
        placement)."""
        _count_solve()
        shards = cls._seq_shards(mesh, batch)
        divisors = [n for n in range(1, min(n_max, seq_len) + 1)
                    if seq_len % n == 0]
        extras = {"axis": axis, "seq": seq_len, "d_model": d_model}
        if window:
            extras["window"] = window
        if head_dim:  # lets kernelize_plan price the swa VMEM working set
            extras["head_dim"] = head_dim
        best = None
        for n in divisors:
            est = cls.seq_estimate(seq_len, d_model, batch // shards, n,
                                   d_ff, window, dtype_bytes)
            plan = ExecutionPlan(
                engine=engine, n_rows=n, in_shape=None, batch=batch,
                dtype_bytes=dtype_bytes, est_bytes=est * shards,
                est_bytes_per_device=est, budget=budget,
                feasible=(budget == 0 or est < budget // shards),
                mesh=mesh, residency=residency,
                extras=tuple(extras.items()))
            if plan.feasible:
                return plan
            best = plan
        return best

    @classmethod
    def for_model(cls, cfg, batch: int, seq_len: int, budget: int = 0,
                  mesh: Optional[MeshSpec] = None,
                  residency: Optional[ResidencySpec] = None,
                  kernel=None) -> ExecutionPlan:
        """Sequence plan for a :class:`~repro.models.lm.config.ModelConfig`:
        engine from the layer pattern, N from the budget (or the config's
        ``row_chunks`` when unconstrained).  ``mesh=`` makes the budget
        per-device, exactly as on the CNN side; ``residency=`` rides along
        (see :meth:`for_budget_seq`); ``kernel=`` (spec or backend string)
        kernelizes the resolved plan (:func:`kernelize_plan`), so the
        KernelSpec/ResidencySpec land on the ONE plan the train path
        executes."""
        _count_solve()
        kinds = set(cfg.layer_kinds())
        if kinds & {"mamba", "mlstm", "slstm"}:
            engine, window = "seq_carry_scan", 0
        elif "local" in kinds and cfg.sliding_window:
            engine, window = "seq_swa_overlap", cfg.sliding_window
        else:
            engine, window = "seq_chunked", 0
        head_dim = cfg.head_dim if window else 0
        dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
        if budget:
            plan = cls.for_budget_seq(seq_len, cfg.d_model, batch, budget,
                                      d_ff=cfg.d_ff, engine=engine,
                                      window=window, dtype_bytes=dtype_bytes,
                                      head_dim=head_dim, mesh=mesh,
                                      residency=residency)
        else:
            shards = cls._seq_shards(mesh, batch)
            n = max(1, cfg.row_chunks)
            est = cls.seq_estimate(seq_len, cfg.d_model, batch // shards, n,
                                   cfg.d_ff, window, dtype_bytes)
            extras = {"axis": 1, "seq": seq_len, "d_model": cfg.d_model}
            if window:
                extras["window"] = window
            if head_dim:
                extras["head_dim"] = head_dim
            plan = ExecutionPlan(engine=engine, n_rows=n, in_shape=None,
                                 batch=batch, dtype_bytes=dtype_bytes,
                                 est_bytes=est * shards,
                                 est_bytes_per_device=est, mesh=mesh,
                                 residency=residency,
                                 extras=tuple(extras.items()))
        if kernel:
            plan = kernelize_plan(plan, kernel)
        return plan


def segment_row_capacity(modules: Sequence, h0: int, inner: str,
                         n_segments: Optional[int] = None
                         ) -> Tuple[Tuple[int, int, int], ...]:
    """Per-segment granularity caps under sqrt(L) segmentation — the
    Table I counters, exposed as plan-shaped (start, end, cap) triples."""
    from repro.core.hybrid import auto_segments, max_rows_per_segment
    cuts = auto_segments(len(modules), n_segments)
    caps = max_rows_per_segment(modules, h0, cuts, inner)
    return tuple((a, b, cap) for (a, b), cap in zip(cuts, caps))

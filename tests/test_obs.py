"""Observability tier: the obs registry/tracer contracts, disabled-mode
no-op behaviour, the executor and scheduler event streams, and the plan
audit — tracing must never change what a run computes, only record it."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.exec import Planner, ResidencySpec, build_apply
from repro.exec.rowprog import RowProgram, make_rowprog_apply
from repro.obs.audit import live_bytes, measure_step, memory_metrics, \
    plan_audit
from repro.obs.metrics import MetricsRegistry, NULL_METRIC
from repro.obs.steplog import StepLog, load_steps
from repro.obs.trace import Tracer, read_jsonl


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("rows").inc()
    reg.counter("rows").inc(2)
    reg.gauge("bytes").set(128)
    for v in range(10):
        reg.histogram("lat").observe(float(v))
    d = reg.to_dict()
    assert d["schema"] == 1
    assert d["counters"]["rows"] == 3
    assert d["gauges"]["bytes"] == 128.0
    h = d["histograms"]["lat"]
    assert h["count"] == 10 and h["min"] == 0.0 and h["max"] == 9.0
    # nearest-rank, same convention as repro.serve.percentile
    assert h["p50"] == 4.0 and h["p95"] == 9.0


def test_registry_accessors_are_get_or_create():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("x") is not reg.histogram("y")


def test_metrics_dump_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(7)
    path = str(tmp_path / "m.json")
    reg.dump(path)
    d = MetricsRegistry.load(path)
    assert d["counters"]["n"] == 7
    # schema gate: a future layout must not parse silently
    with open(path, "w") as f:
        json.dump({"schema": 99}, f)
    with pytest.raises(ValueError, match="schema"):
        MetricsRegistry.load(path)


# ---------------------------------------------------------------------------
# tracer + JSONL round-trip
# ---------------------------------------------------------------------------


def test_trace_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = Tracer(path, meta={"arch": "vgg16"})
    tr.span("fp_row", tick=0, bytes=64)
    tr.event("offload", tick=1.5, bytes=32)
    tr.close()
    recs = read_jsonl(path)
    assert recs[0] == {"schema": 1, "kind": "header", "arch": "vgg16"}
    assert recs[1] == {"kind": "span", "name": "fp_row", "tick": 0,
                       "attrs": {"bytes": 64}}
    # fractional scheduler ticks survive; integral ticks stay ints
    assert recs[2]["tick"] == 1.5 and isinstance(recs[1]["tick"], int)
    assert recs == tr.records


def test_read_jsonl_rejects_headerless_and_wrong_schema(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "span", "name": "x"}\n')
    with pytest.raises(ValueError, match="header"):
        read_jsonl(str(p))
    p.write_text('{"kind": "header", "schema": 99}\n')
    with pytest.raises(ValueError, match="schema"):
        read_jsonl(str(p))


# ---------------------------------------------------------------------------
# module-level session: disabled-mode no-op, capture scoping
# ---------------------------------------------------------------------------


def test_disabled_mode_is_noop_and_allocation_free():
    assert not obs.enabled()
    obs.emit("span", "x", 0, a=1)  # must not raise, must not record
    # every metric accessor hands back the one shared null singleton —
    # no per-call-site allocation in disabled mode
    assert obs.counter("a") is NULL_METRIC
    assert obs.gauge("b") is NULL_METRIC
    assert obs.histogram("c") is NULL_METRIC
    NULL_METRIC.inc()
    NULL_METRIC.set(3)
    NULL_METRIC.observe(1.0)


def test_capture_scopes_and_restores():
    assert not obs.enabled()
    with obs.capture() as s:
        assert obs.enabled() and obs.session() is s
        obs.counter("n").inc()
        obs.span("unit", tick=3)
        assert s.metrics.counters["n"].value == 1
        assert s.tracer.records[-1]["name"] == "unit"
    assert not obs.enabled()


# ---------------------------------------------------------------------------
# rowprog event stream + tracing-changes-nothing
# ---------------------------------------------------------------------------


class _Scan(RowProgram):
    n_rows = 4

    def init_carry(self, args):
        return jnp.zeros((4,))

    def carry_names(self, r):
        return "sd"

    def row_args(self, args, r):
        return args[0][r]

    def row_step(self, carry, ra, r):
        y = jnp.tanh(ra * 2.0 + carry)
        return y, y

    def finish(self, ys):
        return jnp.stack(ys)

    def out_cotangent(self, g, r):
        return g[r]


X = jnp.arange(16.0).reshape(4, 4) / 16.0


@pytest.mark.parametrize("policy", ["device", "host", "recompute"])
def test_rowprog_tracing_is_bit_identical(policy):
    res = ResidencySpec.parse(policy)

    def loss(a):
        return make_rowprog_apply(_Scan(), res)(a).sum()

    base_l, base_g = jax.value_and_grad(loss)(X)
    with obs.capture():
        obs_l, obs_g = jax.value_and_grad(loss)(X)
    assert np.array_equal(np.asarray(base_l), np.asarray(obs_l))
    assert np.array_equal(np.asarray(base_g), np.asarray(obs_g))


def test_rowprog_event_stream_host_residency():
    res = ResidencySpec.parse("host")
    with obs.capture() as s:
        jax.grad(lambda a: make_rowprog_apply(_Scan(), res)(a).sum())(X)
        names = [r["name"] for r in s.tracer.records[1:]]
        counts = {n: c.value for n, c in s.metrics.counters.items()}
    assert names.count("fp_row") == 4 and names.count("bp_row") == 4
    # row 0's carry is init_carry (still placed); rows 1..3 offload too
    assert names.count("offload") == 4
    # every host-placed carry is fetched exactly once during BP
    assert names.count("prefetch") == 4
    assert counts["rowprog.prefetches"] == 4
    # double buffering: the first BP row (tick 3) issues its own fetch
    # AND the next row's, one tick ahead
    first = [r for r in s.tracer.records if r.get("name") == "prefetch"
             and r.get("tick") == 3]
    assert sorted(e["attrs"]["depth"] for e in first) == [0, 1]


def test_rowprog_event_stream_recompute():
    res = ResidencySpec.parse("recompute")
    with obs.capture() as s:
        jax.grad(lambda a: make_rowprog_apply(_Scan(), res)(a).sum())(X)
        names = [r["name"] for r in s.tracer.records[1:]]
        counts = {n: c.value for n, c in s.metrics.counters.items()}
    assert names.count("drop_recompute") == 4
    # rows 1..3 regenerate their chains (row 0's chain is empty: upto=0)
    assert names.count("recompute_chain") == 4
    assert counts["rowprog.recompute_rows"] == 3 + 2 + 1  # O(N^2) sweep


def test_rowprog_device_residency_emits_no_transfer_events():
    with obs.capture() as s:
        jax.grad(lambda a: make_rowprog_apply(_Scan())(a).sum())(X)
        names = {r["name"] for r in s.tracer.records[1:]}
    assert "offload" not in names and "prefetch" not in names
    assert {"fp_row", "bp_row"} <= names


# ---------------------------------------------------------------------------
# scheduler event stream / timeline / serve plan audit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_run():
    from repro.configs import get_reduced
    from repro.models.lm import model as LM
    from repro.serve import make_requests, serve
    cfg = get_reduced("qwen1_5_4b")
    # prompt 15 fills two 8-token pages at admit, so decode crosses a
    # page boundary on token 2 -> page_grow events appear
    reqs = make_requests(3, cfg.vocab, seed=0, prompt_len=15,
                         max_new_tokens=3)
    params = LM.init_lm(jax.random.PRNGKey(0), cfg)

    def run():
        return serve(params, cfg, reqs, n_slots=2, cache_kind="paged_kv",
                     page_size=8)
    base_report, _ = run()
    with obs.capture() as s:
        obs_report, plan = run()
    return base_report, obs_report, plan, s


def test_scheduler_timeline_schema_and_order(serve_run):
    base, _, _, _ = serve_run
    tl = base.timeline()
    assert tl, "scheduler must produce events without an obs session"
    for e in tl:
        assert e["kind"] == "event" and "name" in e and "tick" in e
    ticks = [e["tick"] for e in tl]
    assert ticks == sorted(ticks)
    names = {e["name"] for e in tl}
    assert {"admit", "prefill", "decode", "finish"} <= names
    assert {"page_alloc", "page_grow", "page_free"} <= names
    # tick-range filtering
    assert all(e["tick"] <= 2 for e in base.timeline(end=2))
    assert base.timeline(start=1e9) == []


def test_scheduler_events_mirror_into_tracer(serve_run):
    _, obs_report, _, s = serve_run
    traced = [r for r in s.tracer.records if r["kind"] == "event"]
    assert [(r["name"], r["tick"]) for r in traced] \
        == [(e["name"], e["tick"]) for e in obs_report.events]
    assert s.metrics.counters["serve.admit"].value == 3
    assert s.metrics.counters["serve.finish"].value == 3


def test_tracing_does_not_change_tokens(serve_run):
    base, obs_report, _, _ = serve_run
    for st in base.states:
        assert obs_report.tokens(st.rid) == list(st.generated)
    assert obs_report.events == base.events


def test_serve_plan_audit_is_near_exact(serve_run):
    base, obs_report, plan, _ = serve_run
    assert base.plan_audit is None  # audit only under an obs session
    audit = obs_report.plan_audit
    assert audit["source"] == "serve_pool"
    assert audit["cache_kind"] == "paged_kv"
    # pool buffers come from the plan's own slot/page formulae: the
    # serve_pool tolerance in repro.analysis.audit is [0.95, 1.10]
    assert 0.95 <= audit["ratio"] <= 1.10


# ---------------------------------------------------------------------------
# plan audit: measured peak bytes vs estimate
# ---------------------------------------------------------------------------


def test_memory_metrics_and_measure_step():
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((8, 8))
    measured = measure_step(jax.jit(f), a, a)
    if measured is None:
        pytest.skip("backend has no memory_analysis")
    assert measured["peak_bytes"] > 0
    assert measured["peak_bytes"] == (
        measured["temp_size_in_bytes"] + measured["argument_size_in_bytes"]
        + measured["output_size_in_bytes"] - measured["alias_size_in_bytes"])


def test_measure_step_reports_wall_time_when_asked():
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((8, 8))
    assert "wall_us" not in (measure_step(jax.jit(f), a, a) or {})
    measured = measure_step(jax.jit(f), a, a, time_iters=2)
    if measured is None:
        pytest.skip("backend supports neither memory_analysis nor AOT timing")
    assert measured["wall_us"] > 0


@pytest.mark.parametrize("kind", ["full", "paged_kv", "quant_kv"])
def test_measure_step_against_serve_pools(kind):
    """measure_step prices the real decode step against every pool cache
    kind: the jitted decode's argument bytes must cover the pool's live
    dense view, and the peak must be positive — the serve-side audit the
    cost model seeds from."""
    from repro.configs import get_reduced
    from repro.models.lm import model as LM
    from repro.serve import ServeEngine, make_pool
    cfg = get_reduced("qwen1_5_4b")
    params = LM.init_lm(jax.random.PRNGKey(0), cfg)
    plan = Planner.for_serve(cfg, 16, n_slots=2, cache_kind=kind,
                             page_size=8)
    engine = ServeEngine(params, cfg, plan)
    pool = make_pool(cfg, plan)
    view = pool.decode_view()
    tokens = jnp.zeros((pool.n_slots, 1), jnp.int32)
    measured = measure_step(engine._decode, params, tokens, view)
    if measured is None:
        pytest.skip("backend has no memory_analysis")
    assert measured["peak_bytes"] > 0
    # the dense view is a decode argument, so the compiled argument
    # bytes bound it from above (quant pools dequantise into the view)
    assert measured["argument_size_in_bytes"] >= live_bytes(view)


def test_plan_audit_record_and_emission():
    from repro.exec.plan import ExecutionPlan
    plan = ExecutionPlan(engine="twophase", n_rows=2, est_bytes=1000,
                         est_bytes_per_device=1000)
    with obs.capture() as s:
        rec = plan_audit(plan, {"peak_bytes": 1500}, "train_step")
        assert rec["ratio"] == 1.5
        assert rec["engine"] == "twophase" and rec["n_rows"] == 2
        assert s.tracer.records[-1]["kind"] == "plan_audit"
        assert s.metrics.gauges["audit.train_step.ratio"].value == 1.5
    # est override (global / host-term audits)
    rec = plan_audit(plan, {"peak_bytes": 500}, "serve_pool",
                     est_bytes=500)
    assert rec["ratio"] == 1.0


def test_live_bytes_counts_committed_buffers():
    tree = {"a": jnp.ones((4, 4), jnp.float32),
            "b": [jnp.ones((2,), jnp.int8)]}
    assert live_bytes(tree) == 4 * 4 * 4 + 2


# ---------------------------------------------------------------------------
# step log (satellite: versioned train_log.json)
# ---------------------------------------------------------------------------


def test_steplog_formats_and_versioned_dump(tmp_path, capsys):
    log = StepLog("train")
    with obs.capture() as s:
        log.log({"step": 0, "loss": 1.25, "elapsed_s": 0.5})
        log.log({"step": 1, "loss": 1.0, "grad_norm": 2.0,
                 "elapsed_s": 0.7})
        assert s.metrics.counters["train.steps_logged"].value == 2
        assert s.metrics.histograms["train.loss"].values == [1.25, 1.0]
    out = capsys.readouterr().out
    # the two historical trainer line formats, key-detected
    assert "step     0 loss 1.2500 (0.5s)" in out
    assert "step     1 loss 1.0000 ce 0.0000 gnorm 2.00 (0.7s)" in out
    path = str(tmp_path / "train_log.json")
    log.dump(path, arch="vgg16")
    with open(path) as f:
        d = json.load(f)
    assert d["schema"] == 1 and d["arch"] == "vgg16"
    assert [r["step"] for r in d["steps"]] == [0, 1]
    assert load_steps(path) == log.records


def test_load_steps_reads_pre_schema_bare_list(tmp_path):
    path = tmp_path / "old.json"
    path.write_text(json.dumps([{"step": 0, "loss": 2.0}]))
    assert load_steps(str(path)) == [{"step": 0, "loss": 2.0}]


# ---------------------------------------------------------------------------
# obs.scope: timed spans, HLO op names, the profiler's clock
# ---------------------------------------------------------------------------


def test_scope_records_timed_spans_in_open_order(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with obs.capture(trace=path) as s:
        with obs.scope("bp_row", tick=3, n_rows=4):
            with obs.scope("vjp"):
                pass
            # a span reaches the file once it has its duration
            s.tracer._fh.flush()
            assert open(path).read().count("\n") == 1  # the header
        recs = s.tracer.records[1:]
    assert [r["name"] for r in recs] == ["bp_row", "vjp"]
    outer, inner = recs
    assert outer["kind"] == "span" and outer["tick"] == 3
    assert outer["attrs"] == {"n_rows": 4} and "attrs" not in inner
    for r in recs:
        assert isinstance(r["t_ns"], int) and r["dur_ns"] >= 0
    assert outer["t_ns"] <= inner["t_ns"]
    assert inner["t_ns"] + inner["dur_ns"] <= outer["t_ns"] + outer["dur_ns"]
    assert read_jsonl(path)[1:] == recs


def test_scope_without_session_records_nothing():
    assert not obs.enabled()
    with obs.scope("fp_row", tick=0, carry_bytes=8):
        assert obs.counter("rowprog.fp_rows") is NULL_METRIC
    assert obs.session() is None
    with pytest.raises(ZeroDivisionError):
        with obs.scope("sync"):
            1 / 0


def test_scope_names_the_ops_of_a_jitted_step():
    def f(x):
        with obs.scope("fp_row", tick=2):
            y = jnp.sin(x)
        with obs.scope("sgd_update"):
            return y * 3.0

    txt = jax.jit(f).lower(jnp.ones(4)).as_text(dialect="hlo",
                                                 debug_info=True)
    assert 'op_name="jit(f)/fp_row2/sin"' in txt
    assert 'op_name="jit(f)/sgd_update/mul"' in txt


def test_profiled_anchor_puts_jsonl_spans_on_the_profile_clock(tmp_path):
    import time
    import types

    from repro.obs.cli import profiled

    args = types.SimpleNamespace(jax_profile=str(tmp_path / "prof"))
    names = ("obs_anchor", "data", "dispatch", "sync")
    with obs.capture(trace=str(tmp_path / "t.jsonl")) as s:
        with profiled(args):
            for _ in range(3):
                with obs.scope("data"):
                    time.sleep(0.002)
                with obs.scope("dispatch"):
                    x = jnp.arange(64.0).sum()
                with obs.scope("sync"):
                    float(x)
    spans = [r for r in read_jsonl(str(tmp_path / "t.jsonl"))
             if r.get("name") in names]
    (path,) = (tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = sorted((e.start_ns, e.name) for p in pd.planes
                  if p.name.startswith("/host:") for ln in p.lines
                  for e in ln.events if e.name in names)
    assert [n for _, n in host] == [r["name"] for r in spans]
    assert len(spans) == 10 and spans[0]["name"] == "obs_anchor"
    shift = spans[0]["t_ns"] - host[0][0]
    for (start, _), r in zip(host, spans):
        assert abs(r["t_ns"] - shift - start) < 1e6

"""The program's obs scopes in the compiled step: every segment, row and
backward phase of the row engines names its ops in the HLO metadata that
the device trace is keyed by, and the names change nothing else."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.launch import train
from repro.launch.compile_cache import enable_compile_cache

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"[\w\-]*\((.*)\)")


def _parts(op_name: str):
    """The components of an op_name with JAX's transform wrappers
    (``jvp(...)``, ``transpose(...)``) taken off."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(part)):
            part = m.group(1)
        out.append(part)
    return out


@pytest.fixture(scope="module", params=["vgg16", "resnet50"])
def lowered(request):
    args = train.build_parser().parse_args(
        ["--arch", request.param, "--preset", "reduced",
         "--strategy", "twophase_h", "--rows", "4", "--batch", "2"])
    run = train.setup_cnn(args)
    low = run.step_fn.lower(run.params, run.opt, *run.batch_at(0))
    return run.plan, low.as_text(dialect="hlo", debug_info=True)


def test_every_row_and_backward_phase_is_named(lowered):
    plan, text = lowered
    seen = set()
    for op_name in _OP_NAME.findall(text):
        parts = _parts(op_name)
        seg = next((p for p in parts if re.fullmatch(r"seg\d+", p)), None)
        for i, p in enumerate(parts):
            m = re.fullmatch(r"(fp_row|bp_row)(\d+)", p)
            if m and seg is not None:
                phase = parts[i + 1] if i + 1 < len(parts) else ""
                seen.add((seg, m.group(1), int(m.group(2)), phase))
    assert plan.segments
    for i, (_, _, n_rows) in enumerate(plan.segments):
        for r in range(n_rows):
            assert any(k[:3] == (f"seg{i}", "fp_row", r) for k in seen), \
                (i, r)
            # the backward starts at the last row, whose scatter adds into
            # zeros, an add the lowering folds; the barrier in front of
            # every row's scatter still names the phase
            for phase in ("replay", "vjp", "grad_scatter"):
                assert (f"seg{i}", "bp_row", r, phase) in seen, (i, r, phase)


def test_every_convolution_carries_a_program_scope(lowered):
    _, text = lowered
    convs = [line for line in text.splitlines() if " convolution(" in line]
    assert convs
    for line in convs:
        m = _OP_NAME.search(line)
        assert m, line
        parts = _parts(m.group(1))
        assert "trunk" in parts and any(
            re.fullmatch(r"(fp_row|bp_row)\d+", p) for p in parts), m.group(1)


def test_scopes_change_only_metadata(monkeypatch):
    """The compiled 2PS step with its scopes against the same step with
    ``jax.named_scope`` made a no-op: the same FLOPs, bytes and ops."""
    from repro.core.twophase import make_twophase_apply
    from repro.models.cnn.layers import Conv, ReLU, init_trunk

    mods = [Conv(8), ReLU(), Conv(8), ReLU(), Conv(8)]
    params, _ = init_trunk(mods, jax.random.PRNGKey(0), (16, 16, 4))
    x = jnp.ones((2, 16, 16, 4))

    def compiled():
        apply = make_twophase_apply(mods, 16, 2)

        def step(p, x):
            with obs.scope("trunk"):
                return jax.grad(lambda p: apply(p, x).sum())(p)
        return jax.jit(step).lower(params, x).compile()

    def ops(c):
        text = re.sub(r", metadata=\{[^}]*\}", "", c.as_text())
        return sorted(re.findall(r"= \S+ ([\w\-]+)\(", text))

    scoped = compiled()
    assert re.search(r"bp_row1\)?/vjp/", scoped.as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "bp_row" not in plain.as_text()
    assert ops(scoped) == ops(plain)
    for key in ("flops", "bytes accessed"):
        assert scoped.cost_analysis()[key] == plain.cost_analysis()[key]


def test_compile_cache_key_includes_scope_names(tmp_path, monkeypatch):
    """A step whose scopes were renamed is compiled again, not loaded
    with the old names from the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def text(name):
            def step(x):
                with obs.scope(name):
                    return jnp.sin(x) * 2.0
            return jax.jit(step).lower(jnp.ones(4)).compile().as_text()

        assert "alpha" in text("alpha")
        assert "beta" in text("beta")
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()

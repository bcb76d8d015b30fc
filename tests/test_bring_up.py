"""What the chip run rests on, checked on the CPU: meshes with Auto
axes, the compile-cache placement, the lowering report, and the serving
phase of ``chip_smoke.py`` at the ``smoke()`` width."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_config
from repro.dist.constrain import constrain, use_mesh
from repro.dist.sharding import named, param_specs
from repro.launch.mesh import make_local_mesh, make_mesh
from repro.models.api import get_family
from repro.nn.context import QuantContext, lowerings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_meshes_have_auto_axes_and_serve_gathers():
    """Explicit axes (jax.make_mesh's default) made ``constrain`` raise
    and the sharded embedding gather refuse to trace; every mesh the
    program builds is Auto, so both go through GSPMD."""
    mesh = make_local_mesh()
    assert set(mesh.axis_types) == {AxisType.Auto}
    assert set(make_mesh((1,), ("pod",)).axis_types) == {AxisType.Auto}
    cfg = get_config("yi-6b").smoke()
    fam = get_family(cfg)
    with use_mesh(mesh):
        params = fam.init(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
        params = jax.device_put(params, named(param_specs(params, mesh),
                                              mesh))
        toks = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)

        @jax.jit
        def gather(p, t):
            y = jnp.take(p["embed"]["table"], t, axis=0)
            return constrain(y, "dp", None, "tp")

        y = gather(params, toks)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32),
        np.asarray(params["embed"]["table"], np.float32)[np.asarray(toks)])


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The environment's directory wins and is left to JAX; without it
    the cache is the fixed ``.jax_cache`` of the checkout."""
    from pathlib import Path
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        want = str(Path(REPO).resolve() / ".jax_cache")
        assert compile_cache.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)


def _pages_of(arch: str, kv_dtype=jnp.float32):
    """One layer's page pools of ``arch``'s smoke() paged serving cache."""
    from repro.launch.serve import _first_pages
    from repro.models.api import init_paged_cache_fn
    cache = init_paged_cache_fn(get_config(arch).smoke(), 2, 4, 16, 2,
                                kv_dtype)
    return _first_pages(cache)


def test_lowerings_report_bypasses():
    """Off the kernel path the report names the einsum bypasses and the
    registry fallback instead of claiming kernels; on it, only the cache
    layouts the block-table kernel reads report it."""
    gqa = _pages_of("yi-6b")
    ctx = QuantContext(mode="int8", backend="ref")
    assert lowerings(ctx, pages=gqa, spec=True) == {
        "attention": "einsum", "paged_attention": "einsum-gather",
        "qmatmul": "ref", "sample_tokens": "ref", "verify_tokens": "ref"}
    pal = QuantContext(backend="pallas", use_lut=True,
                       force_paged_kernel=True)
    assert lowerings(pal, pages=gqa) == {
        "attention": "einsum", "paged_attention": "pallas-interpret",
        "lut_activation": "pallas-interpret",
        "sample_tokens": "xla-fusion"}
    assert "paged_attention" not in lowerings(pal)
    for pages in (_pages_of("yi-6b", jnp.int8),
                  _pages_of("deepseek-v2-236b")):
        assert lowerings(pal, pages=pages)["paged_attention"] == \
            "einsum-gather"


def test_mla_paged_engine_reports_gather(monkeypatch):
    """An MLA model's latent pages never reach the block-table kernel, so
    its paged engine reports the gather even where the kernels are on."""
    import repro.kernels.ops as ops
    from repro.launch import serve
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    args = serve.parse_args(["--arch", "deepseek-v2-236b", "--smoke",
                             "--paged", "--batch", "2", "--prompt-len", "8",
                             "--gen-len", "2"])
    cfg, ctx, mesh = serve.setup(args)
    assert ctx.backend == "pallas"
    with use_mesh(mesh):
        params = serve.serving_params(cfg, ctx, mesh, seed=0)
        eng, _ = serve.build_engine(args, cfg, ctx, mesh, params)
    low = dict(kv.split("=") for kv in
               serve.lowerings_line(eng).split()[1:])
    assert low["paged_attention"] == "einsum-gather"
    assert low["attention"] == "pallas"


def _kernel_path_on(monkeypatch):
    """Steer the model onto its kernel path with the kernels in
    interpret mode, as on a TPU but runnable here."""
    import repro.kernels.ops as ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: True)


def _chip_smoke():
    sys.path.insert(0, REPO)
    import chip_smoke
    return chip_smoke


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_smoke_serving_phase_on_cpu(monkeypatch, quant):
    """chip_smoke's serving phase at the smoke() width, with the kernel
    path steered on and the kernels in interpret mode: every request
    served with its exact token count, Pallas logits and served tokens
    within REF_TOL of the ref backend."""
    chip_smoke = _chip_smoke()
    _kernel_path_on(monkeypatch)
    argv = ["--arch", "yi-6b", "--smoke", "--paged", "--batch", "2",
            "--requests", "3", "--prompt-len", "40", "--gen-len", "5",
            "--prefill-chunk", "16", "--quant", quant]
    out = chip_smoke.serve_phase(argv, name="cpu", check_ref=True)
    for label in ("paged prefill", "flash forward", "decode gap"):
        assert out[f"{label} err"] <= chip_smoke.REF_TOL
    assert out["first_logits"].shape == (512,)
    json.dumps({k: v for k, v in out.items() if k != "first_logits"})


def _planted(fault: str, decode_only: bool):
    """(paged_attention, attention) wrappers around the kernel ops that
    plant ``fault``; ``decode_only`` leaves every call with more than one
    query position sound.  A wrapper is None where the fault has no
    meaning for that kernel."""
    import repro.kernels.ops as ops
    pa, fa = ops.paged_attention, ops.attention

    def paged(q, kp, vp, bt, pos, **kw):
        if not (decode_only and q.shape[2] > 1):
            if fault == "kv-group":     # q heads read the next kv group
                kp, vp = jnp.roll(kp, 1, axis=1), jnp.roll(vp, 1, axis=1)
            elif fault == "softmax-scale":
                kw["softmax_scale"] = (2 / q.shape[-1]) ** 0.5
            elif fault == "position":   # queries one position behind
                pos = pos - 1
            elif fault == "page-order":
                bt = jnp.roll(bt, 1, axis=1)
        return pa(q, kp, vp, bt, pos, **kw)

    def flash(q, k, v, *, causal=True, softmax_scale=None, **kw):
        if fault == "kv-group":
            k, v = jnp.roll(k, 1, axis=1), jnp.roll(v, 1, axis=1)
        elif fault == "softmax-scale":
            softmax_scale = (2 / q.shape[-1]) ** 0.5
        elif fault == "position":       # keys one position late
            k, v = jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2)
        elif fault == "causal-mask":
            causal = False
        return fa(q, k, v, causal=causal, softmax_scale=softmax_scale, **kw)

    return (None if fault == "causal-mask" else paged,
            None if fault == "page-order" else flash)


@pytest.mark.parametrize("fault", ["kv-group", "softmax-scale", "position",
                                   "page-order", "causal-mask"])
def test_ref_check_catches_planted_faults(monkeypatch, fault):
    """Each planted kernel fault moves the chip smoke's Pallas-vs-ref
    numbers past REF_TOL at the smoke() width: the prompt logits when it
    is planted everywhere, the served tokens when only decode steps
    carry it.  Two kv groups of two heads make a wrong group visible."""
    import dataclasses
    import repro.kernels.ops as ops
    from repro.launch import serve
    chip_smoke = _chip_smoke()
    _kernel_path_on(monkeypatch)
    args = serve.parse_args(["--arch", "yi-6b", "--smoke", "--paged",
                             "--batch", "1", "--requests", "1",
                             "--prompt-len", "40", "--gen-len", "8",
                             "--prefill-chunk", "16"])
    cfg, ctx, mesh = serve.setup(args)
    cfg = dataclasses.replace(cfg, n_kv_heads=2)
    tol = chip_smoke.REF_TOL
    with use_mesh(mesh):
        params = serve.serving_params(cfg, ctx, mesh, seed=0)
        prompt = serve.make_prompts(cfg, args)[0]
        paged, flash = _planted(fault, decode_only=False)
        for label, wrapper in (("paged prefill", paged),
                               ("flash forward", flash)):
            if wrapper is None:
                continue
            with monkeypatch.context() as m:
                m.setattr(ops, "paged_attention" if label == "paged prefill"
                          else "attention", wrapper)
                eng, _ = serve.build_engine(args, cfg, ctx, mesh, params)
                if label == "paged prefill":
                    got = chip_smoke.paged_prefill_logits(eng, params, prompt)
                else:
                    got = chip_smoke.forward_logits(cfg, ctx, params,
                                                    prompt)[-1]
            ref = chip_smoke.forward_logits(
                cfg, dataclasses.replace(ctx, backend="ref"), params,
                prompt)[-1]
            assert chip_smoke.rel_err(got, ref) > tol, label
        paged, _ = _planted(fault, decode_only=True)
        if paged is not None:
            with monkeypatch.context() as m:
                m.setattr(ops, "paged_attention", paged)
                eng, _ = serve.build_engine(args, cfg, ctx, mesh, params)
                eng, _, _ = serve.drive(eng, None, [prompt], args)
            errs = chip_smoke.ref_errors(eng, params, prompt,
                                         eng.results[0]["tokens"])
            assert errs["decode gap"] > tol
            assert errs["paged prefill"] <= tol     # prefill left sound

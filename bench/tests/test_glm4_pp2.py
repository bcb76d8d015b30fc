"""The glm4-9b-pp2 configuration against the plain reference, on the CPU
at a smoke size that keeps glm4's features: q/k/v bias, rotary on half
of each head, 16 query heads over one kv head and an untied LM head.

A harness run through the serving engine is correct; a program that
rotates the whole head, or that drops its q/k/v bias, is not; nor is
the int8-weight control.  The cell's per-layer metrics read what their
namesakes on the yi-6b cells read.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

from bench import control, roofline, run, trace, traffic  # noqa: E402
from test_bench_units import hand_trace  # noqa: E402

SEED = 2**31 + 1515
SMOKE_MODEL = dict(n_layers=2, d_model=128, n_heads=16, n_kv_heads=1,
                   head_dim=16, d_ff=256, vocab=512)
SMOKE_DEPLOYMENT = dict(lanes=4, page_size=16, num_pages=160, max_len=512,
                        prefill_chunk=32)
#: the smoke size's own token_gap limit, between its sound readings
#: (0.0036-0.0064) and its int8-weight control's (0.0149-0.0384) over
#: two runs each of three seeds, SEED among them (which requests finish
#: in the window, and so are checked, moves with the CPU's speed); a
#: whole-head rotary reads 0.52-0.71 and a program without its q/k/v
#: bias 1.58-1.68
SMOKE_LIMIT = 0.010
#: the cell's mix at a smoke size: a closed loop of the lanes, prompts
#: several chunks long, outputs a few blocks long
SMOKE_MIX = traffic.Mix(name="longdoc-smoke", loop="closed", clients=4,
                        prompt={"dist": "uniform", "min": 96, "max": 320},
                        output={"dist": "uniform", "min": 32, "max": 96})


def smoke_cell() -> run.Cell:
    cell = run.load_cell("glm4-longctx")
    config = json.loads(json.dumps(cell.config))
    config["model"].update(SMOKE_MODEL)
    config["deployment"].update(SMOKE_DEPLOYMENT)
    config["check"]["token_gap"] = SMOKE_LIMIT
    return dataclasses.replace(cell, config=config, mix=SMOKE_MIX)


def test_smoke_keeps_glm4_features():
    m = smoke_cell().config["model"]
    assert m["qkv_bias"] and m["rope_fraction"] == 0.5
    assert m["n_heads"] // m["n_kv_heads"] == 16
    assert not m["tie_embeddings"]
    full = run.load_cell("glm4-longctx").config
    assert full["model"]["n_layers"] == full["num_layers"] == 20
    assert full["reduced"] == ["num_layers"]
    assert full["published"]["num_layers"] == 40
    d = full["deployment"]
    assert d["max_len"] == full["seq_length"] == 8192
    mix = run.load_cell("glm4-longctx").mix
    assert mix.prompt["max"] + mix.output["max"] <= d["max_len"]


def _run(alter=None, monkeypatch=None):
    """One harness run of the smoke cell; ``alter(cfg, params)`` changes
    the model the engine is built with, not the one the reference
    reads."""
    if alter is not None:
        from repro.launch import serve
        orig = serve.build_engine

        def build_engine(args, cfg, ctx, mesh, params):
            cfg, params = alter(cfg, params)
            return orig(args, cfg, ctx, mesh, params)
        monkeypatch.setattr(serve, "build_engine", build_engine)
    return run.run_cell(smoke_cell(), SEED, 2.0, False, jax.devices()[:1])


def _full_rotary(cfg, params):
    return dataclasses.replace(cfg, rope_fraction=1.0), params


def _no_qkv_bias(cfg, params):
    attn = params["dense"]["attn"]
    params = dict(params, dense=dict(params["dense"], attn={
        k: ({n: a for n, a in v.items() if n != "b"}
            if k in ("wq", "wk", "wv") else v) for k, v in attn.items()}))
    return cfg, params


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["requests_checked"]["value"] >= run.CHECK_REQUESTS


@pytest.mark.parametrize("alter", [_full_rotary, _no_qkv_bias],
                         ids=["rope_fraction_1", "no_qkv_bias"])
def test_altered_program_is_not_correct(monkeypatch, alter):
    res = _run(alter, monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["token_gap"]["value"] > SMOKE_LIMIT


def test_int8_control_reads_above_the_limit():
    r = control.readings(smoke_cell(), SEED, 2.0, jax.devices()[:1])
    assert r["sound"]["correct"] is True
    assert r["int8_weights"]["correct"] is False
    assert r["int8_weights"]["token_gap"] > SMOKE_LIMIT


#: each of the cell's per-layer metrics and the accepted metric it reads
#: as
NAMESAKES = {"prefill_call_ms.longctx": "prefill_call_ms.chat",
             "decode_step_ms.longctx": "decode_step_ms.chat",
             "paged_attn_roofline.longctx": "paged_attn_roofline",
             "mfu.longctx": "mfu",
             "device_idle_share.longctx": "device_idle_share",
             "lane_occupancy.longctx": "lane_occupancy"}


def test_cell_metrics_are_the_namesakes():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in spec["per_layer"]
            if "glm4-longctx" in m.get("workloads", ())}
    assert mine == set(NAMESAKES)


@pytest.mark.parametrize("name", sorted(NAMESAKES))
def test_cell_metric_reads_its_namesake(name):
    """On the hand trace, at glm4-9b-pp2's widths and pool dtype."""
    config = run.load_cell("glm4-longctx").config
    w = run.Window(steps=4, calls=1, gen_tokens=10, lane_steps=12,
                   decode_ctx=6000, prefill_tokens=20, prefill_ctx=5000)
    x = run.LayerInputs(w, trace.summarize(hand_trace()), config["model"],
                        config["deployment"]["pool_dtype"],
                        config["deployment"]["lanes"], 1,
                        roofline.peaks("TPU v5 lite"))
    got = run.load_reader(name)(x)
    assert got is not None and got > 0
    assert got == run.load_reader(NAMESAKES[name])(x)

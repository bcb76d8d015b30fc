"""The harness end to end on the CPU at a smoke size: cells,
configurations, mixes and metrics found by name; a run that is correct;
runs with a token altered where it is produced, which are not; the
control's readings; and the command's refusal of a host with no TPU.

These tests call the harness's functions with a configuration of small
widths; the program is driven through the same calls as on the chip.
"""

import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import control, run  # noqa: E402

SEED = 2**31 + 4321
SMOKE_MODEL = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                   head_dim=32, d_ff=256, vocab=512)
SMOKE_DEPLOYMENT = dict(lanes=4, page_size=16, num_pages=96, max_len=512,
                        prefill_chunk=32)
#: the smoke size's own token_gap limit, between its sound readings
#: (0, 0.0017, 0.0075) and its int8-weight control's (0.024, 0.027,
#: 0.069) on three seeds, SEED among them
SMOKE_LIMIT = 0.015
SMOKE_MIX = {"loop": "open", "rate": 4.0, "gap_shape": 0.25,
             "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                        "min": 8, "max": 200},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                        "min": 2, "max": 24}}
NEW_METRIC = '''"""A metric that a later change adds: lanes times decode steps."""


def read(x):
    return float(x.lanes * x.window.steps)
'''


def smoke_config(name="yi-6b"):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    c["model"].update(SMOKE_MODEL)
    c["deployment"].update(SMOKE_DEPLOYMENT)
    c["check"]["token_gap"] = SMOKE_LIMIT
    return c


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with one configuration, one traffic mix,
    one cell and one per-layer metric added as new files."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(smoke_config()))
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps(SMOKE_MIX))
    (root / "bench" / "metrics" / "lane_steps.tiny.py").write_text(
        NEW_METRIC)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "smoke"})
    spec["workloads"].append({"name": "tiny-chat", "config": "tiny",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "smoke"})
    spec["per_layer"].append({"name": "lane_steps.tiny", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "device", "moves": "output_tok_s",
                              "workloads": ["tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def cell(tree):
    return run.load_cell("tiny-chat", root=tree)


def test_new_files_found_by_name(tree, cell):
    assert cell.config["model"]["d_model"] == 128
    assert cell.mix.loop == "open" and cell.mix.rate == 4.0
    assert [m["name"] for m in cell.per_layer] == ["lane_steps.tiny"]
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "tpot_p50_ms", "output_tok_s", "setup_s"}
    read = run.load_reader("lane_steps.tiny", tree)
    x = run.LayerInputs(run.Window(steps=3), None, {}, "float32", 4, 8, None)
    assert read(x) == 12.0
    # every metric the repository's cells name has a reader
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(run.load_reader(m["name"]))
    for w in spec["workloads"]:
        c = run.load_cell(w["name"])
        assert c.per_layer and c.end_to_end


def test_sound_run_is_correct(cell):
    res = run.run_cell(cell, SEED, 2.0, False, jax.devices()[:1])
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] == 8
    assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms",
                                   "output_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["token_gap"]["value"] <= \
        res["checks"]["token_gap"]["limit"]


def _alter_decode(orig):
    def block_decode(self, n):
        block, live, fault = orig(self, n)
        block = block.copy()
        j, s = np.argwhere(live)[0]
        block[j, s] = (block[j, s] + 1) % self.cfg.vocab
        return block, live, fault
    return block_decode


def _alter_prefill(orig):
    def prefill(self, reqs, starts=None):
        first = orig(self, reqs, starts)
        return {s: (t + 1) % self.cfg.vocab for s, t in first.items()}
    return prefill


@pytest.mark.parametrize("where", ["decode", "prefill"])
def test_altered_token_is_not_correct(cell, monkeypatch, where):
    from repro.launch.serve import Engine
    if where == "decode":
        monkeypatch.setattr(Engine, "_block_decode",
                            _alter_decode(Engine._block_decode))
    else:
        monkeypatch.setattr(Engine, "_prefill_chunked",
                            _alter_prefill(Engine._prefill_chunked))
    res = run.run_cell(cell, SEED, 2.0, False, jax.devices()[:1])
    assert res["correct"] is False
    assert res["checks"]["token_gap"]["value"] > \
        res["checks"]["token_gap"]["limit"]


def test_control_reads_above_sound(cell):
    """The program's int8-weight path, in place of the configuration's
    bf16, comes out not correct by the benchmark's own check; the sound
    run is correct.  The int8 page pool reads within the limit at this
    size: the harness refuses it by its dtype instead (below)."""
    r = control.readings(cell, SEED, 2.0, jax.devices()[:1])
    limit = cell.config["check"]["token_gap"]
    assert r["sound"]["correct"] is True
    assert r["sound"]["token_gap"] <= limit
    assert r["int8_weights"]["correct"] is False
    assert r["int8_weights"]["token_gap"] > limit
    for name in ("sound", "int8_weights", "int8_kv"):
        assert r[name]["failed"] == 0
        assert r[name]["requests_checked"] >= run.CHECK_REQUESTS


@pytest.mark.parametrize("key", ["pool_dtype", "weights_dtype"])
def test_refuses_another_precision(key):
    """An engine that serves an int8 page pool or int8 weights where the
    configuration states float32 or bf16 is refused before any run."""
    config = smoke_config()
    stated = dict(config["deployment"])
    config["deployment"][key] = "int8"
    eng, _, _ = run.build(config, SEED, jax.devices()[:1])
    run.check_served(eng, config["deployment"])
    with pytest.raises(RuntimeError, match="states"):
        run.check_served(eng, stated)


def test_refuses_a_host_without_tpu(monkeypatch, tmp_path):
    assert jax.devices()[0].platform != "tpu"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "yi6b-chat", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert out.getvalue().strip() == ""

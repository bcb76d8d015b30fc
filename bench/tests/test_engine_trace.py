"""Unit tests of the reduction of the serving engine's spans and counters
(``bench/engine_trace.py``) and of the four metrics that read it, on
hand-made traces and on a CPU profiler trace of a smoke engine."""

import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

from bench import engine_trace, roofline, run, trace  # noqa: E402
from test_bench_units import ev, hand_trace, model  # noqa: E402

#: the per-layer metrics read from the engine's spans and counters
ENGINE_METRICS = {"prefill_gap_ms", "decode_gap_ms", "queue_wait_ms",
                  "prefill_useful_share"}


def admit(start, dur, **stats):
    e = ev("engine.admit", start, dur)
    e.stats = list(stats.items())
    return e


#: engine spans over the hand trace's idle gaps 0..10, 50..60, 80..95:
#: admit 5..12 around prefill 7..9 (idle: none 0..5, admit 5..7 and
#: 9..10, prefill 7..9); step 38..70 around decode.fetch 45..55 (fetch
#: 50..55, step 55..60); retire 85..90 (none 80..85 and 90..95); a
#: retire 96..110 that the window cuts (busy inside it); an admission
#: 120..130 wholly after the window, whose counts are not the window's
ENGINE_SPANS = (admit(5, 7, admitted=2, queue_wait_s=3.0, prefill_rows=120,
                      prefill_tokens=30),
                ev("engine.prefill", 7, 2),
                ev("engine.step", 38, 32), ev("engine.decode.fetch", 45, 10),
                ev("engine.retire", 85, 5), ev("engine.retire", 96, 14),
                admit(120, 10, admitted=5, queue_wait_s=9.0,
                      prefill_rows=64, prefill_tokens=8))


def with_engine(planes):
    """``planes`` with the engine spans on the host thread."""
    host, device = planes
    python = host.lines[0]
    host = NS(name=host.name, lines=[NS(name=python.name,
                                        events=python.events
                                        + list(ENGINE_SPANS))])
    return [host, device]


def test_engine_idle_cut_at_span_boundaries():
    """Each idle gap is cut where an engine span opens or closes, each
    piece goes to the innermost span open over it, idle outside every
    engine span goes to "none", and the admission counts are summed over
    the sweeps inside the window."""
    es = engine_trace.summarize(with_engine(hand_trace()))
    assert es.idle == pytest.approx({
        "none": 15e-9, "engine.admit": 3e-9, "engine.prefill": 2e-9,
        "engine.decode.fetch": 5e-9, "engine.step": 5e-9,
        "engine.retire": 5e-9})
    s = trace.summarize(hand_trace())
    assert es.window_s == s.window_s
    assert sum(es.idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert es.n == {"engine.admit": 1, "engine.prefill": 1,
                    "engine.step": 1, "engine.decode.fetch": 1,
                    "engine.retire": 2}
    assert es.counts == {"admitted": 2, "queue_wait_s": 3.0,
                         "prefill_rows": 120, "prefill_tokens": 30}
    # a trace without engine spans puts all idle under "none"
    bare = engine_trace.summarize(hand_trace())
    assert bare.idle == pytest.approx({"none": 35e-9})
    assert bare.n == {} and bare.counts == {}
    assert bare.idle_under(("engine.prefill",)) is None


def test_innermost_of_spans_opened_together():
    """Of two spans opened at once the shorter is the inner one; the
    outer one holds the rest once the inner closes."""
    segs = engine_trace.innermost_segments(
        [(0, 10, "engine.outer"), (0, 4, "engine.inner")], -2, 12)
    assert segs == [(-2, 0, "none"), (0, 4, "engine.inner"),
                    (4, 10, "engine.outer"), (10, 12, "none")]


def layer_inputs(summary):
    w = run.Window(steps=4, calls=1, gen_tokens=10, lane_steps=12,
                   decode_ctx=500, prefill_tokens=20, prefill_ctx=300)
    return run.LayerInputs(w, summary, model("yi-6b"), "float32", 8, 1,
                           roofline.peaks("TPU v5 lite"))


@pytest.fixture
def run_trace(tmp_path, monkeypatch):
    """Serve the planes a test sets as the newest profile the harness
    wrote."""
    monkeypatch.setattr(engine_trace, "TRACE_ROOT", tmp_path)
    got = {}
    monkeypatch.setattr(engine_trace, "summarize_file",
                        lambda path: engine_trace.summarize(got["planes"]))
    d = tmp_path / "cell" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")

    def put(planes):
        got["planes"] = planes
    return put


def test_engine_spans_change_no_existing_reading():
    """The same trace with and without engine spans: every field of the
    summary, the breakdown and every reader that does not read the
    engine's spans or counters give the same numbers."""
    with_, bare = (trace.summarize(with_engine(hand_trace())),
                   trace.summarize(hand_trace()))
    for f in dataclasses.fields(trace.Summary):
        assert getattr(with_, f.name) == getattr(bare, f.name), f.name
    assert trace.breakdown(with_) == trace.breakdown(bare)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in spec["per_layer"]}
    assert ENGINE_METRICS <= names
    for name in sorted(names - ENGINE_METRICS):
        read = run.load_reader(name)
        got = read(layer_inputs(with_))
        assert got is not None and got == read(layer_inputs(bare)), name


def test_engine_readers(run_trace, capsys):
    """The four readers on the hand trace; None where the program records
    no engine span or counter, as a program without them; None where the
    newest profile is not the one the run's summary was read from."""
    s = trace.summarize(hand_trace())
    x = layer_inputs(s)
    run_trace(with_engine(hand_trace()))
    # one prefill_step run, 2 ns of idle under engine.prefill
    assert run.load_reader("prefill_gap_ms")(x) == pytest.approx(2e-6)
    # one decode_loop run: 5 ns under engine.step, 5 under decode.fetch
    assert run.load_reader("decode_gap_ms")(x) == pytest.approx(1e-5)
    assert run.load_reader("queue_wait_ms")(x) == pytest.approx(1500.0)
    assert run.load_reader("prefill_useful_share")(x) == pytest.approx(25.0)
    # read once for all four readers
    assert capsys.readouterr().err.count("engine spans read in") == 1
    run_trace(hand_trace())
    x = layer_inputs(s)
    for name in sorted(ENGINE_METRICS):
        assert run.load_reader(name)(x) is None, name
    # a profile over another window is another run's
    run_trace(with_engine(hand_trace()))
    assert engine_trace.of_run(layer_inputs(s)) is not None
    x = layer_inputs(trace.summarize(hand_trace(), window=(0, 90)))
    for name in sorted(ENGINE_METRICS):
        assert run.load_reader(name)(x) is None, name


def test_no_profile_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(engine_trace, "TRACE_ROOT", tmp_path / "none")
    x = layer_inputs(trace.summarize(hand_trace()))
    assert engine_trace.of_run(x) is None
    for name in sorted(ENGINE_METRICS):
        assert run.load_reader(name)(x) is None, name


def test_engine_spans_in_a_real_trace(tmp_path):
    """A CPU profiler trace of a smoke paged engine: one admission of two
    prompts of two prefill chunks each, then one decode block.  The
    engine's spans nest as the program nests its calls, the spans
    counted per fetch match the engine's count of prefill calls, and the
    admission span carries the change of the admission counters."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.dist.constrain import use_mesh
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import ADMIT_SPAN_COUNTERS, Engine
    from repro.models.api import get_family
    from repro.nn.context import QuantContext
    assert tuple(ADMIT_SPAN_COUNTERS) == engine_trace.ADMIT_COUNTERS
    cfg = get_config("gemma-2b").smoke()
    params = get_family(cfg).init(jax.random.PRNGKey(0), cfg)
    mesh = make_local_mesh()
    rs = np.random.RandomState(0)
    with use_mesh(mesh):
        eng = Engine(cfg, QuantContext(compute_dtype=jnp.float32), params,
                     mesh, batch=2, max_len=32, paged=True, page_size=8,
                     prefill_chunk=8)
        for n in (12, 16):
            eng.submit(rs.randint(0, cfg.vocab, (n,)), gen_len=4)
        before = dict(eng.counters)
        with jax.profiler.trace(str(tmp_path)):
            with TraceAnnotation("bench.window"):
                eng.try_admit()
                eng.step_many(2)
    delta = {k: eng.counters[k] - before[k] for k in eng.counters
             if isinstance(before[k], (int, float))}
    assert delta["prefill_calls"] == 2 and delta["admitted"] == 2
    planes = list(ProfileData.from_file(
        trace.newest_trace(str(tmp_path))).planes)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for p in planes for ln in p.lines for e in ln.events
             if e.name.startswith(engine_trace.ENGINE_PREFIX)]

    def within(span, outer):
        return any(o[2] == outer and o[0] <= span[0] and span[1] <= o[1]
                   for o in spans)

    def named(name):
        return [sp for sp in spans if sp[2] == name]

    assert len(named("engine.prefill.fetch")) == delta["prefill_calls"]
    assert all(within(f, "engine.prefill")
               for f in named("engine.prefill.fetch"))
    assert named("engine.prefill") and all(
        within(p, "engine.admit") for p in named("engine.prefill"))
    assert named("engine.decode.fetch") and all(
        within(f, "engine.step") for f in named("engine.decode.fetch"))
    # with a chip's plane that ran no op, the whole window is idle
    es = engine_trace.summarize(planes + [NS(name="/device:TPU:0",
                                             lines=[])])
    assert es.n["engine.prefill.fetch"] == delta["prefill_calls"]
    assert es.n["engine.decode.dispatch"] == 1
    assert sum(es.idle.values()) == pytest.approx(es.window_s)
    assert es.counts == pytest.approx(
        {k: delta[k] for k in engine_trace.ADMIT_COUNTERS})
    assert es.counts["prefill_tokens"] == 12 + 16

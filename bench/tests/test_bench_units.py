"""Unit tests of the benchmark's yardstick: trace reduction, operation
and byte counts, peaks, traffic generation."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline, trace, traffic  # noqa: E402

CONFIGS = ROOT / "bench" / "configs"


#: THUDM/glm-4-9b's widths at 20 of its 40 layers (one stage of a
#: two-stage pipeline), the second configuration the operation and
#: byte counts are held to
GLM4_PP2 = dict(n_layers=20, d_model=4096, n_heads=32, n_kv_heads=2,
                head_dim=128, d_ff=13696, vocab=151552, mlp_gated=True,
                qkv_bias=True, tie_embeddings=False)


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


# -- trace reduction -------------------------------------------------------------
def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def line(name, *events):
    return NS(name=name, events=list(events))


def hand_trace():
    """One chip, a 100 ns window (bench.window 0..100).

    XLA Modules: decode (10..50), prefill (60..80).
    XLA Ops: a loop 10..50 around a kernel 10..28 and a fusion 28..50,
    60..80 matmul, 95..120 (only 95..100 inside the window), with the
    names TPU traces give them (the whole HLO text).
    Busy: [10, 50] + [60, 80] + [95, 100] = 40 + 20 + 5 = 65 ns; the
    loop counts for no op of its own.
    Idle gaps: 0..10 (under bench.submit 0..12), 50..60 (under
    bench.step_many 40..70), 80..95 (midpoint 87.5: bench.sleep 80..95).
    """
    device = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit_decode_loop(3)", 10, 40),
             ev("jit_prefill_step(4)", 60, 20)),
        line("XLA Ops", ev("%while.3 = (s32[]) while(...)", 10, 40),
             ev("%paged_attention_pallas.6 = (f32[8]) custom-call(...)",
                10, 18),
             ev("%fusion.1 = bf16[8] fusion(...)", 28, 22),
             ev("convolution", 60, 20), ev("copy", 95, 25)),
    ])
    host = NS(name="/host:CPU", lines=[
        line("python", ev("bench.window", 0, 100), ev("bench.submit", 0, 12),
             ev("bench.step_many", 40, 30), ev("bench.sleep", 80, 15),
             ev("other", 0, 5)),
    ])
    return [host, device]


@pytest.mark.parametrize("extra", [[], ["/device:CUSTOM:Megascale Trace",
                                        "#Chip0 Host Interface"]])
def test_trace_busy_idle_and_programs(extra):
    """Planes that are no chip, as a TPU trace holds, change nothing."""
    s = trace.summarize(hand_trace() + [NS(name=n, lines=[]) for n in extra])
    assert s.chips == 1
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(65e-9)
    assert s.idle_by_span == pytest.approx({
        "bench.submit": 10e-9, "bench.step_many": 10e-9,
        "bench.sleep": 15e-9})
    assert s.program_seconds("decode_loop") == (pytest.approx(40e-9), 1)
    assert s.program_seconds("prefill_step") == (pytest.approx(20e-9), 1)
    assert s.op_seconds("paged_attention") == pytest.approx(18e-9)
    assert s.op_seconds("paged_attention", "decode_loop") == \
        pytest.approx(18e-9)
    assert s.op_seconds("while") == 0
    assert set(s.op_s) == {"paged_attention_pallas.6", "fusion.1",
                           "convolution", "copy"}
    assert s.op_seconds("paged_attention", "prefill_step") == 0
    assert s.op_seconds("copy") == pytest.approx(5e-9)   # clipped at 100


def test_trace_two_chips_average():
    a, dev = hand_trace()
    dev2 = NS(name="/device:TPU:1", lines=[
        line("XLA Ops", ev("fusion.2", 0, 100))])
    s = trace.summarize([a, dev, dev2])
    assert s.chips == 2
    assert s.busy_s == pytest.approx((65e-9 + 100e-9) / 2)


def test_trace_breakdown_and_missing_window():
    s = trace.summarize(hand_trace())
    b = trace.breakdown(s)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(22e-9)]
    assert b["idle_gaps"][0] == ["bench.sleep", pytest.approx(15e-9)]
    host, dev = hand_trace()
    host.lines[0].events = host.lines[0].events[1:]
    with pytest.raises(ValueError):
        trace.summarize([host, dev])


def test_union_and_gaps():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_trace_file_round_trip(tmp_path):
    """The same reduction through ProfileData, from a serialized
    XSpace written as a text proto."""
    from jax.profiler import ProfileData
    txt = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "paged_attention" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_loop(1)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}"""
    s = trace.summarize(ProfileData.from_text_proto(txt).planes)
    # window 1000..10000 ns; ops 1000..3000 and 4000..5000
    assert s.window_s == pytest.approx(9e-6)
    assert s.busy_s == pytest.approx(3e-6)
    assert s.op_seconds("paged_attention", "decode_loop") == \
        pytest.approx(1e-6)


# -- operations, bytes and peaks ----------------------------------------------------
def test_param_counts():
    yi = model("yi-6b")
    assert roofline.total_params(yi) == pytest.approx(6.06e9, rel=2e-3)
    assert roofline.total_params(yi) * 2 == pytest.approx(12.12e9, rel=2e-3)
    glm = GLM4_PP2
    assert roofline.total_params(glm) == pytest.approx(5.32e9, rel=2e-3)
    # matmul params leave out the input embedding only
    assert roofline.total_params(yi) - roofline.matmul_params(yi) == \
        64000 * 4096 + 32 * 2 * 4096 + 4096


def test_paged_decode_bytes_by_hand():
    glm = GLM4_PP2
    # K and V, 20 layers, 2 kv heads of 128, f32: 40 KiB a token
    assert roofline.kv_bytes_per_token(glm, "float32") == 40 * 1024
    assert roofline.paged_decode_bytes(glm, "float32", 5000) == \
        5000 * 2 * 20 * 2 * 128 * 4
    yi = model("yi-6b")
    assert roofline.kv_bytes_per_token(yi, "float32") == 128 * 1024
    # QK^T and PV: 4 FLOPs per (layer, q head, dim, key)
    assert roofline.attention_flops(yi, 100) == 4 * 32 * 32 * 128 * 100


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_s == 819e9
    assert p.source
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


# -- traffic --------------------------------------------------------------------------
def mixes():
    return {p.stem: traffic.load_mix(p)
            for p in (ROOT / "bench" / "traffic").glob("*.json")}


@pytest.mark.parametrize("name", sorted(mixes()))
def test_traffic_seed_determines_schedule(name):
    mix = mixes()[name]
    big = 2**31 + 98765

    def draw(seed):
        reqs = (traffic.schedule(mix, 51) if mix.loop == "open"
                else traffic.closed_sizes(mix))
        toks = [traffic.prompt_tokens(1000, seed, i, r.prompt_len)
                for i, r in enumerate(reqs[:3])]
        return reqs, toks

    a, ta = draw(big)
    b, tb = draw(big)
    c, tc = draw(big + 1)
    assert a == b and all(np.array_equal(x, y) for x, y in zip(ta, tb))
    # another seed writes other prompt tokens into the same sizes and
    # arrivals: the same work
    assert a == c
    assert not all(np.array_equal(x, y) for x, y in zip(ta, tc))
    for r in a:
        assert mix.prompt["min"] <= r.prompt_len <= mix.prompt["max"]
        assert mix.output["min"] <= r.gen_len <= mix.output["max"]
    for t in ta:
        assert t.dtype == np.int32 and t.min() >= 0 and t.max() < 1000


def test_closed_sizes_are_the_quantiles():
    """The closed loop's sequence holds every stratified quantile of
    each length once, in an order that is not sorted."""
    mix = traffic.Mix("t", "closed", {"dist": "uniform", "min": 1, "max": 99},
                      {"dist": "uniform", "min": 1, "max": 9}, clients=2,
                      pool=64)
    a = traffic.closed_sizes(mix)
    plen = [r.prompt_len for r in a]
    assert sorted(plen) == list(traffic.quantiles(mix.prompt, 64))
    assert plen != sorted(plen)
    assert sorted(r.gen_len for r in a) == \
        list(traffic.quantiles(mix.output, 64))


def test_mix_refuses_unknown_keys(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"loop": "open", "rate": 1.0, "order": "fixed",
                             "prompt": {}, "output": {}}))
    with pytest.raises(ValueError, match="order"):
        traffic.load_mix(p)


def test_open_loop_window():
    mix = traffic.Mix("t", "open", {"dist": "uniform", "min": 1, "max": 9},
                      {"dist": "uniform", "min": 1, "max": 9}, rate=2.0,
                      gap_shape=0.25)
    reqs = traffic.schedule(mix, 30)
    assert len(reqs) == 60
    due = [r.due for r in reqs]
    assert due[0] == 0 and due == sorted(due) and due[-1] < 30
    # the window closes on the longest gap
    gaps = np.diff(due + [30.0])
    assert gaps[-1] == pytest.approx(gaps.max())


def test_quantiles_clip():
    q = traffic.quantiles({"dist": "lognormal", "median": 512, "sigma": 0.8,
                           "min": 32, "max": 3072}, 1000)
    assert q.min() >= 32 and q.max() <= 3072
    assert np.median(q) == pytest.approx(512, rel=0.01)

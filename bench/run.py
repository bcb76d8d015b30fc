#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the
model's sizes, the serving deployment and the comparison's limit) and a
traffic mix (``bench/traffic/<traffic>.json``, read by
``bench/traffic.py``).  Each per-layer metric is a reader of its own,
``bench/metrics/<metric>.py``, found by the metric's name.  Adding a
cell, a configuration or a metric is adding files.

The run drives the program's normal serving path, the calls its serve
entry point makes: ``serve.parse_args`` / ``setup`` / ``build_engine``
(with weights this benchmark made from the seed, ``weights.py``), then
``Engine.submit``, ``Engine.step_many``, ``Engine.retire_finished`` and,
when no lane is live, ``Engine.try_admit``.  Set-up warms the cell's
shapes (one prefill chunk and one decode block) and counts as
``setup_s``; the window then runs for ``--seconds``.

With ``--trace 0`` the result line carries the end-to-end metrics, timed
on the host from the client side; with ``--trace 1`` the same window is
traced and the line carries the per-layer metrics, read from the device
trace (``trace.py``) and the harness's own counts.  Either way, once the
window has closed and the device's peak memory is read, the program's
state is freed and a sample of the finished requests is compared with
the plain float32 reference (``check.py``, ``reference.py``).

The last line of standard output is one JSON object; the compared
numbers and their limits are also the last lines of standard error.
A run on a host whose first device is not a TPU of the peaks table, or
that has fewer chips than the cell asks for, exits 3 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from bench import check, roofline, traffic  # noqa: E402

#: reference sample: the request with most served tokens and others,
#: until at least CHECK_REQUESTS requests and CHECK_TOKENS served tokens
#: are in it, or CHECK_MAX_REQUESTS
CHECK_TOKENS = 512
CHECK_REQUESTS = 4
CHECK_MAX_REQUESTS = 8


class NoChip(RuntimeError):
    """The host cannot run the cell: no TPU of the peaks table, or too
    few chips."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache in the checkout, at a
    fixed path (the program takes the directory it is given), and put
    the program on the path.  Call before JAX is imported."""
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path.insert(0, str(ROOT / "src"))


# -- what the cell is made of ------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: traffic.Mix
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, its traffic mix and the metrics it reports, each
    found by name under ``<root>/bench``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    config = json.loads((root / "bench" / "configs"
                         / f"{w['config']}.json").read_text())
    config["name"] = w["config"]
    mix = traffic.load_mix(root / "bench" / "traffic"
                           / f"{w['traffic']}.json")

    def mine(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if mine(m) and ("workloads" in m or m["moves"] in names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per)


def load_reader(metric: str, root: Path = ROOT):
    """``read`` of ``<root>/bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chip(chips: int) -> dict:
    """The device record of the run; NoChip where the host has no TPU
    of the peaks table or fewer than ``chips`` of them."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"first device is {d0.platform}, not a TPU")
    roofline.peaks(d0.device_kind)
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, the host has "
                     f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


# -- the program under test ----------------------------------------------------
class Compiles:
    """Seconds JAX spends compiling (persistent-cache reads included)
    and the number of compiles, from jax.monitoring."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def take(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


#: serve arguments of each weight and page-pool dtype a deployment may
#: state
WEIGHTS_ARGS = {"bfloat16": [], "int8": ["--quant", "int8"]}
POOL_ARGS = {"float32": [], "int8": ["--kv-bits", "8"]}


def serve_argv(config: dict) -> List[str]:
    """The serve arguments of a configuration's deployment."""
    d = config["deployment"]
    return (["--arch", config["arch"], "--paged", "--temperature", "0",
             "--batch", str(d["lanes"]), "--page-size", str(d["page_size"]),
             "--num-pages", str(d["num_pages"]),
             "--prefill-chunk", str(d["prefill_chunk"]),
             "--prompt-len", str(d["max_len"] - 1), "--gen-len", "0",
             "--seed", "0"]
            + WEIGHTS_ARGS[d["weights_dtype"]] + POOL_ARGS[d["pool_dtype"]])


def model_shapes(cfg, ctx):
    import jax
    from repro.models.api import get_family
    fam = get_family(cfg)
    return jax.eval_shape(lambda k: fam.init(k, cfg, dtype=ctx.param_dtype),
                          jax.random.PRNGKey(0))


def served_dtypes(eng):
    """``(pool, weights)``: the dtypes of the engine's K/V pages and of
    the payload of its projection matrices (each ``w`` leaf, or the
    first array of a quantized one)."""
    import jax
    pool, wts = set(), set()

    def walk(tree):
        if isinstance(tree, dict):
            if "pages" in tree:
                pool.update(str(tree["pages"][k].dtype) for k in ("k", "v"))
            if "w" in tree:
                wts.add(str(jax.tree_util.tree_leaves(tree["w"])[0].dtype))
            for sub in tree.values():
                walk(sub)

    walk(eng.cache)
    walk(eng.params)
    return pool, wts


def check_served(eng, deployment: dict) -> None:
    """Refuse an engine that serves another context, page pool or
    weight precision than the deployment states: the yardstick counts
    bytes at the stated dtypes, and a narrower one is another
    configuration, not a faster program."""
    if eng.max_len != deployment["max_len"]:
        raise RuntimeError(f"engine max_len {eng.max_len} != "
                           f"{deployment['max_len']}")
    pool, wts = served_dtypes(eng)
    if pool != {deployment["pool_dtype"]}:
        raise RuntimeError(f"page pool holds {sorted(pool)}, the "
                           f"deployment states {deployment['pool_dtype']}")
    if wts != {deployment["weights_dtype"]}:
        raise RuntimeError(f"projections are {sorted(wts)}, the deployment "
                           f"states {deployment['weights_dtype']}")


def build(config: dict, seed: int, devices):
    """``(engine, cfg, mesh)``: the configuration's model with this
    benchmark's weights, behind the program's serving engine."""
    from bench import weights
    from repro.dist.constrain import use_mesh
    from repro.launch import serve
    args = serve.parse_args(serve_argv(config))
    cfg, ctx, mesh = serve.setup(args, devices=devices)
    cfg = dataclasses.replace(cfg, **config["model"])
    with use_mesh(mesh):
        params = weights.make_weights(model_shapes(cfg, ctx), seed,
                                      devices[0])
        if args.quant == "int8":
            params = serve.quantize_for_serving(params, ctx)
        eng, _ = serve.build_engine(args, cfg, ctx, mesh, params)
    check_served(eng, config["deployment"])
    log(f"engine: {cfg.name} {cfg.n_layers} layers, lanes {eng.batch}, "
        f"max_len {eng.max_len}, pages {eng.allocator.num_pages} x "
        f"{eng.allocator.page_size}, prefill_chunk {eng.prefill_chunk}, "
        f"decode_block {eng.decode_block}, kv_split {eng.kv_split}, "
        f"pages_per_step {eng.pages_per_step}")
    log(serve.lowerings_line(eng))
    return eng, cfg, mesh


def block_of(eng) -> int:
    """The decode block the serve entry point would run (its ``drive``)."""
    return max(1, eng.decode_block or 8)


def warm_up(eng, block: int, vocab: int) -> None:
    """Run the programs the window runs, in the states it runs them in.

    The cell's shapes are few (a prefill chunk over every lane, a decode
    block, a retirement), but the program compiles each again for each
    placement its inputs arrive with: a cache fresh from the host, from
    a block table upload, or from a program.  So a few requests of two
    prefill chunks, some ending within a block and some after it, go
    through the same calls the window makes, with one admitted into a
    lane that a decode block has dirtied."""
    prompt = np.arange(2 * eng.prefill_chunk, dtype=np.int32) % vocab
    gens = [2 if i % 2 else 2 * block + 1 for i in range(eng.batch + 2)]
    ids = [eng.submit(prompt, gen_len=g) for g in gens[:eng.batch]]
    eng.try_admit()
    for g in gens[eng.batch:]:
        eng.step_many(block)
        eng.retire_finished()
        ids.append(eng.submit(prompt, gen_len=g))
    while eng.live.any() or eng.waiting:
        eng.step_many(block)
        eng.retire_finished()
    for rid, g in zip(ids, gens):
        if len(eng.results[rid]["tokens"]) != g:
            raise RuntimeError("a warm-up request did not finish")


# -- the window ----------------------------------------------------------------
@dataclasses.dataclass
class Window:
    """What the harness saw in one measured window."""
    t0: float = 0.0
    t_close: float = 0.0
    requests: List[dict] = dataclasses.field(default_factory=list)
    steps: int = 0               # decode steps run (calls x block)
    calls: int = 0               # step_many calls
    gen_tokens: int = 0          # tokens emitted in the window
    lane_steps: int = 0          # live (lane, step) pairs in decode
    decode_ctx: int = 0          # sum of their contexts
    prefill_tokens: int = 0      # prompt tokens admitted in the window
    prefill_ctx: int = 0         # sum of their contexts
    late_s: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    compile_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t0


def drive(eng, mix: traffic.Mix, seed: int, seconds: float, block: int,
          vocab: int, span=contextlib.nullcontext, clock=time.perf_counter
          ) -> Window:
    """Serve ``mix`` for ``seconds`` from the client side.

    Open loop: each request is submitted once it is due (late by the
    time the previous call took; recorded); closed loop: each client
    submits its next request once its previous one has finished.  A
    request's first token has reached the harness at the end of the
    call that admitted it (the prefill's token is on the host then); its
    last at the end of the block that ended it.
    """
    from repro.launch.lifecycle import RequestStatus
    w = Window()
    open_loop = mix.loop == "open"
    queue = (traffic.schedule(mix, seconds) if open_loop
             else traffic.closed_sizes(mix))
    nxt = 0
    waiting_first: Dict[int, dict] = {}
    running: Dict[int, dict] = {}

    def submit(req: traffic.Request, due: float) -> None:
        idx = len(w.requests)
        prompt = traffic.prompt_tokens(vocab, seed, idx, req.prompt_len)
        with span("bench.submit"):
            rid = eng.submit(prompt, gen_len=req.gen_len)
        rec = {"id": rid, "due": due, "prompt": prompt,
               "gen_len": req.gen_len, "first": None, "last": None,
               "tokens": None, "status": None}
        w.requests.append(rec)
        waiting_first[rid] = rec

    def observe(t: float) -> int:
        """Record first tokens and finished requests; returns how many
        finished."""
        for rid in list(waiting_first):
            if eng.status(rid) is not RequestStatus.QUEUED:
                rec = waiting_first.pop(rid)
                rec["first"] = t
                running[rid] = rec
                w.prefill_tokens += len(rec["prompt"])
                p = len(rec["prompt"])
                w.prefill_ctx += p * (p + 1) // 2
        done = 0
        for rid in list(running):
            res = eng.results.get(rid)
            if res is not None:
                rec = running.pop(rid)
                rec["last"], rec["tokens"] = t, list(res["tokens"])
                rec["status"] = res["status"]
                done += 1
        return done

    gen0 = eng.counters["gen_tokens"]
    with span("bench.window"):
        w.t0 = clock()
        end = w.t0 + seconds
        if not open_loop:
            for _ in range(mix.clients):
                submit(queue[nxt], w.t0)
                nxt += 1
        while True:
            now = clock()
            if now >= end:
                break
            if open_loop:
                while nxt < len(queue) and w.t0 + queue[nxt].due <= now:
                    due = w.t0 + queue[nxt].due
                    w.late_s.append(now - due)
                    submit(queue[nxt], due)
                    nxt += 1
            if not eng.live.any():
                if eng.waiting:
                    with span("bench.admit"):
                        eng.try_admit()
                    observe(clock())
                if not eng.live.any():
                    if open_loop and nxt < len(queue):
                        with span("bench.sleep"):
                            time.sleep(max(0.0, min(
                                w.t0 + queue[nxt].due, end) - clock()))
                        continue
                    if open_loop:
                        with span("bench.sleep"):
                            time.sleep(max(0.0, end - clock()))
                        continue
            pos = eng.pos.copy()
            with span("bench.step_many"):
                _, live = eng.step_many(block)
            live = np.asarray(live)
            w.calls += 1
            w.steps += live.shape[0]
            c = live.sum(axis=0).astype(np.int64)
            w.lane_steps += int(c.sum())
            w.decode_ctx += int((c * (pos.astype(np.int64) + 1)
                                 + c * (c - 1) // 2).sum())
            with span("bench.retire"):
                eng.retire_finished()
            done = observe(clock())
            if not open_loop:
                for _ in range(done):
                    submit(queue[nxt % len(queue)], clock())
                    nxt += 1
        w.t_close = clock()
    if open_loop:
        # due while the last call ran, never sent: they waited all the
        # same, and count with no first token
        for req in queue[nxt:]:
            if w.t0 + req.due <= w.t_close:
                w.requests.append({"id": None, "due": w.t0 + req.due,
                                   "prompt": None, "gen_len": req.gen_len,
                                   "first": None, "last": None,
                                   "tokens": None, "status": None})
    w.gen_tokens = eng.counters["gen_tokens"] - gen0
    return w


# -- the numbers ----------------------------------------------------------------
def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order
    statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(w: Window):
    """(ttft, tpot) in seconds: the time to first token of every request
    due in the window (one with no first token by the close counts at
    close - due), and the time per output token of every request that
    finished with two tokens or more."""
    ttft = [((r["first"] if r["first"] is not None else w.t_close)
             - r["due"]) for r in w.requests]
    tpot = [(r["last"] - r["first"]) / (len(r["tokens"]) - 1)
            for r in w.requests
            if r["tokens"] is not None and len(r["tokens"]) >= 2]
    return ttft, tpot


def end_to_end(w: Window) -> Dict[str, float]:
    """The medians of the latencies: a window holds tens of requests, and
    the median is the highest percentile with ten samples beyond it."""
    ttft, tpot = latencies(w)
    return {"ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
            "tpot_p50_ms": percentile(tpot, 50) * 1e3 if tpot else None,
            "output_tok_s": w.gen_tokens / w.seconds}


@dataclasses.dataclass
class LayerInputs:
    """What a per-layer metric's reader may read."""
    window: Window
    summary: object              # trace.Summary
    model: dict                  # the configuration's sizes
    pool_dtype: str
    lanes: int
    block: int
    peaks: roofline.Peaks


def finished(w: Window) -> List[dict]:
    return [r for r in w.requests if r["tokens"] is not None]


def failures(w: Window) -> int:
    """Finished requests that did not complete with exactly their
    tokens, all in the vocabulary."""
    from repro.launch.lifecycle import RequestStatus
    bad = 0
    for r in finished(w):
        if (r["status"] is not RequestStatus.COMPLETED
                or len(r["tokens"]) != r["gen_len"]):
            bad += 1
    return bad


def free_engine(eng) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves((eng.cache, eng.params)):
        if hasattr(leaf, "delete"):
            leaf.delete()


def compare(config: dict, cfg, seed: int, w: Window, devices) -> dict:
    """Regenerate the weights and hold a sample of the finished requests
    to the reference; frees the weights before returning."""
    import jax
    from bench import reference, weights
    from repro.launch import serve
    args = serve.parse_args(serve_argv(config))
    _, ctx, _ = serve.setup(args, devices=devices)
    params = weights.make_weights(model_shapes(cfg, ctx), seed, devices[0])
    picked = check.sample(finished(w), seed, min_tokens=CHECK_TOKENS,
                          min_requests=CHECK_REQUESTS,
                          max_requests=CHECK_MAX_REQUESTS)
    out = check.judge(lambda seq, rows: reference.reference_rows(
        params, config["model"], seq, rows), picked)
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    return out


def model_sizes(config: dict, cfg) -> dict:
    """The sizes the yardstick counts with: the configuration file's,
    checked against what the program built."""
    m = dict(config["model"])
    for k, v in m.items():
        if getattr(cfg, k) != v:
            raise RuntimeError(f"program built {k}={getattr(cfg, k)}, the "
                               f"configuration says {v}")
    return m


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, *, root: Path = ROOT, t_start: float = T_START,
             trace_dir: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    from repro.dist.constrain import use_mesh
    comp = Compiles()
    eng, cfg, mesh = build(cell.config, seed, devices)
    model = model_sizes(cell.config, cfg)
    block = block_of(eng)
    span = contextlib.nullcontext
    if trace:
        span = jax.profiler.TraceAnnotation
    with use_mesh(mesh):
        warm_up(eng, block, cfg.vocab)
        jax.block_until_ready(eng.cache)
        setup_s = time.perf_counter() - t_start
        csec, cnum = comp.take()
        log(f"setup {setup_s:.3f} s, {cnum} compiles in {csec:.3f} s")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        try:
            w = drive(eng, cell.mix, seed, seconds, block, cfg.vocab, span)
            jax.block_until_ready(eng.cache)
        finally:
            if trace:
                jax.profiler.stop_trace()
    w.compile_s, w.compiles = comp.take()
    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"device memory: bytes_limit {stats.get('bytes_limit')}, "
        f"bytes_in_use {stats.get('bytes_in_use')}")
    late = max(w.late_s) if w.late_s else 0.0
    ttft, tpot = latencies(w)
    log("latency ms (p50 / p90 / max, n): ttft " + " / ".join(
        f"{percentile(ttft, q) * 1e3:.1f}" for q in (50, 90, 100))
        + f", {len(ttft)}; tpot " + (" / ".join(
            f"{percentile(tpot, q) * 1e3:.1f}" for q in (50, 90, 100))
            if tpot else "none") + f", {len(tpot)}")
    unsent = sum(1 for r in w.requests if r["id"] is None)
    log(f"window {w.seconds:.3f} s: {len(w.requests)} requests due "
        f"({unsent} of them fell due during the last call, unsent), "
        f"{len(finished(w))} finished, {w.gen_tokens} tokens, "
        f"{w.calls} blocks of {block}; generator late by at most "
        f"{late * 1e3:.1f} ms; {w.compiles} compiles in the window "
        f"({w.compile_s:.3f} s); peak_bytes_in_use {peak}")
    free_engine(eng)
    del eng
    gc.collect()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        from bench import trace as tr
        summary = tr.summarize_file(tr.newest_trace(trace_dir))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for name, sec in sorted(summary.module_s.items(),
                                key=lambda kv: -kv[1])[:8]:
            log(f"program {name}: {sec:.4f} s in "
                f"{summary.module_n[name]} runs")
        breakdown = tr.breakdown(summary)
        inp = LayerInputs(w, summary, model,
                          cell.config["deployment"]["pool_dtype"],
                          cell.config["deployment"]["lanes"], block,
                          roofline.peaks(devices[0].device_kind))
        for m in cell.per_layer:
            v = load_reader(m["name"], root)(inp)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(w)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    t_ref = time.perf_counter()
    got = compare(cell.config, cfg, seed, w, devices)
    failed = failures(w)
    correct, checks = check.verdict(got, failed,
                                    cell.config["check"]["token_gap"],
                                    CHECK_REQUESTS)
    log(f"reference: {got['requests_checked']} requests, "
        f"{got['tokens_checked']} served tokens in "
        f"{time.perf_counter() - t_ref:.3f} s")
    result = {"correct": bool(correct), "attempted": len(w.requests),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}"
            + (" (at least)" if k in check.AT_LEAST else ""))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: the program (src/repro) is not in {ROOT}")
        return 2
    use_checkout_cache()
    cell = load_cell(args.workload)
    try:
        require_chip(cell.chips)
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return 3
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    devices = jax.devices()[:cell.chips]
    trace_dir = str(ROOT / ".bench_trace" / args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, trace_dir=trace_dir)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

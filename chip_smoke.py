#!/usr/bin/env python3
"""Serve yi-6b at its published widths on one TPU chip, end to end.

    python3 chip_smoke.py                # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips   # four chips: (1, 4) mesh vs one

One process drives the chip through ``repro.launch.serve`` (the same
``parse_args`` / ``setup`` / ``serving_params`` / ``build_engine`` /
``drive`` calls as ``serve.main``), with random weights from ``--seed``:

(a) device check — the first device must be a TPU;
(b) bf16: ``--paged``, batch 8, 16 requests of 512-token prompts, 32
    greedy tokens each; a cold pass (compiles included) and a warm pass
    over the same engine, each ending in ``block_until_ready``, and the
    device's ``peak_bytes_in_use``;
(c) int8: the same traffic under ``--quant int8`` once (b)'s buffers are
    freed;
(d) in (b): one request through the Pallas kernels against the same
    request through the ``ref`` backend: its last-position prompt logits
    (the engine's paged prefill step, and the flash-kernel forward), and
    each of its 32 served tokens against a teacher-forced ref forward.

Every op must resolve to its Pallas lowering (the ``lowerings:`` line);
every request must finish with exactly its 32 tokens, all in vocab.  A
failure raises, exits non-zero and prints no result line.  The last line
of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--four-chips`` runs only the (1, 4) ``(data, model)`` mesh path (the
kernels inside ``shard_map``), the same requests on a one-device mesh,
and a comparison of the two runs: their first-step logits, and the
mesh's served tokens for one request under the one-device forward.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: the traffic of phases (b) and (c): yi-6b at published widths and depth
SERVE_ARGV = ["--arch", "yi-6b", "--paged", "--batch", "8",
              "--requests", "16", "--prompt-len", "512", "--gen-len", "32",
              "--prefill-chunk", "128", "--temperature", "0"]

#: lowerings that mean a kernel did not run
NOT_KERNELS = ("ref", "pallas-interpret", "einsum", "einsum-gather")

#: Pallas vs ref tolerance on logits, as max|a - b| over max|ref|.  Both
#: paths take bf16 operands with f32 accumulation, but they round at
#: different points: the kernels read K/V from the f32 page pool and keep
#: f32 softmax weights, the einsum path rounds q, k and the softmax
#: weights to bf16 before each matmul.  The ref backend itself moves by
#: as much when its compute dtype goes from bf16 to f32 (the "bf16 floor"
#: line), which is what bounds the sound readings from below.  At the
#: smoke() width on the CPU (interpret mode) the sound readings are about
#: 1e-2 and the smallest of the planted faults in tests/test_bring_up.py
#: (a wrong GQA group, a query position one behind, pages out of order,
#: a softmax scale off by sqrt(2), no causal mask) moves them by 1.6e-1;
#: PERF.md keeps those numbers beside the chip's.  5e-2 sits between.
#: The decode check holds every served token of one request to the same
#: bound: its ref logit is at most REF_TOL * max|ref| below the ref's best.
REF_TOL = 5e-2

#: 4-chip vs 1-chip tolerance on first-step logits, same metric.  Both
#: runs execute the same kernels on the same bf16 weights; the sharded
#: run differs in where the matmuls are split and reduced: each
#: row-parallel projection (attention output, MLP down) sums four bf16
#: partial products across the model axis where one chip rounds once.
#: That is an extra bf16 rounding or two per layer, the same kind and
#: count of drift REF_TOL admits, so the bound is the same.
MESH_TOL = REF_TOL


class Compiles:
    """Seconds JAX spends compiling (backend compile, persistent-cache
    reads included) and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = (self.seconds, self.hits)
        self.seconds, self.hits = 0.0, 0
        return out


def log(msg: str) -> None:
    print(msg, flush=True)


def check_requests(eng, ids, gen_len: int, vocab: int) -> None:
    for i in ids:
        res = eng.results.get(i)
        if res is None:
            raise RuntimeError(f"request {i} was never finished")
        toks = res["tokens"]
        if len(toks) != gen_len:
            raise RuntimeError(f"request {i}: {len(toks)} tokens, "
                               f"expected {gen_len} ({res['status']})")
        if not all(0 <= int(t) < vocab for t in toks):
            raise RuntimeError(f"request {i}: token outside [0, {vocab})")


def check_lowerings(line: str) -> None:
    bad = [kv for kv in line.split()[1:]
           if kv.split("=", 1)[1] in NOT_KERNELS]
    if bad:
        raise RuntimeError(f"ops did not resolve to their kernels: {bad}")


def paged_prefill_logits(eng, params, prompt, ctx=None):
    """Last-position logits of ``prompt`` through the engine's paged
    prefill step (one lane of a fresh page pool, ``prefill_chunk``
    tokens per call) under ``ctx`` (default: the engine's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.api import init_paged_cache_fn, set_block_table
    from repro.train.step import build_prefill_step
    cfg, chunk = eng.cfg, eng.prefill_chunk
    ps = eng.allocator.page_size
    n = len(prompt)
    padded = -(-n // chunk) * chunk
    width = -(-padded // ps)
    cache = init_paged_cache_fn(cfg, 1, width, ps, width, jnp.float32)
    cache = set_block_table(cache, jnp.arange(width, dtype=jnp.int32)[None])
    step = jax.jit(build_prefill_step(cfg, ctx or eng.ctx))
    toks = np.zeros((1, padded), np.int32)
    toks[0, :n] = prompt
    last = None
    for c0 in range(0, padded, chunk):
        logits, cache = step(params, {"tokens": jnp.asarray(
            toks[:, c0:c0 + chunk])}, cache, jnp.full((1,), c0, jnp.int32))
        if c0 <= n - 1 < c0 + chunk:
            last = np.asarray(logits[0, n - 1 - c0], np.float32)
    return last


def forward_logits(cfg, ctx, params, tokens, last: int = 1):
    """Logits at the last ``last`` positions of a cache-free forward pass
    over ``tokens`` under ``ctx``: (last, vocab) float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.api import get_family
    fam = get_family(cfg)
    fwd = jax.jit(lambda p, t: fam.forward(p, t, cfg, ctx)[0][0, -last:])
    return np.asarray(fwd(params, jnp.asarray(tokens, jnp.int32)[None]),
                      np.float32)


def rel_err(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def decode_gap(ref_rows, tokens) -> float:
    """Largest shortfall of a served token's ref logit below the ref's
    best at its position, over max|ref| there: 0 when every token is
    the ref's greedy choice."""
    import numpy as np
    return max(float((r.max() - r[int(t)]) / max(np.abs(r).max(), 1e-30))
               for r, t in zip(ref_rows, tokens))


def ref_errors(eng, params, prompt, tokens) -> dict:
    """The engine's Pallas path against the ``ref`` backend on one
    request: ``prompt`` and the ``tokens`` the engine served for it.

    ``paged prefill`` and ``flash forward`` are the last-position prompt
    logits through the paged prefill step and the flash-kernel forward;
    ``decode gap`` is :func:`decode_gap` of the served tokens against a
    teacher-forced ref forward over prompt and tokens; ``bf16 floor`` is
    how far the ref moves when it computes in f32 instead.
    """
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    cfg, ctx = eng.cfg, eng.ctx
    ref_ctx = dataclasses.replace(ctx, backend="ref")
    seq = np.concatenate([np.asarray(prompt), np.asarray(tokens[:-1])])
    rows = forward_logits(cfg, ref_ctx, params, seq, last=len(tokens))
    ref = rows[0]                       # position len(prompt) - 1
    ref32 = forward_logits(cfg, dataclasses.replace(
        ref_ctx, compute_dtype=jnp.float32), params, prompt)[-1]
    return {"paged prefill": rel_err(paged_prefill_logits(eng, params,
                                                          prompt), ref),
            "flash forward": rel_err(forward_logits(cfg, ctx, params,
                                                    prompt)[-1], ref),
            "decode gap": decode_gap(rows, tokens),
            "bf16 floor": rel_err(ref, ref32)}


def serve_phase(argv, *, name: str, devices=None, check_ref: bool = False,
                judge=None, comp: Compiles = None) -> dict:
    """Build the model and engine as ``serve.main`` does, serve the
    requests twice (cold, warm) and check them; with ``check_ref`` also
    the Pallas-vs-ref check.  ``judge``: tokens another run served for
    the first prompt, scored by :func:`decode_gap` against this run's
    teacher-forced forward (``judged gap``).  Frees every device buffer
    it made before returning.  Returns the numbers it printed, and the
    first prompt's warm-pass ``tokens``."""
    import jax
    import numpy as np
    from repro.dist.constrain import use_mesh
    from repro.launch import serve

    comp = comp or Compiles()
    args = serve.parse_args(argv)
    cfg, ctx, mesh = serve.setup(args, devices=devices)
    log(f"[{name}] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}; mesh {dict(mesh.shape)}; "
        f"param dtype {ctx.param_dtype.__name__}, quant {ctx.mode}")
    out = {"name": name}
    with use_mesh(mesh):
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            serve.serving_params(cfg, ctx, mesh, seed=args.seed))
        out["init_s"] = time.perf_counter() - t0
        n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
        log(f"[{name}] params {n_bytes / 1e9:.3f} GB made on device in "
            f"{out['init_s']:.2f} s")
        eng, _ = serve.build_engine(args, cfg, ctx, mesh, params)
        line = serve.lowerings_line(eng)
        log(f"[{name}] {line}")
        check_lowerings(line)
        prompts = serve.make_prompts(cfg, args)
        comp.take()
        for label in ("cold", "warm"):
            first = len(eng.results)
            t0 = time.perf_counter()
            eng, gen, _ = serve.drive(eng, None, prompts, args)
            jax.block_until_ready(eng.cache)
            dt = time.perf_counter() - t0
            csec, hits = comp.take()
            ids = range(first, len(eng.results))
            if len(ids) != len(prompts):
                raise RuntimeError(f"{len(ids)} of {len(prompts)} served")
            check_requests(eng, ids, args.gen_len, cfg.vocab)
            out[f"{label}_s"], out[f"{label}_compile_s"] = dt, csec
            log(f"[{name}] {label} pass: {len(ids)} requests, {gen} tokens "
                f"in {dt:.3f} s (compile {csec:.3f} s, persistent-cache "
                f"hits {hits})")
        stats = jax.devices()[0].memory_stats() or {}
        out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        log(f"[{name}] peak_bytes_in_use {out['peak_bytes_in_use']} "
            f"(process so far), bytes_in_use {stats.get('bytes_in_use')}")
        for leaf in jax.tree_util.tree_leaves(eng.cache):
            leaf.delete()
        out["first_logits"] = paged_prefill_logits(eng, params, prompts[0])
        out["tokens"] = toks = eng.results[first]["tokens"]  # warm, prompt 0
        if judge is not None:
            seq = np.concatenate([prompts[0], np.asarray(judge[:-1])])
            out["judged gap"] = decode_gap(forward_logits(
                cfg, eng.ctx, params, seq, last=len(judge)), judge)
        if check_ref:
            errs = ref_errors(eng, params, prompts[0], toks)
            log(f"[{name}] bf16 floor, ref in bf16 vs ref in f32: "
                f"max|diff|/max|ref| = {errs.pop('bf16 floor'):.3e}")
            for label, err in errs.items():
                log(f"[{name}] {label} (pallas) vs ref: "
                    f"{'max gap' if label == 'decode gap' else 'max|diff|'}"
                    f"/max|ref| = {err:.3e} (tol {REF_TOL:g})")
                if not err <= REF_TOL:
                    raise RuntimeError(f"{label}: pallas disagrees with ref")
                out[f"{label} err"] = err
        for leaf in jax.tree_util.tree_leaves(eng.params):
            leaf.delete()
        del eng, params
    gc.collect()
    return out


def one_chip(seed: int, comp: Compiles) -> None:
    import jax
    one = jax.devices()[:1]
    argv = SERVE_ARGV + ["--seed", str(seed)]
    serve_phase(argv, name="bf16", devices=one, check_ref=True, comp=comp)
    serve_phase(argv + ["--quant", "int8"], name="int8", devices=one,
                comp=comp)


def four_chips(seed: int, comp: Compiles) -> None:
    import jax
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
    argv = SERVE_ARGV + ["--seed", str(seed)]
    sharded = serve_phase(argv + ["--model-parallel", "4"], name="mesh1x4",
                          devices=devs[:4], comp=comp)
    single = serve_phase(argv, name="mesh1x1", devices=devs[:1],
                         judge=sharded["tokens"], comp=comp)
    err = rel_err(sharded["first_logits"], single["first_logits"])
    log(f"[four-chips] first-step logits, (1, 4) mesh vs one device: "
        f"max|diff|/max|ref| = {err:.3e} (tol {MESH_TOL:g})")
    gap = single["judged gap"]
    log(f"[four-chips] the mesh's served tokens of request 0 under the "
        f"one-device forward: max gap/max|logit| = {gap:.3e} "
        f"(tol {MESH_TOL:g})")
    if not (err <= MESH_TOL and gap <= MESH_TOL):
        raise RuntimeError("sharded and one-device runs disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4) mesh path and its one-device "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 2
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    log(f"compile cache: {configure_compile_cache()}")
    comp = Compiles()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed, comp)
        dev["count"] = 4
    else:
        one_chip(args.seed, comp)
        dev["count"] = 1
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

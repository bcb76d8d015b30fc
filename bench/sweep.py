#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate the system
sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r> [<r> ...]

One process, one engine: the cell's traffic mix is offered at each
fixed rate in turn for ``--seconds`` (the same generator as the
benchmark's, with only the rate changed), and what was left in flight
is drained before the next rate.  For each rate it prints the requests
due and finished in the window, the time to first token (median and
90th percentile), the requests still queued when the window closed,
and the ratio of the median time to first token of the last third of
the requests to that of the first third: with a growing backlog both
grow with the rate.  The knee is read from these numbers by hand and
written into the cell's traffic file; the benchmark's runs never run
this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


def one_rate(eng, mix, seed, seconds, block, vocab) -> dict:
    w = run.drive(eng, mix, seed, seconds, block, vocab)
    queued = len(eng.waiting)
    ttft = [((r["first"] if r["first"] is not None else w.t_close)
             - r["due"]) for r in w.requests]
    third = max(1, len(ttft) // 3)
    head, tail = np.median(ttft[:third]), np.median(ttft[-third:])
    while eng.live.any() or eng.waiting:
        eng.step_many(block)
        eng.retire_finished()
    e2e = run.end_to_end(w)
    return {"rate": mix.rate, "due": len(w.requests),
            "finished": len(run.finished(w)), "queued_at_close": queued,
            "ttft_p50_ms": float(np.median(ttft)) * 1e3,
            "ttft_p90_ms": run.percentile(ttft, 90) * 1e3,
            "tpot_p50_ms": e2e["tpot_p50_ms"],
            "output_tok_s": e2e["output_tok_s"],
            "ttft_last_over_first_third": float(tail / max(head, 1e-9))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    run.use_checkout_cache()
    cell = run.load_cell(args.workload)
    if cell.mix.loop != "open":
        run.log("sweep: the cell's traffic is not an open loop")
        return 2
    try:
        run.require_chip(cell.chips)
    except run.NoChip as e:
        run.log(f"sweep: {e}; nothing was run")
        return 3
    import jax
    from repro.dist.constrain import use_mesh
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    devices = jax.devices()[:cell.chips]
    eng, cfg, mesh = run.build(cell.config, args.seed, devices)
    block = run.block_of(eng)
    out = []
    with use_mesh(mesh):
        run.warm_up(eng, block, cfg.vocab)
        for rate in args.rates:
            mix = dataclasses.replace(cell.mix, rate=rate)
            r = one_rate(eng, mix, args.seed, args.seconds, block, cfg.vocab)
            run.log(json.dumps(r))
            out.append(r)
    print(json.dumps({"workload": args.workload, "sweep": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

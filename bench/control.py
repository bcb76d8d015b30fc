#!/usr/bin/env python3
"""Readings that the limit of a cell's ``token_gap`` is set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process, the cell's run as the benchmark makes it
(``run.run_cell``: the window at the cell's own load, then the
comparison with the plain reference), once as the configuration states
(``sound``) and once with each of the program's own lower-precision
paths switched on in its place (``CONTROLS``):

* ``int8_weights``: ``--quant int8``, int8 weights and activations, the
  precision below the configuration's bf16 weights;
* ``int8_kv``: ``--kv-bits 8``, an int8 page pool in place of the
  configuration's float32 one.

Each reading is the run's ``token_gap`` and its ``correct``, decided by
the same check against the configuration's limit as every benchmark
run (``check.verdict``).  A limit is sound only above every sound
reading and below the control's.  The benchmark's own runs never run
this.  The last line of standard output is one JSON object with the
readings.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

#: deployment settings of each control, over the configuration's own
CONTROLS = {"int8_weights": {"weights_dtype": "int8"},
            "int8_kv": {"pool_dtype": "int8"}}


def with_deployment(cell: run.Cell, over: dict) -> run.Cell:
    config = copy.deepcopy(cell.config)
    config["deployment"].update(over)
    return dataclasses.replace(cell, config=config)


def readings(cell: run.Cell, seed: int, seconds: float, devices) -> dict:
    """The sound run's and each control's reading on one seed."""
    out = {"seed": seed}
    for name, over in [("sound", {})] + list(CONTROLS.items()):
        res = run.run_cell(with_deployment(cell, over), seed, seconds,
                           False, devices)
        out[name] = {"correct": res["correct"], "failed": res["failed"],
                     **{k: v["value"] for k, v in res["checks"].items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    run.use_checkout_cache()
    cell = run.load_cell(args.workload)
    try:
        run.require_chip(cell.chips)
    except run.NoChip as e:
        run.log(f"control: {e}; nothing was run")
        return 3
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    devices = jax.devices()[:cell.chips]
    out = []
    for seed in args.seeds:
        r = readings(cell, seed, args.seconds, devices)
        run.log(json.dumps(r))
        out.append(r)
    print(json.dumps({"workload": args.workload, "readings": out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

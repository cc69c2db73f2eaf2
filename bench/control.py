"""Readings that set a cell's limits: the program, the control, faults.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3]

For each seed, at the cell's own sizes and through the timed path's
program (the job's first steps, as the warm-up runs them; no window),
prints one JSON line with the numbers of ``compare.py``:

* ``program``: the program against the plain reference (lower readings);
* ``control`` (``--control-seeds``): the reference computed in bfloat16,
  put in the program's place, against the reference in float32;
* one entry per fault (``--fault-seeds``): the program with that fault of
  ``faults.py`` planted. A step that returns its state unchanged reads 1
  by construction and needs no run; a cell without RSC has no sampled
  path to break.

The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import compare  # noqa: E402
import faults  # noqa: E402
import graphgen  # noqa: E402
import harness  # noqa: E402
import spec  # noqa: E402


def program_side(cell, graph, pseed, patch=None):
    trainer, engine, src, p0 = harness.build(cell, graph, pseed, patch)
    r = harness.refresh_step(cell.traffic)
    src.stop_at = r + 3
    try:
        engine.train(epochs=cell.traffic["epochs"],
                     eval_every=cell.traffic["eval_every"])
    except harness.JobStopped:
        pass
    side = harness.checked_steps(engine, src, p0, r)
    del trainer, engine, src
    gc.collect()
    return side


def fault_names(cell) -> list[str]:
    names = (faults.FAULTS if cell.traffic["rsc"] else faults.EXACT_FAULTS)
    return [n for n in names if n != "state_unchanged"]


def readings(cell, seed, control: bool, fault: bool,
             require_chip: bool = True) -> dict:
    import jax.numpy as jnp
    harness.configure_jax()
    harness.chips_for(cell.chips, require_chip)
    pseed = harness.program_seed(seed)
    r = harness.refresh_step(cell.traffic)
    graph = graphgen.generate(cell.config, seed)
    sides = {"program": program_side(cell, graph, pseed)}
    if fault:
        for name in fault_names(cell):
            sides[name] = program_side(cell, graph, pseed,
                                       faults.FAULTS[name])
    if control:
        sides["control"] = harness.reference_run(cell, graph, pseed,
                                                 dtype=jnp.bfloat16)
    ref = harness.reference_run(cell, graph, pseed)
    return {"seed": seed, **{k: compare.gaps(v, ref, r)
                             for k, v in sides.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        r = readings(cell, seed, seed in args.control_seeds,
                     seed in args.fault_seeds)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

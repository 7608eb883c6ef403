"""Readings that set a training cell's limits, all seeds in one process.

    python3 -m chipbench.calibrate --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--set ssm_cfg.chunk_size=32] [--out PATH]

For each seed: the program's readings through the cell's own set-up (the
``Trainer`` step at the cell's sizes) and the plain reference's; for each
control seed also the control (the reference in int8, ``precision.py``)
and a planted fault (the reference on half of each batch, the mean taken
over the rest). Each comparison's numbers (``checks.py``) are printed as
one JSON line and appended to ``--out``. A state left unchanged reads 1
on ``change_gap`` by its definition and needs no run. ``--set`` changes a
key of the configuration file, for a second witness at another size.

The benchmark's runs do not call this; it needs the chip (or, in the
tests, the same devices check bypassed).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench import checks, registry, run
from chipbench.drivers import train_loop
from chipbench.reference import train as reference


def _set(cfg, assignment: str) -> None:
    key, value = assignment.split("=", 1)
    *parents, leaf = key.split(".")
    node = cfg
    for p in parents:
        node = node[p]
    node[leaf] = json.loads(value)


def main(argv=None, root=registry.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = registry.workload(args.workload, root)
    run.require_chip(cell["chips"])
    run.use_compile_cache()
    cfg = registry.config(cell["config"], root)
    tr = registry.traffic(cell["traffic"], root)
    for a in args.set:
        _set(cfg, a)
    steps = tr["check_steps"]

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    for seed in args.seeds:
        t = time.perf_counter()
        ref = reference.run(cfg, tr, seed, "highest", steps=steps)
        emit({"seed": seed, "side": "reference", "loss": ref["loss"],
              "grad": ref["grad"], "change": ref["change"],
              "seconds": time.perf_counter() - t})
        t = time.perf_counter()
        s = train_loop.setup(cfg, tr, seed)
        s.loader.close()
        prog = s.readings
        del s
        emit({"seed": seed, "side": "program", "set": args.set,
              "numbers": checks.numbers(prog, ref), **prog,
              "seconds": time.perf_counter() - t})
        if seed in args.control_seeds:
            for side, kw in (("control_int8", {"precision": "int8"}),
                             ("fault_half_batch", {"rows_used": tr["batch"] // 2})):
                t = time.perf_counter()
                other = reference.run(cfg, tr, seed, steps=steps, **kw)
                emit({"seed": seed, "side": side,
                      "numbers": checks.numbers(other, ref), **other,
                      "seconds": time.perf_counter() - t})
    return 0


if __name__ == "__main__":
    sys.exit(main())

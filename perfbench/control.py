#!/usr/bin/env python3
"""Readings that the limits of `compare` are set from.

    python perfbench/control.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3]

On the chip, in one process: for every seed of `--seeds`, the cell's
program (compiled once, called once per seed) against the plain
reference; for every seed of `--control-seeds`, the control
(`compare.control`: the reference in bfloat16, the nearest precision below
the configurations' float32, in the program's place).  Prints one line per
reading: {"seed", "side": "program"|"control", "numbers"}.  A limit lies
above every program reading and below every control reading.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(cell, seeds, control_seeds, need_tpu: bool = True,
             build=None):
    """Yield (seed, side, numbers) for the program and the control."""
    import jax
    from perfbench import compare, generator, system
    from perfbench.run import check_devices
    check_devices(cell.chips, need_tpu)
    build = build or system.build
    program = None
    horizon = None
    for seed in seeds:
        study = generator.study(cell.config, cell.traffic, seed)
        horizon = study.deployment.n_steps * study.deployment.dt_h
        if program is None:
            program = build(cell.config, cell.traffic, study)
            args = program.args
        else:
            args = system.arguments(study)
        out = jax.block_until_ready(program.fn(*args))
        prog = system.outputs(program, out, study)
        del out, args
        ref = compare.reference_for(cell.config, study)
        yield seed, "program", compare.numbers(prog, *ref, horizon)
    for seed in control_seeds:
        study = generator.study(cell.config, cell.traffic, seed)
        horizon = study.deployment.n_steps * study.deployment.dt_h
        ctl = compare.control(cell.config, study)
        ref = compare.reference_for(cell.config, study)
        yield seed, "control", compare.numbers(ctl, *ref, horizon)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import enable_compile_cache
    from perfbench import manifest
    enable_compile_cache()
    cell = manifest.cell(args.workload, ROOT)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    for seed, side, nums in readings(cell, ints(args.seeds),
                                     ints(args.control_seeds)):
        print(json.dumps({"workload": cell.name, "seed": seed, "side": side,
                          "numbers": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

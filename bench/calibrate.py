"""Readings that a cell's limits are set from, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--faults 3] --out FILE

For each seed: the program's first ``check_steps`` steps through the timed
path (no window), then the plain reference over the same steps, and the
compared numbers of the program against it (the lower readings).  For the
first ``--faults`` seeds also the control, the reference in bfloat16 in the
program's place, and two planted faults, the reference with half of the
batch left out of the loss and the reference whose towers never get their
cut jacobians (the upper readings).  One JSON line per seed goes to
``--out``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    run.prepare_jax()
    run.require_chips(cell["chips"])
    import importlib

    import jax
    import jax.numpy as jnp

    import check
    import generator
    import harness

    arch, mix = cell["arch"], cell["mix"]
    family = importlib.import_module(f"reference.{arch['family']}")
    cfg = harness.arch_config(arch)
    make_weights = jax.jit(lambda k: family.make_weights(arch, k))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        batches = generator.TokenBatches(mix, arch["vocab_size"], seed)
        key = run.seed_key(seed)
        trainer = harness.SplitTrainer(cfg, mix, make_weights(key), batches)
        try:
            prog = run.program_readings(trainer, mix["check_steps"],
                                        make_weights, key,
                                        mix["optimizer"]["b1"])
        finally:
            trainer.close()
        del trainer
        gc.collect()
        t_prog = time.perf_counter() - t0
        ref = run.reference_readings(family, arch, mix, batches,
                                     make_weights, key)
        line = {"seed": seed, "program_s": t_prog,
                "reference_s": time.perf_counter() - t0 - t_prog,
                "program": check.numbers(prog, ref),
                "losses": {"program": prog["losses"], "reference": ref["losses"]}}
        if i < args.faults:
            for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                             ("half_batch", {"half_batch": True}),
                             ("no_exchange", {"no_exchange": True})):
                got = run.reference_readings(family, arch, mix, batches,
                                             make_weights, key, **kw)
                line[name] = check.numbers(got, ref)
        with out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

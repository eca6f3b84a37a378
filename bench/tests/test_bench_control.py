"""The check passes a sound run and fails its control: the plain reference
in bfloat16, the precision below the configuration's float32, in the
program's place.  Tiny size on the CPU, held to the smollm-360m cell's
limits; the same control at the cell's own size is read on the chip."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import generator  # noqa: E402
import run  # noqa: E402
from bench_cells import tiny_cell  # noqa: E402
from reference import dense  # noqa: E402


def test_sound_run_is_correct():
    # a tiny eager step takes seconds on a loaded CPU
    result = run.run_cell(tiny_cell(), 2**31 + 13, 20.0, False)
    assert result["correct"] is True, result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_bf16_control_is_not_correct():
    cell = tiny_cell()
    arch, mix = cell["arch"], cell["mix"]
    batches = generator.TokenBatches(mix, arch["vocab_size"], 5)
    make = jax.jit(lambda k: dense.make_weights(arch, k))
    key = run.seed_key(5)
    ref = run.reference_readings(dense, arch, mix, batches, make, key)
    control = run.reference_readings(dense, arch, mix, batches, make, key,
                                     dtype=jnp.bfloat16)
    ok, lines = check.verdict(check.numbers(control, ref), cell["limits"])
    assert not ok, lines

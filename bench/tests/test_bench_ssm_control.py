"""The tiny ssm cell, held to the mamba2-1.3b cell's limits: a sound run
is correct, and the bfloat16 control, the plain reference in the precision
below the configuration's float32, is not.  The same control at the cell's
own size is read on the chip."""
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
from reference import ssm  # noqa: E402
from test_bench_faults import SECONDS  # noqa: E402
from test_bench_ssm import ssm_cell  # noqa: E402


def test_sound_run_is_correct():
    result = run.run_cell(ssm_cell(), 2**31 + 17, SECONDS, False)
    assert result["correct"] is True, result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_bf16_control_is_not_correct():
    cell = ssm_cell()
    arch, mix = cell["arch"], cell["mix"]
    batches = generator.TokenBatches(mix, arch["vocab_size"], 5)
    make = jax.jit(lambda k: ssm.make_weights(arch, k))
    key = run.seed_key(5)
    ref = run.reference_readings(ssm, arch, mix, batches, make, key)
    control = run.reference_readings(ssm, arch, mix, batches, make, key,
                                     dtype=jnp.bfloat16)
    ok, lines = check.verdict(check.numbers(control, ref), cell["limits"])
    assert not ok, lines

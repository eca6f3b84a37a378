"""Bring-up check: the split-training main path on one TPU chip.

    python chip_smoke.py        # from the checkout root, on a TPU host

Everything runs in this one process, which holds the chip:

1. device check: exits non-zero unless JAX's first device is a TPU (there
   is no CPU fallback);
2. merge kernel: the fused ``merge_pool`` Pallas kernel, forward and
   backward, for all five merges at K=4 over 2048 rows (D=960 for the
   reductions, smollm-360m's concat cut width 240), through the dispatch
   the executor uses, against the jnp oracle; the compiled programs must
   contain the kernel (``tpu_custom_call``);
3. split training: full-width smollm-360m through ``train_split`` over the
   in-process transport (role 0 and the K=4 tower workers share the chip),
   3 steps at window W=1 and 3 at W=2, batch 4, seq 256, random weights
   from seed 0.  Step 0 must match the serial ``protocol_step`` and every
   loss must be finite.

Times and memory printed here are set-up facts of a bring-up run, not
benchmark numbers.  The last stdout line is one JSON object naming the
device; it is printed only when every phase passed.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

MERGES = ("avg", "sum", "max", "mul", "concat")
K, ROWS, D_MODEL = 4, 2048, 960
# f32 K=4 reductions in a different association (and a divide that may be a
# reciprocal multiply) differ from the oracle by a few ulp of values ~O(4)
MERGE_RTOL = MERGE_ATOL = 1e-5
# the benchmark cell's shape; batch 8 x 256 also fits on a v5e now that role
# 0's server step is one compiled program (eagerly, it ran out of HBM)
BATCH, SEQ, STEPS = 4, 256, 3


def device_check():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's first device is {dev.platform} "
                 f"({dev.device_kind}); this check has no CPU fallback")
    print(f"device: {dev.platform} {dev.device_kind}, {len(devices)} "
          "device(s)")
    return dev, len(devices)


def _require_kernel(compiled, what):
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what}: compiled program has no "
                             "tpu_custom_call; the Pallas kernel did not run")


def merge_kernel_phase():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    for strategy in MERGES:
        d = D_MODEL // K if strategy == "concat" else D_MODEL
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (K, ROWS, d), jnp.float32)
        out_d = K * d if strategy == "concat" else d
        w = jax.random.normal(kw, (ROWS, out_d), jnp.float32)
        for live in (jnp.ones((K,), jnp.float32),
                     jnp.asarray([1.0, 0.0, 1.0, 1.0])):
            def fwd(x, live):
                return ops.merge_pool(x, live, strategy=strategy)

            def bwd(x, live):
                return jax.grad(lambda t: jnp.vdot(fwd(t, live), w))(x)

            fwd_c = jax.jit(fwd).lower(x, live).compile()
            bwd_c = jax.jit(bwd).lower(x, live).compile()
            _require_kernel(fwd_c, f"merge_pool {strategy} forward")
            _require_kernel(bwd_c, f"merge_pool {strategy} backward")
            got, dgot = fwd_c(x, live), bwd_c(x, live)
            want = ref.merge_pool(x, strategy, live)
            dwant = jax.grad(lambda t: jnp.vdot(
                ref.merge_pool(t, strategy, live), w))(x)
            np.testing.assert_allclose(got, want, rtol=MERGE_RTOL,
                                       atol=MERGE_ATOL)
            np.testing.assert_allclose(dgot, dwant, rtol=MERGE_RTOL,
                                       atol=MERGE_ATOL)
            print(f"merge_pool {strategy:6s} K={K} rows={ROWS} D={d} "
                  f"live={np.asarray(live).astype(int).tolist()}: "
                  f"max |fwd err| {float(jnp.max(jnp.abs(got - want))):.2e}, "
                  f"max |bwd err| {float(jnp.max(jnp.abs(dgot - dwant))):.2e}"
                  f" (rtol=atol={MERGE_ATOL:g}), tpu_custom_call OK")


def split_training_phase(cfg):
    import jax

    from repro.data.loader import LMBatchLoader
    from repro.models.backbone import param_count
    from repro.train.loop import train_split

    v = cfg.vertical
    print(f"{cfg.name}: {param_count(cfg)} params, {cfg.num_layers} layers, "
          f"d_model {cfg.d_model}, vocab {cfg.vocab_size}, K={v.num_clients} "
          f"towers of {v.tower_layers} layers, merge {v.merge}; "
          f"batch {BATCH}, seq {SEQ}")
    for window in (1, 2):
        t0 = time.perf_counter()
        # keep only the metrics: the returned params would stay on the
        # device through the next window's run
        metrics, report = train_split(
            cfg, LMBatchLoader(cfg, BATCH, SEQ, seed=0), steps=STEPS,
            batch=BATCH, seq=SEQ, transport="inproc", inflight_steps=window,
            log_every=1, seed=0)[1:]
        total = time.perf_counter() - t0
        if metrics.step0_max_dgrad is None:
            raise AssertionError(f"W={window}: step-0 verification did not "
                                 "run")
        if len(metrics.losses) != STEPS or not all(
                math.isfinite(x) for x in metrics.losses):
            raise AssertionError(f"W={window}: losses {metrics.losses}")
        print(f"W={window}: step-0 max |dgrad| {metrics.step0_max_dgrad:.3e} "
              f"<= atol {metrics.step0_atol:g}; losses {metrics.losses}; "
              f"towers on {report.tower_platform}")
        print(f"W={window}: first step (compile + step-0 verification) "
              f"{metrics.step_times[0]:.2f} s, later steps "
              f"{[round(t, 3) for t in metrics.step_times[1:]]} s, "
              f"train_split total {total:.2f} s")
        stats = jax.devices()[0].memory_stats() or {}
        print(f"W={window}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}"
              f" of bytes_limit {stats.get('bytes_limit')}")


def main():
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: {ROOT} is not a checkout of this repository "
                 "(src/repro is missing)")
    dev, count = device_check()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.base import get_arch
    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}")
    t0 = time.perf_counter()
    merge_kernel_phase()
    print(f"merge kernel phase {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    split_training_phase(get_arch("smollm-360m"))
    print(f"split training phase {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)


if __name__ == "__main__":
    main()

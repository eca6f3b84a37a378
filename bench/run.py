"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose first JAX device is a TPU
(there is no CPU fallback).  The cell (``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``, whose ``arch.family`` picks
the plain reference ``bench/reference/<family>.py``) and a traffic mix
(``bench/traffic/<name>.json``); its limits are ``bench/limits/<cell>.json``
and each per-layer metric is read by ``bench/metrics/<metric>.py``.

A run: weights made on the device from the seed in one jitted call, the
program's split-training path built around them, ``check_steps`` steps
driven through it (the programs they compile are set-up), then the window:
steps until ``--seconds`` have passed.  After the window the program's state
is freed and the plain reference follows the first steps from the same
weights and rows; ``correct`` says whether the two agree within the cell's
limits.  ``--trace 1`` profiles the window's first ``traced_steps`` steps
and reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"  # fixed: the path is part of the cache key
GIB = 2 ** 30


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    doc = json.loads((ROOT / config["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    metrics = [m for m in spec["per_layer"]
               if name in m.get("workloads", [name])]
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"name": name, "chips": cell["chips"], "arch": doc["arch"],
            "mix": mix, "limits": limits["limits"], "per_layer": metrics,
            "end_to_end": end_to_end}


def prepare_jax() -> None:
    """Put the program on the path and JAX's persistent compilation cache
    at the checkout's fixed ``.jax_cache``, through the program's own cache
    set-up, which takes the directory from ``JAX_COMPILATION_CACHE_DIR``.
    Every program is cached, however short its compile, so that a warm run
    compiles nothing: under JAX's default rule (one second or more) the
    check steps' small op-by-op programs compile again in every run."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup_compile_cache()


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX has {len(devices)} "
                     f"{devices[0].platform} device(s) ({devices[0].device_kind})")
    return devices


class CompileCounter:
    """Backend compiles and persistent-cache loads, with their times."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


def _metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_readings(trainer, check_steps: int, make_weights, key, b1):
    """Drive the first ``check_steps`` steps through the window's own call,
    and read what the check compares: each step's loss, each optimizer's
    first gradient (from its first moment after one update) and each
    parameter's change over the steps."""
    import check

    losses, grads, step = [], None, 0
    while len(losses) < check_steps:
        done = trainer.advance(step) if step < check_steps else trainer.drain()
        step += 1
        for s, loss, _ in done:
            losses.append(loss)
            if s == 0:
                grads = {
                    "server": check.first_grads_from_moment(
                        trainer.opt_state["mu"], b1),
                    "towers": [check.first_grads_from_moment(st["mu"], b1)
                               for _, st in trainer.tower_states()]}
                grads = check.leaf_norms(grads)
    after = {"server": trainer.server,
             "towers": [p for p, _ in trainer.tower_states()]}
    change = check.change_norms(after, make_weights(key))
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def reference_readings(family, arch, mix, batches, make_weights, key,
                       dtype=None, **faults):
    """The plain reference's readings over the same first steps."""
    import jax
    import jax.numpy as jnp

    import check
    from reference import common

    with jax.default_matmul_precision("highest"):
        weights = make_weights(key)
        if dtype is not None:
            weights = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                             weights)
        rows = [tuple(jnp.asarray(a) for a in batches.step(t))
                for t in range(mix["check_steps"])]
        losses, grads, final = common.train_steps(
            family, arch, mix["optimizer"], weights, rows,
            summarize=check.leaf_norms, **faults)
        del weights  # donated to the first step
        change = check.change_norms(final, make_weights(key))
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def window(trainer, first_step: int, seconds: float, traced_steps: int,
           trace_dir):
    """Steps until ``seconds`` have passed.  Returns the window's start,
    the completion times and losses of the steps that completed inside it,
    and the steps attempted.  With a ``trace_dir``, the profiler records
    the first ``traced_steps`` of them."""
    import jax

    # set-up's objects out of the collector's reach, so that a full
    # collection in the window walks only what the steps leave behind
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    done, attempted, step, tracing = [], 0, first_step, trace_dir is not None
    if trace_dir is not None:
        # host TraceMe events (dispatch, compile) without the Python tracer,
        # whose one event per Python call doubles an eager step's time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    while time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation("bench.step"):
            got = trainer.advance(step)
        attempted += 1
        step += 1
        done += [(t, loss) for _, loss, t in got if t <= deadline]
        if tracing and len(done) >= traced_steps:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
    trainer.drain()
    if tracing:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    gc.unfreeze()  # so that freeing the program's state can collect it
    return t0, done, attempted


def run_cell(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """One run of ``cell`` (``load_cell``'s dict); the result line's keys,
    with the stderr lines under ``_lines``."""
    import jax

    import check
    import generator
    import harness
    import peaks as peaks_lib

    arch, mix = cell["arch"], cell["mix"]
    family = importlib.import_module(f"reference.{arch['family']}")
    counter = CompileCounter()
    device = jax.devices()[0]
    peak = peaks_lib.peaks(device.device_kind) if device.platform == "tpu" \
        else None
    cfg = harness.arch_config(arch)
    batches = generator.TokenBatches(mix, arch["vocab_size"], seed)
    key = seed_key(seed)
    make_weights = jax.jit(lambda k: family.make_weights(arch, k))
    weights = make_weights(key)
    harness.check_layout(cfg, weights)
    trainer = harness.SplitTrainer(cfg, mix, weights, batches)
    del weights
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        prog = program_readings(trainer, mix["check_steps"], make_weights,
                                key, mix["optimizer"]["b1"])
        t0, done, attempted = window(
            trainer, mix["check_steps"], seconds, mix["traced_steps"],
            trace_dir)
        stats = device.memory_stats() or {}
    finally:
        trainer.close()
    del trainer
    gc.collect()
    setup_s = t0 - T_START
    if not done:
        raise RuntimeError(f"no step completed inside the {seconds} s window")
    losses = [loss for _, loss in done]
    failed = sum(not math.isfinite(x) for x in losses)
    t_end = done[-1][0]
    tokens = len(done) * mix["batch"] * mix["seq"]
    ctx = {"tokens_per_s": tokens / (t_end - t0), "chips": cell["chips"],
           "peak": peak, "arch": arch, "mix": mix,
           "flops_per_token": family.flops_per_token(arch, mix["seq"]),
           "compiles_in_window": counter.between(t0, t_end), "trace": None}

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        import devtrace as trace_lib

        try:
            tr = trace_lib.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = tr
        lo, hi = trace_lib.window(tr)
        metrics = {}
        for m in cell["per_layer"]:
            value = _metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace_lib.op_totals(tr, lo, hi)[:10]],
            "idle_gaps": [list(x) for x in trace_lib.idle_gaps(tr, lo, hi)[:10]]}
        busy = trace_lib.busy_ns(tr, lo, hi) * 1e-9
        extra = {"busy_s": busy, "window_s": (hi - lo) * 1e-9}
    else:
        e2e = {
            "train_tokens_per_s": ctx["tokens_per_s"],
            "peak_hbm_gib": stats.get("peak_bytes_in_use", 0) / GIB,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        extra = {}
    result["metrics"] = metrics
    result["device"] = {"platform": device.platform, "kind": device.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": stats.get("peak_bytes_in_use"),
                        **extra}

    ref = reference_readings(family, arch, mix, batches, make_weights, key)
    nums = check.numbers(prog, ref)
    ok, lines = check.verdict(nums, cell["limits"])
    result["correct"] = ok and failed == 0
    result["check"] = {n: {"value": nums[n][0], "limit": cell["limits"][n]}
                       for n in check.NUMBERS}
    result["_lines"] = [
        f"setup_s {setup_s!r}; window steps {len(done)} of {attempted} "
        f"attempted; compiles in window {ctx['compiles_in_window']}; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}"] + lines
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    prepare_jax()
    try:
        require_chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}; there is no CPU fallback", file=sys.stderr)
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    lines = result.pop("_lines")
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Split training of one cell through the program's own training path.

``SplitTrainer`` is the body of ``repro.train.loop.train_split`` (its
transport, ``Executor``, ``StepPipeline`` and server AdamW update), mirrored
so that the benchmark supplies the weights and the rows: ``train_split``
makes its own weights from its seed, its tower workers draw their own rows,
and it keeps its state in locals that nothing outside can read.  Everything
below the loop body is the program's: the split program's tower, server and
loss, the ``TowerWorker`` with its local AdamW, the in-process transport,
the executor's merge (the ``merge_pool`` kernel on a TPU), server
forward/backward and jacobian fan-out.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def arch_config(arch: dict):
    """The program's ``ArchConfig`` for a configuration file's ``arch``."""
    from repro.configs.base import ArchConfig, SSMConfig, VerticalConfig

    a = dict(arch)
    a["vertical"] = VerticalConfig(**a["vertical"])
    if a.get("ssm"):
        a["ssm"] = SSMConfig(**a["ssm"])
    return ArchConfig(**a)


def check_layout(cfg, weights) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the
    program's own partitioned parameters for ``cfg``."""
    from repro.models import backbone
    from repro.models.split_program import get_program

    def program_tree(key):
        towers, server = get_program(cfg).partition(
            backbone.init_params(cfg, key))
        return {"server": server, "towers": towers}

    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    want = sig(jax.eval_shape(program_tree, jax.random.PRNGKey(0)))
    got = sig(jax.eval_shape(lambda: weights))
    if want != got:
        raise RuntimeError(f"{cfg.name}: the benchmark's weights do not match "
                           f"the program's parameter layout:\nprogram "
                           f"{want}\nbenchmark {got}")


class SplitTrainer:
    """One split-training job: K tower workers behind the in-process
    transport, role 0's executor and server optimizer, fed step by step
    from ``batches`` (a ``generator.TokenBatches``)."""

    def __init__(self, cfg, mix: dict, weights: dict, batches):
        from repro.models.split_program import get_program
        from repro.optim import AdamW
        from repro.optim.schedules import linear_warmup_cosine
        from repro.runtime.executor import Executor
        from repro.runtime.pipeline import StepPipeline
        from repro.transport import InprocTransport, TowerWorker

        if mix["transport"] != "inproc":
            raise ValueError(f"unsupported transport {mix['transport']!r}")
        o = mix["optimizer"]
        self.program = program = get_program(cfg)
        self.opt = AdamW(
            learning_rate=linear_warmup_cosine(o["learning_rate"], o["warmup"],
                                               o["schedule_steps"]),
            b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip_norm=o["grad_clip"])
        self.batches = batches
        self.window = mix["window"]
        self._rows: dict = {}
        self.workers = [
            TowerWorker(k, program.tower_fwd(k), weights["towers"][k],
                        feature_fn=self._tower_rows, optimizer=self.opt)
            for k in range(program.num_clients)]
        self.server = weights["server"]
        self.opt_state = None  # made at the first update, as train_split does
        self.ema_state = None
        self.transport = InprocTransport(self.workers)
        try:
            self.executor = Executor(
                self.transport, program.server_fwd, program.loss_fn,
                program.merge, mode="serial", microbatches=1,
                **program.executor_kwargs)
            self.pipeline = StepPipeline(self.executor, window=self.window)
        except BaseException:
            self.transport.close()
            raise

    def _tower_rows(self, step: int, mb: int):
        return self._rows[step]

    def submit(self, step: int) -> None:
        tokens, labels = self.batches.step(step)
        self._rows[step] = jnp.asarray(tokens)
        self.pipeline.submit(step, self.program.batch_ctx({"labels": labels}))

    def collect(self) -> tuple[int, float]:
        """Collect the oldest step, update the server; returns its loss."""
        res = self.pipeline.collect(self.server, ema_state=self.ema_state,
                                    collect_grads=False)
        if self.opt_state is None:
            self.opt_state = self.opt.init(self.server)
        self.server, self.opt_state = self.opt.update(
            self.server, res.server_grads, self.opt_state)
        self.ema_state = res.ema_state
        del self._rows[res.step]
        return res.step, float(res.loss)

    def advance(self, step: int) -> list[tuple[int, float, float]]:
        """Submit ``step``; once W steps are in flight, collect the oldest.
        Returns the collected (step, loss, completion time)."""
        self.submit(step)
        if self.pipeline.inflight < self.window:
            return []
        s, loss = self.collect()
        return [(s, loss, time.perf_counter())]

    def drain(self) -> list[tuple[int, float, float]]:
        out = []
        while self.pipeline.inflight:
            s, loss = self.collect()
            out.append((s, loss, time.perf_counter()))
        return out

    def tower_states(self):
        """Each worker's (params, AdamW state), read between steps."""
        return [(w.params, w.opt_state) for w in self.workers]

    def close(self) -> None:
        self.transport.close()

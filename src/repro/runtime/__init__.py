"""Async pipelined split-training runtime.

The paper's protocol (§4.4) is a *schedule*: feature-holders ship cut
activations to the role-0 server, which merges, runs the head, and returns
jacobians.  ``repro.core.protocol.protocol_step`` executes that schedule
strictly serially — simulated step time is the sum of every client forward
plus server compute.  This package executes the SAME schedule (one
``step_schedule`` definition, one ``Ledger``) on a discrete-event clock
with per-link latency/bandwidth, overlapping client forwards, cut
transfers, the fused merge, and server compute across M microbatches.

Three runtimes (``--runtime`` on repro.launch.train):

* ``serial``    — the paper's schedule as written; baseline clock.
* ``pipelined`` — microbatch pipelining at staleness 0.  Gradients are
  identical to ``protocol_step`` (tests assert to 1e-5); only the clock
  improves — ~K x on the client terms plus transfer/compute overlap.
* ``nowait``    — bounded staleness: a client whose cut misses the
  deadline is imputed from its EMA (repro.core.straggler) and skips that
  microbatch's jacobian; a straggler can never stall the step.

Layout: ``links`` (per-link latency/bandwidth + compute rates), ``clock``
(event heap + FIFO resources), ``engine`` (StepPlan, simulate_serial /
simulate_pipelined — including the multi-step cross-step window
``simulate_pipelined(steps, cross_step)`` — and the pipelined_step
wrapper), ``deadline`` (adaptive no-wait windows from per-client arrival
EWMAs), ``executor`` (the Executor — the ONE execution path that moves
real payloads over any ``repro.transport`` backend, in two modes: the
``serial`` barrier, which the serial and pipelined runtimes both run, and
``nowait``; split into ``submit_step`` / ``collect_step`` halves;
``protocol_step`` and ``pipelined_step`` are thin wrappers over it),
``pipeline``
(``StepPipeline`` — the cross-step window driver: W steps in flight, step
t+1 tower forwards overlapping step t's server backward and jacobian
drain; W=1 is the exact per-step barrier, W>1 trains towers on delayed
gradients, one update behind).  Benchmarks: ``python -m benchmarks.run``
has a runtime section sweeping serial vs pipelined vs no-wait at K in
{2, 4, 8}, a transport section timing real execution over threads, and a
split_pipeline section measuring W=1 vs W=2 wall-clock against the
simulator's prediction (written to ``BENCH_split_exec.json``).
"""
from repro.runtime.clock import EventClock, Resource
from repro.runtime.deadline import AdaptiveDeadline
from repro.runtime.engine import (
    MODES,
    SimReport,
    StepPlan,
    default_deadline_s,
    pipelined_step,
    plan_from_arch,
    plan_step,
    simulate_pipelined,
    simulate_serial,
)
from repro.runtime.executor import (
    ExecReport,
    ExecutionResult,
    Executor,
    fast_merge,
)
from repro.runtime.links import LinkModel
from repro.runtime.pipeline import StepPipeline
from repro.runtime.serve_driver import ServeDriver
from repro.runtime.topology import TREE_VERIFY_ATOL, AggTree

__all__ = [
    "AdaptiveDeadline",
    "AggTree",
    "TREE_VERIFY_ATOL",
    "EventClock",
    "ExecReport",
    "ExecutionResult",
    "Executor",
    "Resource",
    "LinkModel",
    "ServeDriver",
    "MODES",
    "SimReport",
    "StepPipeline",
    "StepPlan",
    "default_deadline_s",
    "fast_merge",
    "pipelined_step",
    "plan_from_arch",
    "plan_step",
    "simulate_pipelined",
    "simulate_serial",
]

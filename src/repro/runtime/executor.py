"""Executor: drive the protocol schedule over any transport.

This is the single execution path behind ``protocol_step`` (serial),
``pipelined_step`` (microbatch pipelining / no-wait) and the split-executing
train loop: one role-0 driver that walks ``step_schedule``, records every
message in a per-step :class:`~repro.core.protocol.Ledger`, merges cut
activations (EMA-imputing no-wait misses), backprops the server network and
returns per-client jacobians — over a :class:`~repro.transport.Transport`.

The step is split into two halves so a driver can keep several steps in
flight (cross-step pipelining, :class:`~repro.runtime.pipeline.StepPipeline`):

* :meth:`submit_step` ships every tower-forward request for one step and
  registers the step's in-flight state (its own Ledger, cut buffers,
  deadline bookkeeping) keyed by ``(step, microbatch)``;
* :meth:`collect_step` gathers the OLDEST in-flight step's cuts, runs the
  role-0 merge/forward/backward per microbatch, fans the jacobians out,
  and barriers on the workers' ``step_done`` acks.

A single shared event pump routes every transport response to its step's
buffers, so cuts from step t+1 arriving while step t is being collected
land where they belong instead of being lost or mis-merged.
:meth:`run_step` is exactly ``submit_step`` + ``collect_step`` — the
blocking one-step call every existing caller uses, bit-for-bit unchanged.
Inference traffic pumps the same way in the serving sibling,
:class:`~repro.runtime.serve_driver.ServeDriver`, with the ``(step,
microbatch)`` key generalized to ``(request, position)``.

At window W > 1 the towers train on delayed gradients — a step's forwards
run before the previous step's optimizer update has reached the client, so
tower params are one update behind the submitted forward (server params are
never stale: the server forward happens at collect time).  The lag is
surfaced as ``ExecReport.staleness`` (how many steps were submitted after
the collected one); W = 1 is staleness 0 and reproduces the serial
semantics exactly.

Drop policies (what happens to a client absent from a microbatch's merge):

* ``"neutral"`` — serial protocol semantics: the merge masks the client to
  its strategy's neutral element (``merge_mask``); jacobians still flow to
  every client.  ``protocol_step``'s ``live_mask``.
* ``"fused"``   — staleness 0: everyone is live, the fused
  ``kernels.merge_pool`` path merges the full stack.
* ``"impute"``  — no-wait: missing seats are filled from the per-client
  EMA (``repro.core.straggler``); only live clients get a jacobian.

Liveness comes either from a predetermined matrix (the simulated federation
clock of ``engine.simulate_pipelined`` — every payload still flows, the
clock just decides who made the merge) or, over a real transport in
``"nowait"`` mode, from wall-clock deadlines driven by the
:class:`~repro.runtime.deadline.AdaptiveDeadline` arrival EWMAs.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import compression as comp_lib
from repro.core import merge as merge_lib
from repro.core import straggler as straggler_lib
from repro.core.protocol import Ledger, step_schedule
from repro.core.secure_agg import KEYX_GROUP_BYTES
from repro.runtime.deadline import AdaptiveDeadline
from repro.transport.base import counted_jit
from repro.transport.tree import TreeRouter

DROP_POLICIES = ("neutral", "fused", "impute")

# retired (step, mb) first-arrival timestamps kept around so a no-wait
# straggler's cut landing after its step was collected still feeds the
# deadline EWMA (that is how a recovered client re-opens the window)
_RETIRED_FIRST_T_KEEP = 64


def fast_merge(stacked: jnp.ndarray, strategy: str) -> jnp.ndarray:
    """merge_pool fast path for every strategy — the fused Pallas kernel on
    TPU (reductions AND the gather-concat), the jnp oracle elsewhere.

    The kernel is (K, B, D)-shaped; LM cut stacks arrive as (K, B, S, D),
    so extra middle dims are flattened around the call and restored after
    (rows are independent in every merge, so this is exact).
    """
    from repro.kernels import ops

    if stacked.ndim > 3:
        K, D = stacked.shape[0], stacked.shape[-1]
        out = ops.merge_pool(stacked.reshape(K, -1, D), strategy=strategy)
        out_d = K * D if strategy == "concat" else D
        return out.reshape(stacked.shape[1:-1] + (out_d,))
    return ops.merge_pool(stacked, strategy=strategy)


def tree_mean(trees):
    return jax.tree_util.tree_map(
        lambda *leaves: sum(leaves) / len(leaves), *trees
    )


def _signature(*args) -> tuple:
    """The tree structure, shapes and dtypes of ``args``: what a compiled
    program's cache keys on."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple((a.shape, a.dtype) for a in leaves)


@dataclass
class ExecReport:
    """Measured (wall-clock) sibling of ``engine.SimReport``: the fields the
    two share mean the same, except that ``step_time_s`` is real elapsed
    time on a real transport and ``live`` reflects deadlines that actually
    fired.  It holds only what the step observed; the analytic
    collective-bytes model is the simulator's alone."""

    mode: str
    transport: str
    step_time_s: float
    microbatches: int
    live: list[list[float]]
    misses_per_client: list[int]
    cut_bytes_per_client: int
    deadline_s: Optional[float] = None  # last deadline used (nowait)
    # steps submitted after this one before it was collected: the tower
    # params' delayed-gradient lag (0 = serial semantics, W-1 at window W)
    staleness: int = 0
    # JAX platform the towers computed on (Transport.tower_platform)
    tower_platform: str = ""

    @property
    def total_misses(self) -> int:
        return sum(self.misses_per_client)


@dataclass
class ExecutionResult:
    loss: jnp.ndarray
    tower_grads: Optional[list]
    server_grads: object
    ledger: Ledger
    report: object  # SimReport (simulated liveness) or ExecReport (measured)
    ema_state: Optional[dict]
    # mean server-side auxiliary loss shipped role 0 -> role 3 (families
    # with server_aux, e.g. the moe router load-balance term); None otherwise
    aux: Optional[jnp.ndarray] = None
    step: int = 0  # which training step this result belongs to


@dataclass
class _InflightStep:
    """Role-0-side state of one submitted-but-uncollected step."""

    step: int
    labels: object  # batch-major label array / batch_ctx pytree
    mbsz: int
    ledger: Ledger
    submit_t: float
    cuts: dict = field(default_factory=dict)  # mb -> {client: cut}
    first_t: dict = field(default_factory=dict)  # mb -> first drain time
    merged: set = field(default_factory=set)  # mbs already merged
    sent_jacs: list = field(default_factory=list)  # per-client bwd count
    done: list = field(default_factory=list)  # per-client step_done
    grads: list = field(default_factory=list)  # per-client final tower grads


class Executor:
    """Role-0 server driving training steps over a transport.

    One training step is :meth:`submit_step` (ship the tower forwards)
    followed by :meth:`collect_step` (merge, server backward, jacobian
    fan-out, step barrier); :meth:`run_step` runs both back-to-back.  Up to
    the driver's window W steps may sit between submit and collect — the
    shared pump keys every response by ``(step, microbatch)`` so adjacent
    steps interleave safely.

    The family-specific pieces come in as pure callables (usually from a
    :class:`~repro.models.split_program.SplitProgram`):

    * ``server_fwd(server_params, merged)`` — or ``(server_params, merged,
      batch)`` with ``server_takes_batch`` (e.g. the audio decoder's
      teacher-forcing tokens ride the role-0 batch context);
    * ``server_aux`` — ``server_fwd`` returns ``(logits, aux)`` and the aux
      scalar is folded into the loss AND recorded on the schedule's
      role-0 -> role-3 ``aux_loss`` slot;
    * ``merge_fn(cuts_list, live_mask)`` — replaces the uniform stacked
      merge for programs whose cuts differ in shape per client (the vlm
      sequence concatenation); requires a barrier mode (no EMA imputation
      of a non-uniform stack).

    Secure aggregation (``secure_agg=True``, ``repro.core.secure_agg``):
    :meth:`setup_secure` runs the one-time in-protocol key exchange (run
    automatically on the first ``submit_step`` otherwise), after which the
    workers mask every cut uplink at the source and role 0 merges MASKED
    cuts — the pairwise masks cancel in the sum/avg merge, so only the
    aggregate is meaningful and no raw activation is ever observed.
    Unsupported combinations raise HERE, loudly, rather than silently
    degrading privacy: a non-additive merge, a program ``merge_fn``
    (non-uniform cuts have no mask-cancelling sum), and any non-barrier
    execution (``nowait`` / EMA imputation — a dropped client's masks
    cannot cancel; there is no dropout-recovery round).

    Cut compression (``compress`` = ``"topk"`` | ``"int8"``,
    ``repro.core.compression``): the workers compress cut uplinks at the
    source (error feedback per microbatch) and THIS side symmetrically
    compresses the K jacobian downlinks, with its own per-(client, mb)
    error-feedback residuals — steps are collected oldest-first, so the
    per-stream carry is step-sequential at any window W.  The step ledger
    records the codec's wire bytes (``compression.payload_bytes``) for
    both directions, which must reconcile exactly with
    ``costs.wire_bytes``.  Unsupported combinations raise here, loudly:
    ``secure_agg`` (additive masks do not cancel through
    quantized/sparsified values — the modular-mask gap Secure Forward
    Aggregation addresses) and a program ``merge_fn`` (non-uniform cuts
    have no single per-vector wire frame to audit).

    Tree aggregation (``agg_tree`` = :class:`~repro.runtime.topology.
    AggTree`): the transport is wrapped in a
    :class:`~repro.transport.tree.TreeRouter` (exposed as
    ``self.transport`` — callers who ``close()`` should close THAT) and
    the schedule re-routes per the tree — relay workers partial-sum their
    subtree's cut uplinks, so :meth:`collect_step` gathers only the
    ``min(F, K)`` top-level combined frames per microbatch, merges them
    with one final sum (avg divides the full-tree sum by K), and fans each
    top-level client ONE jacobian that the relays forward down unchanged.
    ``setup_tree`` ships the one-time ``configure_relay`` round (run
    automatically on the first ``submit_step``).  Role 0's per-step submit
    and merge work drops from O(K) to O(F); the Ledger still audits the
    exact LOGICAL per-edge schedule (``tree_cut[l]``/``tree_jac[l]`` tags:
    one uniform frame per tree edge per microbatch per direction).
    Composes with ``secure_agg`` — partial sums of masked cuts stay
    blinded at relays and the pairwise masks cancel in role 0's full-tree
    sum.  Unsupported combinations raise HERE, loudly: non-additive merges
    (max/mul/concat have no partial-sum regrouping), a program
    ``merge_fn``, compression (codec frames cannot be partial-summed), and
    any non-barrier execution (a dropped client inside a combined frame
    cannot be masked out after the fact).

    Role 0's server work is two compiled programs, each traced once per
    input shape (:attr:`traces`): ``server_step``, the loss of the server
    forward and its gradients with respect to the server params and the
    merged cut, and ``mean``, the mean over M > 1 microbatches.  The
    merge stays outside them, run eagerly under ``jax.vjp`` (the
    ``merge_pool`` kernel's own forward and backward programs, the EMA
    imputation, the tree's final sum, a program ``merge_fn``), and the
    server program's gradient for the merged cut flows back through that
    vjp to the cut gradients.  The labels or batch context are arguments,
    not constants of the program, and the logits never leave it: the
    ledger's head records take their size from the trace.  Nothing is
    donated: the caller updates the server params after the step.
    """

    def __init__(self, transport, server_fwd: Callable, loss_fn: Callable,
                 merge: str, *, mode: str = "pipelined", microbatches: int = 1,
                 label_holder: int = 0, drop_policy: Optional[str] = None,
                 ema_decay: float = 0.95, deadline=None,
                 server_takes_batch: bool = False, server_aux: bool = False,
                 merge_fn: Optional[Callable] = None,
                 secure_agg: bool = False, secure_scale: float = 1.0,
                 compress: Optional[str] = None, topk_fraction: float = 0.25,
                 agg_tree=None):
        if mode not in ("serial", "pipelined", "nowait"):
            raise ValueError(f"mode must be serial|pipelined|nowait, got {mode!r}")
        if drop_policy is None:
            drop_policy = "impute" if mode == "nowait" else "fused"
        if drop_policy not in DROP_POLICIES:
            raise ValueError(f"drop_policy must be one of {DROP_POLICIES}")
        if compress is not None and compress not in comp_lib.SCHEMES:
            raise ValueError(
                f"unknown compression scheme {compress!r} (choose from "
                f"{comp_lib.SCHEMES})")
        # every unsound feature composition rejects through the ONE
        # compat matrix (repro.core.compat) — the rule reasons carry the
        # full why; mode/drop_policy collapse into the nowait flag (any
        # non-barrier execution breaks secure masks and tree partial sums)
        compat.check(
            "executor", secure=secure_agg, compress=compress, tree=agg_tree,
            merge=merge, merge_fn=merge_fn,
            nowait=mode == "nowait" or drop_policy != "fused",
            impute=drop_policy == "impute",
            context=f"Executor(mode={mode!r}, drop_policy={drop_policy!r})")
        if agg_tree is not None:
            if agg_tree.num_clients != transport.num_clients:
                raise ValueError(
                    f"tree covers {agg_tree.num_clients} clients, transport "
                    f"has {transport.num_clients}")
            if not isinstance(transport, TreeRouter):
                transport = TreeRouter(transport, agg_tree)
        self.agg_tree = agg_tree
        self._tree_ready = agg_tree is None or not agg_tree.relays
        self.transport = transport
        self.server_fwd = server_fwd
        self.loss_fn = loss_fn
        self.merge = merge
        self.mode = mode
        self.microbatches = microbatches
        self.label_holder = label_holder
        self.drop_policy = drop_policy
        self.ema_decay = ema_decay
        self.server_takes_batch = server_takes_batch
        self.server_aux = server_aux
        self.merge_fn = merge_fn
        self.secure_agg = secure_agg
        self.secure_scale = secure_scale
        self.compress = compress
        self.topk_fraction = topk_fraction
        # error-feedback residuals for the jacobian downlinks, keyed by
        # (client, mb): steps are collected oldest-first, so each stream
        # position's carry advances one step at a time at any window W
        self._jac_residuals: dict = {}
        self._secure_ready = False
        self._max_secure_step = -1  # highest masked step id (freshness)
        # one-time key-exchange round audit (keyx_pub/keyx_bcast tags)
        self.keyx_ledger = Ledger()
        # deadline: None -> bootstrap an AdaptiveDeadline from the first
        # full barrier; float -> static window; AdaptiveDeadline -> as given
        if deadline is None:
            self.deadline = AdaptiveDeadline(transport.num_clients)
            self.static_deadline_s = None
        elif isinstance(deadline, AdaptiveDeadline):
            self.deadline = deadline
            self.static_deadline_s = None
        else:
            self.deadline = None
            self.static_deadline_s = float(deadline)
        self._schedule = step_schedule(transport.num_clients, label_holder,
                                       secure=secure_agg, compress=compress,
                                       tree=agg_tree)
        self._inflight: dict[int, _InflightStep] = {}  # insertion-ordered
        self._retired_first_t: dict[tuple[int, int], float] = {}
        self._traces = {"server_step": 0, "mean": 0}
        self._heads: dict = {}  # argument signature -> the logits' shape
        self._server_step = counted_jit(
            self._traces, "server_step", jax.value_and_grad(
                self._server_loss, argnums=(0, 1), has_aux=True))
        self._mean = counted_jit(self._traces, "mean", tree_mean)

    @property
    def traces(self) -> dict:
        """How many times each compiled program was traced: one per input
        shape that reached it."""
        return dict(self._traces)

    def _server_loss(self, server_params, merged, batch):
        """The server forward's loss plus its aux term; returns (loss,
        aux).  Records the logits' shape for the argument signature."""
        if self.server_takes_batch:
            out = self.server_fwd(server_params, merged, batch)
        else:
            out = self.server_fwd(server_params, merged)
        if self.server_aux:
            logits, aux = out
        else:
            logits, aux = out, jnp.zeros((), jnp.float32)
        self._heads[_signature(server_params, merged, batch)] = \
            jax.ShapeDtypeStruct(logits.shape, logits.dtype)
        return self.loss_fn(logits, batch) + aux, aux

    def _idle_error(self, phase: str, detail: str = "") -> RuntimeError:
        """Uniform phrasing for every wait loop that drains the shared
        pump: ``transport idle <phase>`` plus what was outstanding and
        which steps were in flight — a hung worker names WHERE the
        protocol stalled instead of ten hand-phrased variants."""
        msg = f"transport idle {phase}"
        if detail:
            msg += f" ({detail})"
        if self._inflight:
            msg += f" [steps in flight: {list(self._inflight)}]"
        return RuntimeError(msg)

    # -- secure-aggregation setup (one-time key-exchange round) ---------------

    def setup_secure(self, *, timeout_s: float = 120.0) -> Ledger:
        """Run the in-protocol pairwise key agreement: gather each client's
        fixed-size public value, relay the full directory back down, and
        barrier on every client's ``keys_ready``.  Role 0 only ever handles
        public group elements — each pair's mask seed is derived at the two
        clients.  Recorded in :attr:`keyx_ledger` (``keyx_pub[k]`` /
        ``keyx_bcast[k]`` tags, reconciled against
        ``costs.key_exchange_bytes`` in tests).  Idempotent; runs
        automatically on the first :meth:`submit_step` if not called."""
        if not self.secure_agg:
            raise RuntimeError("setup_secure on a non-secure Executor "
                               "(construct with secure_agg=True)")
        if self._secure_ready:
            return self.keyx_ledger
        if self._inflight:
            raise RuntimeError("key exchange must precede the first step")
        transport, K = self.transport, self.transport.num_clients
        schedule = self._schedule

        for spec in schedule.key_pubs:
            transport.submit(spec.client, {"op": "key_exchange",
                                           "phase": "pub"})
        pubs: dict[int, int] = {}
        while len(pubs) < K:
            got = transport.next_response(timeout_s)
            if got is None:
                raise self._idle_error("during key exchange",
                                       f"{len(pubs)}/{K} public values in")
            k, resp = got
            if resp["op"] != "pub":
                raise RuntimeError(
                    f"unexpected {resp['op']!r} from client {k} during key "
                    "exchange")
            pubs[int(resp["client"])] = resp["pub"]
            self.keyx_ledger.record_spec_bytes(
                schedule.key_pubs[int(resp["client"])], KEYX_GROUP_BYTES)

        for spec in schedule.key_bcasts:
            transport.submit(spec.client, {
                "op": "key_exchange", "phase": "finish", "pubs": pubs,
                "microbatches": self.microbatches,
                "scale": self.secure_scale,
            })
            self.keyx_ledger.record_spec_bytes(spec, K * KEYX_GROUP_BYTES)
        ready = 0
        while ready < K:
            got = transport.next_response(timeout_s)
            if got is None:
                raise self._idle_error("awaiting keys_ready",
                                       f"{ready}/{K} acks in")
            k, resp = got
            if resp["op"] != "keys_ready":
                raise RuntimeError(
                    f"unexpected {resp['op']!r} from client {k} during key "
                    "exchange")
            ready += 1
        self._secure_ready = True
        return self.keyx_ledger

    # -- tree setup (one-time relay configuration round) ----------------------

    def setup_tree(self, *, timeout_s: float = 120.0) -> None:
        """Ship each relay its child id list (one-time ``configure_relay``)
        and barrier on every ``relay_ready`` ack.  Idempotent; runs
        automatically on the first :meth:`submit_step`.  Star-degenerate
        trees (no relays) are a no-op."""
        if self.agg_tree is None:
            raise RuntimeError("setup_tree on a non-tree Executor "
                               "(construct with agg_tree=AggTree(...))")
        if self._tree_ready:
            return
        if self._inflight:
            raise RuntimeError("relay configuration must precede the first "
                               "step")
        relays = self.agg_tree.relays
        for r in relays:
            self.transport.submit(r, {
                "op": "configure_relay",
                "children": list(self.agg_tree.children(r)),
            })
        ready = 0
        while ready < len(relays):
            got = self.transport.next_response(timeout_s)
            if got is None:
                raise self._idle_error("during relay configuration",
                                       f"{ready}/{len(relays)} acks in")
            k, resp = got
            if resp["op"] != "relay_ready":
                raise RuntimeError(
                    f"unexpected {resp['op']!r} from client {k} during relay "
                    "configuration")
            ready += 1
        self._tree_ready = True

    # -- step halves ----------------------------------------------------------

    @property
    def inflight_steps(self) -> list[int]:
        """Steps submitted but not yet collected, oldest first."""
        return list(self._inflight)

    def submit_step(self, step: int, labels, *, features: Optional[list] = None,
                    ledger: Optional[Ledger] = None) -> None:
        """Ship every tower-forward request of ``step`` and register its
        in-flight state.

        ``features`` (per-client arrays, batch-major) are shipped in the
        forward requests; omit them when workers own a ``feature_fn``.
        ``labels`` is the role-0/3-side per-step context — a plain label
        array or any batch-major pytree (a SplitProgram's ``batch_ctx``);
        microbatch slicing maps over its leaves.  Each step audits its
        bytes in its OWN :class:`~repro.core.protocol.Ledger`.
        """
        transport, K, M = self.transport, self.transport.num_clients, self.microbatches
        if step in self._inflight:
            raise ValueError(f"step {step} already in flight")
        if not self._tree_ready:
            self.setup_tree()
        if self.secure_agg:
            if not self._secure_ready:
                self.setup_secure()
            # mask freshness: round indices derive from the step id, so a
            # recycled id (e.g. run_step's default step=0 called in a loop)
            # would reuse masks and let role 0 difference two uplinks to the
            # raw activation delta.  The workers enforce this too — this is
            # the friendly, early error naming the API misuse
            if step <= self._max_secure_step:
                raise ValueError(
                    f"secure aggregation needs strictly increasing step ids "
                    f"(got {step} after {self._max_secure_step}): the mask "
                    "round index derives from the step, and a reused round "
                    "leaks the raw activation delta — pass step= explicitly "
                    "when looping run_step")
            self._max_secure_step = step
        B = jax.tree_util.tree_leaves(labels)[0].shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by microbatches={M}")
        st = _InflightStep(
            step=step, labels=labels, mbsz=B // M,
            ledger=ledger if ledger is not None else Ledger(),
            submit_t=time.monotonic(),
            sent_jacs=[0] * K, done=[False] * K, grads=[None] * K,
        )
        self._inflight[step] = st

        # submit every tower forward upfront: clients stream microbatches in
        # order on their own resources (the overlap the pipeline exists for)
        for m in range(M):
            for spec in self._schedule.cuts:
                req = {"op": "forward", "step": step, "mb": m}
                if features is not None:
                    sl = slice(m * st.mbsz, (m + 1) * st.mbsz)
                    req["feats"] = features[spec.client][sl]
                transport.submit(spec.client, req)

    def collect_step(self, server_params, *, liveness=None, merge_mask=None,
                     ema_state: Optional[dict] = None,
                     collect_grads: bool = True,
                     report=None) -> ExecutionResult:
        """Collect the OLDEST in-flight step: merge its microbatches, run the
        role-0 forward/backward, fan jacobians out, barrier on ``step_done``.

        ``liveness`` is an (M, K) 0/1 matrix from a simulated clock; without
        it, ``"nowait"`` measures liveness against wall-clock deadlines and
        other modes barrier on all K cuts.  A ``report`` passed in (the
        simulated clock's) is returned untouched; otherwise a measured
        :class:`ExecReport` is built.
        """
        if not self._inflight:
            raise RuntimeError("no in-flight step to collect "
                               "(call submit_step first)")
        if self.agg_tree is not None and (liveness is not None
                                          or merge_mask is not None):
            raise ValueError(
                "tree aggregation is barrier-only: per-client liveness / "
                "merge_mask cannot be applied to a relay's combined frame "
                "(the partial sum already folded every subtree member in)")
        st = next(iter(self._inflight.values()))
        transport, K, M = self.transport, self.transport.num_clients, self.microbatches
        schedule = self._schedule
        # steps submitted after this one and still in flight — robust to
        # non-consecutive step ids and to barrier reuse of an executor
        staleness = sum(1 for s in self._inflight if s > st.step)
        mbsz = st.mbsz

        outs, live_matrix = [], []
        misses = [0] * K
        last_deadline: Optional[float] = self.static_deadline_s

        for m in range(M):
            live_row, deadline_used = self._gather(st, m, liveness)
            if deadline_used is not None:
                last_deadline = deadline_used
            for k in range(K):
                if live_row[k] <= 0:
                    misses[k] += 1
            live_matrix.append(live_row)
            st.merged.add(m)

            arrived = st.cuts.pop(m, {})
            if self.agg_tree is not None:
                # keys are the top-level clients; each frame is its whole
                # subtree's partial sum
                cuts_in = jnp.stack([arrived[t]
                                     for t in self.agg_tree.top_level])
            elif self.merge_fn is not None:
                # non-uniform program merge (e.g. vlm sequence concat):
                # cuts differ in shape per client, so there is no stack to
                # zero-fill — barrier modes guarantee every cut arrived
                if len(arrived) < K:
                    raise RuntimeError(
                        f"program merge needs every cut; microbatch {m} is "
                        f"missing clients "
                        f"{sorted(set(range(K)) - set(arrived))}")
                cuts_in = [arrived[k] for k in range(K)]
            else:
                proto = next(iter(arrived.values()))
                cuts_in = jnp.stack([
                    arrived.get(k, jnp.zeros_like(proto)) for k in range(K)
                ])
                if self.drop_policy == "impute" and ema_state is None:
                    ema_state = {
                        "ema": jnp.zeros((K, cuts_in.shape[-1]), jnp.float32),
                        "initialized": jnp.zeros((K,), jnp.float32),
                    }

            labels_m = jax.tree_util.tree_map(
                lambda a: a[m * mbsz:(m + 1) * mbsz], st.labels)
            live_vec = jnp.asarray(live_row, jnp.float32)

            def merge(cuts):
                if self.agg_tree is not None:
                    # final merge over the top-level partial sums; avg is
                    # the full-tree sum over K (NOT over len(top_level))
                    merged = fast_merge(cuts, "sum")
                    if self.merge == "avg":
                        merged = merged / K
                    return merged, ema_state
                if self.merge_fn is not None:
                    mask = merge_mask if self.drop_policy == "neutral" else None
                    return self.merge_fn(cuts, mask), ema_state
                if self.drop_policy == "impute":
                    imputed, new_ema = straggler_lib.impute_stack(
                        cuts, live_vec, ema_state, decay=self.ema_decay)
                    return fast_merge(imputed, self.merge), new_ema
                if self.drop_policy == "neutral":
                    return merge_lib.merge_stacked(
                        cuts, self.merge, live_mask=merge_mask), ema_state
                return fast_merge(cuts, self.merge), ema_state

            with jax.profiler.TraceAnnotation(
                    "executor.server_step", step=st.step, mb=m):
                merged, merge_vjp, ema_state = jax.vjp(merge, cuts_in,
                                                       has_aux=True)
                (loss_m, aux_m), (sg, merged_grad) = self._server_step(
                    server_params, merged, labels_m)
                cut_grads, = merge_vjp(merged_grad)
            # the logits' shape and dtype, from the program's trace
            head = self._heads[_signature(server_params, merged, labels_m)]
            st.ledger.record_spec(schedule.head_out, head)
            if self.server_aux:
                # the aux scalar rides the role-0 -> role-3 loss exchange
                st.ledger.record_spec(schedule.aux, aux_m)
            st.ledger.record_spec(schedule.head_jac, head)

            # (client, jacobian) per backward, shipped once the span below
            # has closed: a transport that runs its workers on this thread
            # (SimTransport) would otherwise nest their spans inside it
            backwards = []
            with jax.profiler.TraceAnnotation(
                    "executor.jac_fanout", step=st.step, mb=m):
                if self.agg_tree is not None:
                    # ONE backward per top-level client; relays forward the
                    # same jacobian down the tree (the additive merges give
                    # every subtree member the identical cut gradient —
                    # avg's 1/K is already inside cut_grads).  The ledger
                    # records every logical tree edge, and sent_jacs counts
                    # the backward each member receives via the router
                    # fan-out.
                    for i, t in enumerate(self.agg_tree.top_level):
                        jac_out = cut_grads[i]
                        for member in self.agg_tree.subtree(t):
                            st.ledger.record_spec(schedule.jacs[member],
                                                  jac_out)
                            st.sent_jacs[member] += 1
                        backwards.append((t, jac_out))
                else:
                    for spec in schedule.jacs:
                        k = spec.client
                        # serial/neutral semantics: jacobians flow to every
                        # client; no-wait: a missed deadline skips this
                        # microbatch's update
                        if self.drop_policy != "neutral" and live_row[k] <= 0:
                            continue
                        jac_out = cut_grads[k]
                        if self.compress is not None:
                            # symmetric downlink compression with error
                            # feedback: the residual this encode drops rides
                            # into the next step's jacobian for the same
                            # (client, mb) stream position
                            jac_out, self._jac_residuals[(k, m)] = \
                                comp_lib.compress_with_feedback(
                                    jac_out, self._jac_residuals.get((k, m)),
                                    self.compress, self.topk_fraction)
                            st.ledger.record_spec_bytes(
                                spec, comp_lib.payload_bytes(
                                    jac_out, self.compress,
                                    self.topk_fraction))
                        else:
                            st.ledger.record_spec(spec, jac_out)
                        st.sent_jacs[k] += 1
                        backwards.append((k, jac_out))
            for k, jac_out in backwards:
                transport.submit(k, {
                    "op": "backward", "step": st.step, "mb": m,
                    "jac": jac_out,
                })
            outs.append((loss_m, aux_m if self.server_aux else None, sg))

        for k in range(K):
            transport.submit(k, {
                "op": "finish_step", "step": st.step, "microbatches": M,
                "collect": collect_grads, "expected_jacs": st.sent_jacs[k],
            })
        while not all(st.done):
            if not self._pump(st.step, None):
                raise self._idle_error(
                    "awaiting step_done",
                    f"step {st.step}: {sum(st.done)}/{K} workers done")
        self._retire(st)

        # (loss, aux, server grads) over the microbatches; one needs no mean
        loss, aux, server_grads = outs[0] if M == 1 else self._mean(outs)
        tower_grads = list(st.grads) if collect_grads else None
        if report is None:
            report = self._build_report(
                time.monotonic() - st.submit_t, live_matrix, misses,
                st.ledger, last_deadline, staleness)
        return ExecutionResult(loss, tower_grads, server_grads, st.ledger,
                               report, ema_state, aux, step=st.step)

    def run_step(self, server_params, labels, *, step: int = 0,
                 features: Optional[list] = None, liveness=None,
                 merge_mask=None, ema_state: Optional[dict] = None,
                 ledger: Optional[Ledger] = None, collect_grads: bool = True,
                 report=None) -> ExecutionResult:
        """Execute one protocol step: ``submit_step`` + ``collect_step``
        back-to-back (window 1 — the blocking barrier call)."""
        self.submit_step(step, labels, features=features, ledger=ledger)
        return self.collect_step(
            server_params, liveness=liveness, merge_mask=merge_mask,
            ema_state=ema_state, collect_grads=collect_grads, report=report)

    # -- the shared event pump ------------------------------------------------

    def _pump(self, step: int, timeout: Optional[float]) -> bool:
        """Drain ONE transport response into its step's buffers; returns
        False on timeout/idle.  Safe under cross-step interleaving: every
        response is routed by its ``(step, mb)`` key.  ``step`` is the step
        role 0 is collecting, the ``transport.wait`` span's tag."""
        with jax.profiler.TraceAnnotation("transport.wait", step=step):
            got = self.transport.next_response(timeout)
        if got is None:
            return False
        k, resp = got
        op = resp["op"]
        if op == "cut":
            self._on_cut(k, resp)
        elif op == "step_done":
            st = self._inflight.get(resp["step"])
            if st is not None:
                st.done[k] = True
                if resp.get("grad") is not None:
                    st.grads[k] = jax.tree_util.tree_map(
                        jnp.asarray, resp["grad"])
        # "grad" responses are per-microbatch acks; nothing to do
        return True

    def _on_cut(self, k: int, resp: dict) -> None:
        now = time.monotonic()
        step, m = resp["step"], resp["mb"]
        st = self._inflight.get(step)
        if st is None:
            # the step was already collected (a no-wait straggler finishing
            # long after the fact): the payload is dropped, but the arrival
            # still feeds the EWMA so a recovered client can re-open the
            # deadline window
            first = self._retired_first_t.get((step, m))
            if self.deadline is not None and first is not None:
                self.deadline.observe(k, now - first)
            return
        if m not in st.first_t:
            st.first_t[m] = now
        if self.deadline is not None:
            spread = now - st.first_t[m]
            if self.mode == "nowait" and m not in st.merged:
                # this cut will make the merge — but role 0 may have drained
                # it long after delivery (busy on an earlier microbatch or
                # the expired-window sweep), so the raw drain spread can
                # include server time.  Clamp to the deadline window: a cut
                # that made the merge arrived within it by definition, and
                # an unclamped observation would let a busy role 0 inflate
                # the EWMA and loosen the deadline for no client reason.
                window = self.static_deadline_s
                if window is None:
                    window = self.deadline.deadline_s()
                if window is not None:
                    spread = min(spread, window)
            # genuinely late arrivals (mb already merged) observe their raw
            # spread — that is how a recovered straggler earns its way back
            self.deadline.observe(k, spread)
        if self.agg_tree is not None:
            # the arriving frame is a top-level client's combined subtree
            # partial sum; every edge under it carried exactly one frame of
            # the same uniform shape, so the logical per-edge schedule is
            # recorded exactly (tree_cut[l] tags)
            for member in self.agg_tree.subtree(k):
                st.ledger.record_spec(self._schedule.cuts[member],
                                      resp["cut"])
        elif self.compress is not None:
            # the payload is the worker's lossy encode; the ledger records
            # the codec's wire bytes (bitmap+values / int8 frame), not the
            # dense f32 carrier that crosses the loopback for convenience
            st.ledger.record_spec_bytes(
                self._schedule.cuts[k],
                comp_lib.payload_bytes(resp["cut"], self.compress,
                                       self.topk_fraction))
        else:
            st.ledger.record_spec(self._schedule.cuts[k], resp["cut"])
        if m in st.merged:
            return  # missed the merge: payload discarded at role 0
        st.cuts.setdefault(m, {})[k] = jnp.asarray(resp["cut"])

    def _retire(self, st: _InflightStep) -> None:
        del self._inflight[st.step]
        for m, t in st.first_t.items():
            self._retired_first_t[(st.step, m)] = t
        while len(self._retired_first_t) > _RETIRED_FIRST_T_KEEP:
            self._retired_first_t.pop(next(iter(self._retired_first_t)))

    # -- gathering ------------------------------------------------------------

    def _gather(self, st: _InflightStep, m: int, liveness):
        """Collect microbatch ``m``'s cuts; returns (live_row, deadline_s)."""
        K = self.transport.num_clients

        def have() -> int:
            return len(st.cuts.get(m, {}))

        if self.agg_tree is not None:
            # barrier on the min(F, K) top-level combined frames — this is
            # the O(K) -> O(F) role-0 serialization win
            need = len(self.agg_tree.top_level)
            while have() < need:
                if not self._pump(st.step, None):
                    raise self._idle_error(
                        "awaiting tree frames",
                        f"step {st.step} mb {m}: {have()}/{need} top-level "
                        "frames in")
            return [1.0] * K, None

        if liveness is not None:
            # simulated clock: the transport delivers every cut; the given
            # matrix decides who made the merge
            while have() < K:
                if not self._pump(st.step, None):
                    raise self._idle_error(
                        "awaiting cuts",
                        f"step {st.step} mb {m}: {have()}/{K} in")
            return [float(x) for x in liveness[m]], None

        if self.mode != "nowait":
            while have() < K:
                if not self._pump(st.step, None):
                    raise self._idle_error(
                        "awaiting cuts",
                        f"step {st.step} mb {m}: {have()}/{K} in")
            return [1.0] * K, None

        # real no-wait: grace window after the first arrival
        deadline_used = None
        while have() < K:
            if m not in st.first_t:
                self._pump(st.step, None)  # the first cut opens the window
                continue
            d = self.static_deadline_s
            if d is None:
                d = self.deadline.deadline_s()
            if d is None:
                # bootstrap barrier: no estimate yet, wait for everyone
                if not self._pump(st.step, None):
                    raise self._idle_error(
                        "awaiting cuts at the bootstrap barrier",
                        f"step {st.step} mb {m}: {have()}/{K} in")
                continue
            deadline_used = d
            remaining = (st.first_t[m] + d) - time.monotonic()
            if remaining <= 0:
                # window expired — but sweep the queue first: a cut that was
                # DELIVERED while role 0 was busy on an earlier microbatch
                # beat the deadline and must not be counted as a miss (the
                # drain timestamp, not the true arrival, is all we see)
                while have() < K and self._pump(st.step, 0.0):
                    pass
                if have() < K:
                    break
                continue
            self._pump(st.step, remaining)
        if (self.deadline is not None and self.deadline.initial_s is None
                and have() == K):
            # seed the adaptive controller from the first full barrier
            self.deadline.seed_from_observations()
        arrived = st.cuts.get(m, {})
        return [1.0 if k in arrived else 0.0 for k in range(K)], deadline_used

    def _build_report(self, elapsed_s, live_matrix, misses, ledger,
                      deadline_s, staleness) -> ExecReport:
        K = self.transport.num_clients
        if self.merge_fn is not None:
            # non-uniform program merge (e.g. vlm seq-concat): cuts differ
            # in shape per client, so the per-client figure is a mean
            cut_bytes = int(round(sum(
                ledger.bytes_with_tag(f"cut[{k}]") for k in range(K)) / K))
        else:
            # the uplink tag is masked_cut[0] under secure aggregation
            cut_bytes = ledger.bytes_with_tag(self._schedule.cuts[0].tag)
            if self.agg_tree is not None:
                # tree_cut[0] is shared by every top-level edge: divide out
                # for the same per-client per-step figure the star reports
                cut_bytes //= len(self.agg_tree.top_level)
        return ExecReport(
            mode=self.mode,
            transport=type(self.transport).__name__,
            step_time_s=elapsed_s,
            microbatches=self.microbatches,
            live=live_matrix,
            misses_per_client=misses,
            cut_bytes_per_client=cut_bytes,
            deadline_s=deadline_s,
            staleness=staleness,
            tower_platform=self.transport.tower_platform,
        )

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

A barrier step (``mode="serial"``) merges every client's cut with the fused
``kernels.merge_pool`` kernel, or with ``merge_lib.merge_stacked`` under a
caller's ``merge_mask`` (``protocol_step``'s ``live_mask``: a masked client
takes its merge's neutral element and still gets a jacobian).  A no-wait
step (``mode="nowait"``) first fills the seats a deadline left empty from
the per-client EMA (``repro.core.straggler``), and only live clients get a
jacobian.

Liveness comes either from a predetermined matrix (the simulated federation
clock of ``engine.simulate_pipelined`` — every payload still flows, the
clock just decides who made the merge) or, over a real transport in
``"nowait"`` mode, from wall-clock deadlines driven by the
:class:`~repro.runtime.deadline.AdaptiveDeadline` arrival EWMAs.
"""
from __future__ import annotations

import functools
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


# -- merge topologies, one picked at construction: the frames a microbatch
# waits for, their merge ``(live_vec, ema_state, cuts) -> (merged,
# ema_state)`` (run under ``jax.vjp``), the jacobians back, the cut records.

class _Star:
    """K uniform cuts, one frame per client, stacked and merged at role 0:
    the fused ``merge_pool`` kernel, after EMA imputation of the clients a
    deadline left out under no-wait.  A ``merge_mask`` selects
    ``merge_stacked`` and sends every client a jacobian; otherwise only the
    live clients get one."""

    def __init__(self, schedule, merge, *, nowait=False, secure=False,
                 compress=None, topk_fraction=0.25):
        self.schedule = schedule
        self.num_clients = self.frames = len(schedule.cuts)
        self.merge = merge
        self.mask_ok = not (nowait or secure)
        self.compress = compress
        self.topk_fraction = topk_fraction
        self._jac_residuals: dict = {}  # (client, mb) -> error feedback
        self._merge = self._impute if nowait else self._fused

    def merge_for(self, liveness, merge_mask) -> Callable:
        """The merge of one step, given its liveness and mask."""
        if merge_mask is None:
            return self._merge
        if not self.mask_ok:
            raise ValueError(
                "merge_mask is barrier-only: under secure aggregation a "
                "masked client's pairwise masks would not cancel, and "
                "no-wait takes its liveness from the deadlines")
        return functools.partial(self._masked, merge_mask)

    def _fused(self, live_vec, ema_state, cuts):
        return fast_merge(cuts, self.merge), ema_state

    def _impute(self, live_vec, ema_state, cuts):
        if ema_state is None:
            K, D = self.num_clients, cuts.shape[-1]
            ema_state = {"ema": jnp.zeros((K, D), jnp.float32),
                         "initialized": jnp.zeros((K,), jnp.float32)}
        imputed, ema_state = straggler_lib.impute_stack(cuts, live_vec,
                                                        ema_state)
        return fast_merge(imputed, self.merge), ema_state

    def _masked(self, merge_mask, live_vec, ema_state, cuts):
        return merge_lib.merge_stacked(cuts, self.merge,
                                       live_mask=merge_mask), ema_state

    def stack(self, arrived: dict):
        # a client no-wait left out takes a zero seat, which imputation fills
        proto = next(iter(arrived.values()))
        return jnp.stack([arrived.get(k, jnp.zeros_like(proto))
                          for k in range(self.num_clients)])

    def fanout(self, st, m, cut_grads, live_row, merge_mask) -> list:
        """Record and count microbatch ``m``'s jacobians; returns the
        ``(client, jacobian)`` backwards to ship."""
        backwards = []
        for spec in self.schedule.jacs:
            k = spec.client
            # a client the merge left out skips this microbatch's update
            if merge_mask is None and live_row[k] <= 0:
                continue
            jac_out = cut_grads[k]
            if self.compress is not None:
                jac_out, self._jac_residuals[(k, m)] = \
                    comp_lib.compress_with_feedback(
                        jac_out, self._jac_residuals.get((k, m)),
                        self.compress, self.topk_fraction)
            self._record(st.ledger, spec, jac_out)
            st.sent_jacs[k] += 1
            backwards.append((k, jac_out))
        return backwards

    def record_cut(self, ledger: Ledger, k: int, cut) -> None:
        self._record(ledger, self.schedule.cuts[k], cut)

    def _record(self, ledger: Ledger, spec, frame) -> None:
        if self.compress is None:
            ledger.record_spec(spec, frame)
        else:  # the codec's wire bytes, not the dense f32 carrier
            ledger.record_spec_bytes(spec, comp_lib.payload_bytes(
                frame, self.compress, self.topk_fraction))

    def cut_bytes(self, ledger: Ledger) -> int:
        # the uplink tag is masked_cut[0] under secure aggregation
        return ledger.bytes_with_tag(self.schedule.cuts[0].tag)


class _ProgramMerge(_Star):
    """A program ``merge_fn`` over cuts that differ in shape per client
    (the vlm sequence concatenation): barrier only, so every cut is in,
    and the list goes to ``merge_fn(cuts, merge_mask)`` unstacked."""

    def __init__(self, schedule, merge_fn: Callable):
        super().__init__(schedule, None)
        self.merge_fn = merge_fn

    def merge_for(self, liveness, merge_mask) -> Callable:
        return lambda live_vec, ema_state, cuts: (
            self.merge_fn(cuts, merge_mask), ema_state)

    def stack(self, arrived: dict):
        return [arrived[k] for k in range(self.num_clients)]

    def cut_bytes(self, ledger: Ledger) -> int:
        # cuts differ in shape per client, so the per-client figure is a mean
        return int(round(sum(ledger.bytes_with_tag(spec.tag)
                             for spec in self.schedule.cuts)
                         / self.num_clients))


class _Tree:
    """An aggregation tree: each of the ``min(F, K)`` top-level frames is
    its subtree's partial sum, merged with one final sum, and each
    top-level client gets ONE jacobian that its relays forward down
    unchanged (the additive merges give every subtree member the same cut
    gradient; avg's 1/K is already inside it)."""

    def __init__(self, schedule, merge, tree):
        self.schedule = schedule
        self.merge = merge
        self.tree = tree
        self.frames = len(tree.top_level)

    def merge_for(self, liveness, merge_mask) -> Callable:
        if liveness is not None or merge_mask is not None:
            raise ValueError(
                "tree aggregation is barrier-only: per-client liveness / "
                "merge_mask cannot be applied to a relay's combined frame "
                "(the partial sum already folded every subtree member in)")
        return self._merge

    def _merge(self, live_vec, ema_state, cuts):
        # avg is the full-tree sum over K (NOT over len(top_level))
        merged = fast_merge(cuts, "sum")
        if self.merge == "avg":
            merged = merged / self.tree.num_clients
        return merged, ema_state

    def stack(self, arrived: dict):
        return jnp.stack([arrived[t] for t in self.tree.top_level])

    def fanout(self, st, m, cut_grads, live_row, merge_mask) -> list:
        # the ledger and sent_jacs count every logical tree edge
        backwards = []
        for i, t in enumerate(self.tree.top_level):
            jac_out = cut_grads[i]
            for member in self.tree.subtree(t):
                st.ledger.record_spec(self.schedule.jacs[member], jac_out)
                st.sent_jacs[member] += 1
            backwards.append((t, jac_out))
        return backwards

    def record_cut(self, ledger: Ledger, k: int, cut) -> None:
        # every edge under a top-level frame carried exactly one frame of
        # the same uniform shape (tree_cut[l] tags)
        for member in self.tree.subtree(k):
            ledger.record_spec(self.schedule.cuts[member], cut)

    def cut_bytes(self, ledger: Ledger) -> int:
        # tree_cut[0] is shared by every top-level edge: divide out for the
        # same per-client per-step figure the star reports
        return (ledger.bytes_with_tag(self.schedule.cuts[0].tag)
                // len(self.tree.top_level))


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
      sequence concatenation); barrier only (no EMA imputation of a
      non-uniform stack).

    Secure aggregation (``secure_agg=True``, ``repro.core.secure_agg``):
    :meth:`setup_secure` runs the one-time in-protocol key exchange (run
    automatically on the first ``submit_step`` otherwise), after which the
    workers mask every cut uplink at the source and role 0 merges MASKED
    cuts — the pairwise masks cancel in the sum/avg merge, so only the
    aggregate is meaningful and no raw activation is ever observed.

    Cut compression (``compress`` = ``"topk"`` | ``"int8"``,
    ``repro.core.compression``): the workers compress the cut uplinks and
    role 0 the K jacobian downlinks, each stream with its own per-(client,
    mb) error feedback, step-sequential at any window W since steps are
    collected oldest-first.  The ledger records the codec's wire bytes both
    ways, which must reconcile exactly with ``costs.wire_bytes``.

    Tree aggregation (``agg_tree`` = :class:`~repro.runtime.topology.
    AggTree`): the transport is wrapped in a
    :class:`~repro.transport.tree.TreeRouter` (exposed as
    ``self.transport`` — callers who ``close()`` should close THAT), whose
    relay workers partial-sum their subtree's cut uplinks, so role 0
    gathers and merges ``min(F, K)`` frames per microbatch instead of K.
    ``setup_tree`` ships the one-time ``configure_relay`` round (run
    automatically on the first ``submit_step``).  The Ledger still audits
    the exact LOGICAL per-edge schedule (``tree_cut[l]``/``tree_jac[l]``).
    Composes with ``secure_agg``: partial sums of masked cuts stay blinded
    at relays and the pairwise masks cancel in role 0's full-tree sum.

    Every unsound composition of these features with each other, with
    ``nowait``, with a non-additive merge or with a program ``merge_fn``
    raises HERE, loudly, through :mod:`repro.core.compat`.

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
                 merge: str, *, mode: str = "serial", microbatches: int = 1,
                 deadline=None, server_takes_batch: bool = False,
                 server_aux: bool = False, merge_fn: Optional[Callable] = None,
                 secure_agg: bool = False, secure_scale: float = 1.0,
                 compress: Optional[str] = None, topk_fraction: float = 0.25,
                 agg_tree=None):
        if mode not in ("serial", "nowait"):
            raise ValueError(f"mode must be serial|nowait, got {mode!r}")
        nowait = mode == "nowait"
        if compress is not None and compress not in comp_lib.SCHEMES:
            raise ValueError(
                f"unknown compression scheme {compress!r} (choose from "
                f"{comp_lib.SCHEMES})")
        # every unsound feature composition rejects through the ONE
        # compat matrix (repro.core.compat) — the rule reasons carry the
        # full why
        compat.check(
            "executor", secure=secure_agg, compress=compress, tree=agg_tree,
            merge=merge, merge_fn=merge_fn, nowait=nowait,
            context=f"Executor(mode={mode!r})")
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
        self.mode = mode
        self.microbatches = microbatches
        self.server_takes_batch = server_takes_batch
        self.server_aux = server_aux
        self.secure_agg = secure_agg
        self.secure_scale = secure_scale
        self._secure_ready = False
        self._max_secure_step = -1  # highest masked step id (freshness)
        # one-time key-exchange round audit (keyx_pub/keyx_bcast tags)
        self.keyx_ledger = Ledger()
        # deadline: a float is a static window (reported as deadline_s);
        # only a no-wait executor adapts one, bootstrapped from the first
        # full barrier unless an AdaptiveDeadline is given
        self.deadline = self.static_deadline_s = None
        if deadline is not None and not isinstance(deadline, AdaptiveDeadline):
            self.static_deadline_s = float(deadline)
        elif nowait:
            self.deadline = deadline or AdaptiveDeadline(transport.num_clients)
        self._schedule = step_schedule(transport.num_clients,
                                       secure=secure_agg, compress=compress,
                                       tree=agg_tree)
        if agg_tree is not None:
            self._topology = _Tree(self._schedule, merge, agg_tree)
        elif merge_fn is not None:
            self._topology = _ProgramMerge(self._schedule, merge_fn)
        else:
            self._topology = _Star(
                self._schedule, merge, nowait=nowait, secure=secure_agg,
                compress=compress, topk_fraction=topk_fraction)
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
        ``"serial"`` barriers on every cut.  ``merge_mask`` (K,) masks
        clients out of a barrier step's merge.  A ``report`` passed in (the
        simulated clock's) is returned untouched; otherwise a measured
        :class:`ExecReport` is built.
        """
        topology = self._topology
        merge = topology.merge_for(liveness, merge_mask)
        if not self._inflight:
            raise RuntimeError("no in-flight step to collect "
                               "(call submit_step first)")
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

            cuts_in = topology.stack(st.cuts.pop(m, {}))
            labels_m = jax.tree_util.tree_map(
                lambda a: a[m * mbsz:(m + 1) * mbsz], st.labels)
            live_vec = jnp.asarray(live_row, jnp.float32)

            with jax.profiler.TraceAnnotation(
                    "executor.server_step", step=st.step, mb=m):
                merged, merge_vjp, ema_state = jax.vjp(
                    functools.partial(merge, live_vec, ema_state), cuts_in,
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

            # the backwards are shipped once the span below has closed: a
            # transport that runs its workers on this thread (SimTransport)
            # would otherwise nest their spans inside it
            with jax.profiler.TraceAnnotation(
                    "executor.jac_fanout", step=st.step, mb=m):
                backwards = topology.fanout(st, m, cut_grads, live_row,
                                            merge_mask)
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
            if m not in st.merged:
                # this cut will make the merge — but role 0 may have drained
                # it long after delivery (busy on an earlier microbatch or
                # the expired-window sweep), so the raw drain spread can
                # include server time.  Clamp to the deadline window: a cut
                # that made the merge arrived within it by definition, and
                # an unclamped observation would let a busy role 0 inflate
                # the EWMA and loosen the deadline for no client reason.
                window = self.deadline.deadline_s()
                if window is not None:
                    spread = min(spread, window)
            # genuinely late arrivals (mb already merged) observe their raw
            # spread — that is how a recovered straggler earns its way back
            self.deadline.observe(k, spread)
        self._topology.record_cut(st.ledger, k, resp["cut"])
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
        """Collect microbatch ``m``'s frames; returns (live_row, deadline_s)."""
        K = self.transport.num_clients

        def have() -> int:
            return len(st.cuts.get(m, {}))

        if liveness is not None or self.mode == "serial":
            # barrier on every frame the topology merges; a simulated clock
            # delivers every cut too, and its matrix decides who made the
            # merge
            need = self._topology.frames
            while have() < need:
                if not self._pump(st.step, None):
                    raise self._idle_error(
                        "awaiting cuts",
                        f"step {st.step} mb {m}: {have()}/{need} in")
            return ([1.0] * K if liveness is None
                    else [float(x) for x in liveness[m]]), None

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
        return ExecReport(
            mode=self.mode,
            transport=type(self.transport).__name__,
            step_time_s=elapsed_s,
            microbatches=self.microbatches,
            live=live_matrix,
            misses_per_client=misses,
            cut_bytes_per_client=self._topology.cut_bytes(ledger),
            deadline_s=deadline_s,
            staleness=staleness,
            tower_platform=self.transport.tower_platform,
        )

"""Transport interface + the role-1/3 worker logic + the inline backend.

``TowerWorker`` is the feature-holder endpoint, transport-agnostic: it owns
this client's tower params (and optionally a local optimizer and feature
source) and serves the request ops documented in the package docstring.
Backends differ only in WHERE ``handle`` runs (caller's thread, a worker
thread, another process) and how requests/responses move.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import compression as comp_lib
from repro.core import secure_agg
from repro.transport import ops as ops_registry


def counted_jit(traces: dict, name: str, fn: Callable) -> Callable:
    """``jax.jit(fn)``, counting in ``traces[name]`` each time jax traces
    it (the Python body runs only then)."""
    def traced(*args):
        traces[name] += 1
        return fn(*args)
    return jax.jit(traced)


class Transport:
    """Star-topology message plane; role 0 (the executor) is the caller."""

    num_clients: int

    @property
    def tower_platform(self) -> str:
        """JAX platform the tower workers compute on.  In-process workers
        share the caller's backend; process backends report their
        children's."""
        return jax.default_backend()

    def submit(self, client: int, request: dict) -> None:
        raise NotImplementedError

    def next_response(self, timeout: Optional[float] = None):
        """Next ``(client, response)`` from any client, else ``None`` on
        timeout.  FIFO per client; cross-client order is arrival order."""
        raise NotImplementedError

    def close(self) -> None:
        raise NotImplementedError

    def __enter__(self) -> "Transport":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class TowerWorker:
    """Role-1/3 endpoint: tower forward/backward + optional local update.

    ``tower_fwd(params, feats) -> cut``; the backward objective is the same
    f32 vdot as ``protocol_step`` so gradients agree bit-for-bit with the
    serial path.

    The per-step work runs as three compiled programs, built once in
    ``__init__`` and cached by ``jax.jit`` per input shape and dtype: the
    forward (``tower_fwd``), the backward (``(params, feats, jac) ->
    grad``, the recomputed forward and its vjp, with ``feats`` and ``jac``
    as arguments so that no step's data becomes a program constant) and
    the local optimizer update.  Every family, transport and microbatch
    shape goes through the same three; a new shape (an uneven last
    microbatch, another family's tower) compiles once more.  ``traces``
    counts how often each was traced.  No buffer is donated: the
    ``_step_params`` snapshot a step's forwards ran under must outlive the
    update that replaces ``self.params`` while later steps are in flight
    (W > 1), and a donated update would free the arrays behind it.

    ``feature_fn(step, mb) -> feats`` lets the worker own its data
    (multiproc children regenerate slices from the shared seed); requests
    may instead carry ``feats`` inline (sim/inproc wrappers).
    ``optimizer`` (repro.optim-style ``init``/``update``) enables local
    parameter updates at ``finish_step`` — the real split-learning flow,
    where tower params never leave the client.  ``forward_delay_s``
    artificially slows this client's forwards: the wall-clock straggler
    scenario the no-wait deadlines exist for, injectable on any transport.

    Cross-step pipelining (the executor's ``submit_step``/``collect_step``
    halves driven at window W > 1) means step t+1 forwards arrive BEFORE
    step t's jacobians, so all per-step state is buffered by step:

    * forwards snapshot the params they ran under (``_step_params``) and
      backwards linearize at that snapshot — the jacobian the server
      returns was computed against the snapshot's cut, so linearizing at
      post-update params would be inconsistent.  At W > 1 the snapshot is
      one optimizer update behind the submitted forward (delayed-gradient
      semantics); at W = 1 it IS the current params and nothing changes.
    * gradient accumulators and pending features are per step, so
      ``finish_step`` for step t cannot clobber step t+1's in-flight state.
    * a ``finish_step`` carrying ``expected_jacs`` defers its optimizer
      update until that many backwards for its step have actually landed
      (FIFO transports always deliver jacobians first, but the protocol
      stays safe for reordering backends); the deferred ``step_done`` is
      returned by the completing backward.

    Secure aggregation (``repro.core.secure_agg``): the one-time
    ``key_exchange`` op runs in two phases — ``"pub"`` draws an ephemeral
    DH keypair and returns the public value; ``"finish"`` delivers the full
    public directory (plus ``microbatches``/``scale``) and derives one
    shared mask key per peer, locally, so role 0 relays public values but
    never holds a pair's seed.  Once keys are set, every forward masks its
    cut AT THE SOURCE with fresh per-round noise
    (``round_idx = step * microbatches + mb`` — unique per (step,
    microbatch) at any driver window W, so masks are never reused and
    consecutive uplinks cannot be differenced to raw activation deltas).

    Cut compression (``compress`` = ``"topk"`` | ``"int8"``,
    ``repro.core.compression``): every forward compresses its cut AT THE
    SOURCE with error feedback — the residual a step's lossy encode drops
    is kept per microbatch (``_ef_residual``) and folded into the NEXT
    step's payload for that same stream position.  The accumulator is
    stream state, not per-step state: requests arrive FIFO in (step, mb)
    ascending order on every backend, so the step-sequential
    carry-and-update is well-defined at any driver window W (step t+1's
    forward for mb m can only arrive after step t's did, whatever else is
    in flight).  Step-0 residuals are zero, which is what lets
    ``train_split`` verify the compressed step-0 gradients against a
    serial ``protocol_step`` running the same compression.  Compression
    does not compose with secure aggregation (masks do not cancel through
    quantized values); the worker refuses key exchange when compressing,
    mirroring the Executor's constructor-time rejection.

    Tree aggregation (``runtime.topology.AggTree``): a one-time
    ``configure_relay`` op turns this worker into a RELAY — it learns its
    child ids and, instead of uplinking its own cut, accumulates a partial
    sum of its subtree: its own forward plus one ``aggregate`` frame per
    child (each itself a subtree partial sum).  Parts are buffered per
    (step, mb) and may arrive in ANY order across adjacent in-flight
    steps; the accumulator returns ``None`` until all ``1 + len(children)``
    parts landed, then sums them in a FIXED deterministic order (own cut
    first, children in configured id order — run-to-run reproducible
    despite f32 reassociation) and emits ONE combined ``tree_cut`` frame
    for the router to forward upstream.  Masked cuts partial-sum the same
    way (pairwise masks cancel only in the root's full sum — a relay's
    partial sum stays blinded, which is the Secure Forward Aggregation
    composition).  Jacobian fan-out rides the ``backward`` op: for the
    additive merges every subtree member receives the SAME jacobian the
    relay got (d merged / d partial = 1 for sum, 1/K pre-applied by role 0
    for avg), so the relay's backward response carries a ``relay_jac``
    directive the router turns into child backwards — no second jacobian
    computation anywhere.  ``configure_relay`` refuses a compressing
    worker (codec frames cannot be partial-summed), mirroring the
    Executor's constructor-time tree+compress rejection.
    """

    def __init__(self, client_id: int, tower_fwd: Callable, tower_params, *,
                 feature_fn: Optional[Callable] = None, optimizer=None,
                 forward_delay_s: float = 0.0,
                 compress: Optional[str] = None,
                 topk_fraction: float = 0.25,
                 serve_fns=None):
        self.client_id = client_id
        self.params = tower_params
        self.feature_fn = feature_fn
        self.optimizer = optimizer
        self.forward_delay_s = forward_delay_s
        if compress is not None and compress not in comp_lib.SCHEMES:
            raise ValueError(
                f"client {client_id}: unknown compression scheme "
                f"{compress!r} (choose from {comp_lib.SCHEMES})")
        self.compress = compress
        self.topk_fraction = topk_fraction
        self.serve_fns = serve_fns  # TowerServeFns when the family serves
        self.opt_state = optimizer.init(tower_params) if optimizer else None
        self._traces = {"forward": 0, "backward": 0, "update": 0}
        self._tower_fwd = counted_jit(self._traces, "forward", tower_fwd)

        def tower_grad(params, feats, jac):
            return jax.grad(lambda tp: jnp.vdot(
                tower_fwd(tp, feats).astype(jnp.float32),
                jac.astype(jnp.float32)))(params)

        self._tower_grad = counted_jit(self._traces, "backward", tower_grad)
        self._update = (counted_jit(self._traces, "update", optimizer.update)
                        if optimizer else None)
        self._feats: dict = {}  # (step, mb) -> feats awaiting backward
        self._step_params: dict = {}  # step -> params its forwards ran under
        self._grad_sums: dict = {}  # step -> accumulated tower grads
        self._jacs_seen: dict = {}  # step -> backwards processed
        self._pending_finish: dict = {}  # step -> deferred finish request
        self._ef_residual: dict = {}  # mb -> error-feedback residual carry
        self._dh_secret: Optional[int] = None  # ephemeral, key exchange only
        self._secure: Optional[dict] = None  # pair keys + round derivation
        self._relay_children: tuple = ()  # child ids when acting as a relay
        self._relay_parts: dict = {}  # (step, mb) -> {"self"|child_id: cut}
        self._serve_sessions: dict = {}  # request id -> tower KV session

    @property
    def traces(self) -> dict:
        """How many times each compiled program was traced: one per input
        shape that reached it."""
        return dict(self._traces)

    # -- ops ----------------------------------------------------------------

    def handle(self, request: dict) -> Optional[dict]:
        """Dispatch one request through the declarative op table
        (:data:`repro.transport.ops.WORKER_OPS`) — the registry IS the
        set of verbs this worker serves."""
        op = request["op"]
        spec = ops_registry.WORKER_OPS.get(op)
        if spec is None:
            raise ValueError(f"unknown op {op!r}")
        return getattr(self, spec.handler)(request)

    def _aggregate(self, request: dict) -> Optional[dict]:
        return self._relay_accumulate(
            request["step"], request["mb"], request["child"],
            jnp.asarray(request["frame"]))

    def _serve_end(self, request: dict) -> None:
        # fire-and-forget session teardown: nothing to reply, the driver
        # retires the request without a barrier
        self._serve_sessions.pop(request["request"], None)
        return None

    def _get_params(self, request: dict) -> dict:
        return {"op": "params", "client": self.client_id,
                "params": self.params}

    def _shutdown(self, request: dict) -> dict:
        return {"op": "bye", "client": self.client_id}

    # -- serving ops --------------------------------------------------------

    def _require_serving(self) -> None:
        if self.serve_fns is None:
            raise ValueError(
                f"client {self.client_id}: no serve_fns configured — split "
                "serving needs the program's tower serving bundle "
                "(SplitProgram.tower_serve_fns; dense family only)")
        # the worker's own guard (it must not trust the driver): serving
        # frames are raw cut tensors
        compat.check("worker", serve=True, secure=self._secure is not None,
                     compress=self.compress,
                     context=f"client {self.client_id}")

    def _serve_prefill(self, request: dict) -> dict:
        """One-time per-request tower prefill: embed the prompt through the
        private embedding columns, fill a fresh tower KV session, uplink
        the full-prompt cut slice.  Re-prefilling an existing request id
        RESETS its session — the driver's readmission path after a role-0
        cut-cache eviction."""
        self._require_serving()
        rid = request["request"]
        tokens = jnp.asarray(request["tokens"], jnp.int32).reshape(1, -1)
        cut, session = self.serve_fns.prefill(
            self.params, tokens, int(request["cache_len"]))
        self._serve_sessions[rid] = session
        return {"op": "serve_prefill_cut", "client": self.client_id,
                "request": rid, "cut": cut}

    def _serve_decode(self, request: dict) -> dict:
        """One decode round for one request: advance the request's tower
        session by the last sampled token and uplink the (1, 1, cut) frame.
        The frame echoes ``pos`` — the driver's ``(request, position)``
        response key — and the worker checks it against the session clock,
        so a desynchronized driver fails loudly instead of silently
        decoding against the wrong cache slot."""
        self._require_serving()
        rid, pos = request["request"], int(request["pos"])
        session = self._serve_sessions.get(rid)
        if session is None:
            raise ValueError(
                f"client {self.client_id}: serve_decode for unknown "
                f"request {rid!r} — prefill first (or the session was "
                "ended/evicted without readmission)")
        have = int(session["index"])
        if have != pos:
            raise ValueError(
                f"client {self.client_id}: request {rid!r} decode position "
                f"mismatch — driver says {pos}, tower session is at {have}")
        token = jnp.asarray(request["token"], jnp.int32).reshape(1)
        cut, session = self.serve_fns.decode(self.params, session, token)
        self._serve_sessions[rid] = session
        return {"op": "serve_cut", "client": self.client_id, "request": rid,
                "pos": pos, "cut": cut}

    def _forward(self, request: dict) -> dict:
        if self.forward_delay_s > 0.0:
            time.sleep(self.forward_delay_s)
        step, mb = request["step"], request["mb"]
        with jax.profiler.TraceAnnotation("tower.forward", step=step, mb=mb,
                                          client=self.client_id):
            feats = request.get("feats")
            if feats is None:
                if self.feature_fn is None:
                    raise ValueError(
                        f"client {self.client_id}: no feats in request and no "
                        "feature_fn configured")
                feats = self.feature_fn(step, mb)
            feats = jnp.asarray(feats)
            self._feats[(step, mb)] = feats
            params = self._step_params.setdefault(step, self.params)
            cut = self._tower_fwd(params, feats)
            if self._secure is not None:
                # mask at the source: role 0 only ever observes the blinded
                # cut.  round_idx is unique per (step, mb) at any driver
                # window W, so masks are never reused across uplinks
                # (differencing two steps' masked cuts yields noise, not the
                # raw activation delta).  The worker — not role 0 — enforces
                # freshness: requests arrive FIFO in (step, mb) order, so a
                # non-increasing round means a replayed or recycled step id,
                # and sending a reused mask would let the server difference
                # two uplinks to the raw activation delta
                sec = self._secure
                round_idx = step * sec["microbatches"] + mb
                if round_idx <= sec["last_round"]:
                    raise ValueError(
                        f"client {self.client_id}: mask round {round_idx} "
                        f"(step {step}, mb {mb}) already used (last "
                        f"{sec['last_round']}) — reusing a mask round leaks "
                        "the raw activation delta; drive secure steps with "
                        "strictly increasing step ids")
                sec["last_round"] = round_idx
                cut = secure_agg.mask_payload_with_keys(
                    cut, sec["pair_keys"], self.client_id, round_idx,
                    sec["scale"])
            if self.compress is not None:
                # compress at the source with error feedback: fold in what
                # the previous step's encode dropped for this stream
                # position, ship the lossy payload, carry the new leftover.
                # FIFO delivery makes the per-mb carry step-sequential at
                # any driver window W
                cut, self._ef_residual[mb] = \
                    comp_lib.compress_with_feedback(
                        cut, self._ef_residual.get(mb), self.compress,
                        self.topk_fraction)
        if self._relay_children:
            # relay: this cut is one part of the subtree partial sum; the
            # combined frame is emitted once every child's frame landed too
            return self._relay_accumulate(step, mb, "self", cut)
        return {"op": "cut", "client": self.client_id, "step": step,
                "mb": mb, "cut": cut}

    def _configure_relay(self, request: dict) -> dict:
        # the worker's own guard, mirroring the Executor's constructor-time
        # tree+compress rejection
        compat.check("worker", tree=True, compress=self.compress,
                     context=f"client {self.client_id}")
        self._relay_children = tuple(int(c) for c in request["children"])
        return {"op": "relay_ready", "client": self.client_id}

    def _relay_accumulate(self, step: int, mb: int, part_key,
                          frame) -> Optional[dict]:
        parts = self._relay_parts.setdefault((step, mb), {})
        if part_key in parts:
            raise ValueError(
                f"client {self.client_id}: duplicate aggregation part "
                f"{part_key!r} for (step {step}, mb {mb})")
        parts[part_key] = frame
        if len(parts) < 1 + len(self._relay_children):
            return None  # subtree incomplete — parts arrive in any order
        del self._relay_parts[(step, mb)]
        # fixed accumulation order: own cut first, then children in
        # configured id order — deterministic rounding run to run
        total = parts["self"]
        for child in self._relay_children:
            total = total + parts[child]
        return {"op": "tree_cut", "client": self.client_id, "step": step,
                "mb": mb, "cut": total}

    def _key_exchange(self, request: dict) -> dict:
        # the privacy principal's own guard: a compressing worker must not
        # join a key exchange, whatever the driver says (checked BEFORE the
        # phase is read, so a malformed request still rejects loudly)
        compat.check("worker", secure=True, compress=self.compress,
                     context=f"client {self.client_id}")
        phase = request["phase"]
        if phase == "pub":
            self._dh_secret, pub = secure_agg.dh_keypair()
            return {"op": "pub", "client": self.client_id, "pub": pub}
        if phase == "finish":
            if self._dh_secret is None:
                raise ValueError(
                    f"client {self.client_id}: key_exchange finish before "
                    "pub phase")
            pair_keys = {}
            for other, peer_pub in request["pubs"].items():
                other = int(other)
                if other == self.client_id:
                    continue
                shared = secure_agg.dh_shared(self._dh_secret, peer_pub)
                pair_keys[other] = secure_agg.seed_from_shared(shared)
            self._dh_secret = None  # ephemeral: drop it once keys exist
            self._secure = {
                "pair_keys": pair_keys,
                "microbatches": int(request.get("microbatches", 1)),
                "scale": float(request.get("scale", 1.0)),
                "last_round": -1,  # freshness floor: rounds must increase
            }
            return {"op": "keys_ready", "client": self.client_id}
        raise ValueError(f"unknown key_exchange phase {phase!r}")

    def _backward(self, request: dict) -> dict:
        step, mb = request["step"], request["mb"]
        feats = self._feats.pop((step, mb))
        jac = jnp.asarray(request["jac"])
        # linearize at the params this step's forwards ran under: the
        # server's jacobian is w.r.t. THAT cut, and at W > 1 a later step's
        # finish may already have moved self.params past the snapshot
        base = self._step_params.get(step, self.params)
        # the span ends before a deferred finish below runs the update,
        # which has its own span
        with jax.profiler.TraceAnnotation("tower.backward", step=step, mb=mb,
                                          client=self.client_id):
            grad = self._tower_grad(base, feats, jac)
            prev = self._grad_sums.get(step)
            self._grad_sums[step] = grad if prev is None else \
                jax.tree_util.tree_map(jnp.add, prev, grad)
        self._jacs_seen[step] = self._jacs_seen.get(step, 0) + 1
        pending = self._pending_finish.get(step)
        if pending is not None and \
                self._jacs_seen[step] >= pending.get("expected_jacs", 0):
            del self._pending_finish[step]
            resp = self._complete_finish(pending)
        else:
            resp = {"op": "grad", "client": self.client_id, "step": step,
                    "mb": mb}
        if self._relay_children:
            # fan the SAME jacobian down the tree: for the additive merges
            # every subtree member's cut gradient equals the relay's (role 0
            # pre-applies the 1/K of avg), so the relay forwards its received
            # jac verbatim — the router turns this directive into one
            # backward per child
            resp["relay_jac"] = {"step": step, "mb": mb, "jac": jac,
                                 "children": list(self._relay_children)}
        return resp

    def _finish_step(self, request: dict) -> Optional[dict]:
        step = request["step"]
        expected = request.get("expected_jacs")
        if expected is not None and self._jacs_seen.get(step, 0) < expected:
            # jacobians for this step still in flight (a non-FIFO backend):
            # defer the update; the completing backward returns step_done
            self._pending_finish[step] = request
            return None
        return self._complete_finish(request)

    def _complete_finish(self, request: dict) -> dict:
        step = request["step"]
        M = request.get("microbatches", 1)
        # microbatches whose jacobian never arrived (no-wait misses)
        # contribute zero — dividing the SUM by M reproduces the serial
        # path's zero-padded tree_mean exactly
        grad_sum = self._grad_sums.pop(step, None)
        if grad_sum is None:
            avg = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        else:
            avg = jax.tree_util.tree_map(lambda g: g / M, grad_sum)
        if self.optimizer is not None:
            with jax.profiler.TraceAnnotation("tower.update", step=step,
                                              client=self.client_id):
                self.params, self.opt_state = self._update(
                    self.params, avg, self.opt_state)
        self._step_params.pop(step, None)
        self._jacs_seen.pop(step, None)
        # only THIS step's leftovers (no-wait misses); later steps' feats
        # are awaiting their own jacobians
        self._feats = {key: v for key, v in self._feats.items()
                       if key[0] != step}
        return {"op": "step_done", "client": self.client_id, "step": step,
                "grad": avg if request.get("collect") else None}


class SimTransport(Transport):
    """Inline backend: ``submit`` runs the worker on the calling thread and
    queues the response.  Fully deterministic, zero concurrency — the
    numerics engine behind ``protocol_step`` / ``pipelined_step`` (the
    federation clock is simulated separately by ``repro.runtime.engine``)."""

    def __init__(self, workers: list[TowerWorker]):
        self.workers = workers
        self.num_clients = len(workers)
        self._responses: deque = deque()

    def submit(self, client: int, request: dict) -> None:
        resp = self.workers[client].handle(request)
        if resp is not None and resp["op"] != "bye":
            self._responses.append((client, resp))

    def next_response(self, timeout: Optional[float] = None):
        if not self._responses:
            return None
        return self._responses.popleft()

    def close(self) -> None:
        self._responses.clear()

"""Role-based split-learning protocol simulator with a communications ledger.

The paper (via Ceballos et al. 2020) assigns each participant a role:

* role 1 — holds features only: runs a tower forward, ships the cut
  activation, receives its jacobian, runs the tower backward.
* role 3 — holds features AND labels: like role 1, plus it computes the loss
  from the server's head output.
* role 0 — compute-only server: merges cut activations, runs the server
  network forward and backward, returns per-client jacobians.

On a real deployment each role is a host; here every message is recorded in
a :class:`Ledger` whose byte counts must match the analytic model in
repro.core.costs (asserted in tests).  The arithmetic is exactly equivalent
to end-to-end backprop through the merged graph — the protocol is a
*schedule*, not a different algorithm (paper §3: "functionally identical").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import compat
from repro.core import merge as merge_lib


@dataclass
class Message:
    sender: str
    receiver: str
    tag: str
    num_bytes: int


@dataclass
class Ledger:
    messages: list[Message] = field(default_factory=list)

    def record(self, sender: str, receiver: str, tag: str, array) -> None:
        self.record_bytes(sender, receiver, tag,
                          array.size * array.dtype.itemsize)

    def record_bytes(self, sender: str, receiver: str, tag: str,
                     num_bytes: int) -> None:
        """Record a non-array payload of known wire size (the key-exchange
        group elements are fixed-size integers, not tensors)."""
        self.messages.append(Message(sender, receiver, tag, num_bytes))

    def record_spec(self, spec: "MessageSpec", array) -> None:
        self.record(spec.sender, spec.receiver, spec.tag, array)

    def record_spec_bytes(self, spec: "MessageSpec", num_bytes: int) -> None:
        self.record_bytes(spec.sender, spec.receiver, spec.tag, num_bytes)

    def sent_by(self, who: str) -> int:
        return sum(m.num_bytes for m in self.messages if m.sender == who)

    def received_by(self, who: str) -> int:
        return sum(m.num_bytes for m in self.messages if m.receiver == who)

    def bytes_with_tag(self, tag: str) -> int:
        return sum(m.num_bytes for m in self.messages if m.tag == tag)

    def total(self) -> int:
        return sum(m.num_bytes for m in self.messages)


#: the client that also holds the labels (role 3); the others are role 1
LABEL_HOLDER = 0


def _role_of(client: int) -> str:
    return "role3" if client == LABEL_HOLDER else "role1"


# ---------------------------------------------------------------------------
# message schedule (paper §4.4) — ONE definition shared by the serial
# protocol_step below and the pipelined runtime (repro.runtime.engine)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WireKind:
    """One registered message kind: its uplink/downlink direction, the
    protocol phase it belongs to, and the ``repro.core.costs`` function
    that prices its bytes (named, not referenced, so ``costs`` stays
    import-light — the analyzer verifies the function exists)."""

    kind: str
    direction: str  # "up" (toward role 0) | "down" (from role 0)
    phase: str      # "train" | "keyx" | "serve"
    cost_model: str  # function name in repro.core.costs


#: THE wire-kind registry — every ``MessageSpec.kind`` anywhere in the
#: stack must be one of these (validated at MessageSpec construction and
#: statically by ``repro.analysis``: every registered kind must have a
#: cost model in repro.core.costs, a schedule producer in this module,
#: and at least one tests/ reconciliation reference).
WIRE_KINDS: dict[str, WireKind] = {spec.kind: spec for spec in (
    WireKind(kind="cut", direction="up", phase="train",
             cost_model="cut_bytes"),
    WireKind(kind="masked_cut", direction="up", phase="train",
             cost_model="masked_cut_bytes"),
    WireKind(kind="compressed_cut", direction="up", phase="train",
             cost_model="wire_bytes"),
    WireKind(kind="tree_cut", direction="up", phase="train",
             cost_model="tree_cut_bytes"),
    WireKind(kind="head_out", direction="down", phase="train",
             cost_model="head_exchange_bytes"),
    WireKind(kind="aux", direction="down", phase="train",
             cost_model="aux_exchange_bytes"),
    WireKind(kind="head_jac", direction="up", phase="train",
             cost_model="head_exchange_bytes"),
    WireKind(kind="jac", direction="down", phase="train",
             cost_model="cut_bytes"),
    WireKind(kind="compressed_jac", direction="down", phase="train",
             cost_model="wire_bytes"),
    WireKind(kind="tree_jac", direction="down", phase="train",
             cost_model="tree_cut_bytes"),
    WireKind(kind="keyx_pub", direction="up", phase="keyx",
             cost_model="key_exchange_bytes"),
    WireKind(kind="keyx_bcast", direction="down", phase="keyx",
             cost_model="key_exchange_bytes"),
    WireKind(kind="serve_prompt", direction="down", phase="serve",
             cost_model="serve_prefill_bytes"),
    WireKind(kind="serve_prefill_cut", direction="up", phase="serve",
             cost_model="serve_prefill_bytes"),
    WireKind(kind="serve_token", direction="down", phase="serve",
             cost_model="serve_decode_bytes"),
    WireKind(kind="serve_cut", direction="up", phase="serve",
             cost_model="serve_decode_bytes"),
)}


@dataclass(frozen=True)
class MessageSpec:
    """One protocol message, independent of any payload: who sends what to
    whom.  ``client`` is the feature-holder index for cut/jac/key-exchange
    messages and None for the role-0 <-> role-3 loss exchange.  ``kind``
    must be registered in :data:`WIRE_KINDS` — the runtime consumes the
    registry, so an unregistered kind cannot even be scheduled."""

    sender: str
    receiver: str
    tag: str
    kind: str
    client: Optional[int] = None

    def __post_init__(self):
        if self.kind not in WIRE_KINDS:
            raise ValueError(
                f"unregistered wire kind {self.kind!r} (tag {self.tag!r}) "
                f"— register it in protocol.WIRE_KINDS with a direction, "
                f"phase, and costs.* byte model")


@dataclass(frozen=True)
class StepSchedule:
    """THE message schedule, in five message classes: the one-time pairwise
    key exchange, K (optionally masked) cut uplinks, the role-0 <-> role-3
    head/loss exchange (with its auxiliary-loss slot), and K jacobian
    downlinks.  Serial execution walks the per-step classes in order; the
    pipelined runtime issues the same messages per microbatch, overlapped.

    ``aux`` is the role-0 -> role-3 auxiliary-loss slot: families whose
    server network computes a loss term of its own (the moe router
    load-balance loss) ship that scalar alongside the head output so role 3
    folds it into the training loss.  The slot is always part of the
    schedule definition; a message is only recorded (and costed) when the
    family's SplitProgram declares an aux term.

    ``key_pubs`` / ``key_bcasts`` are the one-time key-agreement round of
    secure aggregation (``repro.core.secure_agg``): each client uplinks its
    fixed-size public value, role 0 relays the full directory back down and
    every ordered pair derives a shared mask seed role 0 never holds.  Like
    the aux slot the specs are always part of the definition; they are only
    recorded (and costed) when the schedule is built with ``secure=True``,
    in which case the cut uplinks carry the ``masked_cut`` kind — role 0
    observes mask-blinded activations and only their sum is meaningful.

    A schedule built with ``compress`` set ("topk" | "int8",
    ``repro.core.compression``) tags the cut uplinks ``compressed_cut`` and
    the jacobian downlinks ``compressed_jac``: both directions ship lossy
    payloads whose bytes are the codec's wire frame
    (``costs.wire_bytes``), not the dense f32 tensor — the Ledger audits
    those codec bytes and the StepPlan simulators clock them.  ``secure``
    and ``compress`` are mutually exclusive: additive masks do not cancel
    through quantized/sparsified values, so composing them would silently
    break the only-the-sum-is-meaningful privacy claim.

    A schedule built with a ``tree`` (:class:`~repro.runtime.topology.
    AggTree`) re-routes the per-client messages along the aggregation
    tree: client k's cut uplink goes to its RELAY PARENT (or role 0 for
    top-level clients) under the ``tree_cut[level]`` tag, and its jacobian
    arrives FROM that parent under ``tree_jac[level]`` — so
    ``Ledger.received_by("role0")`` counts only the ``min(F, K)`` top-level
    frames per microbatch, which is the O(K) -> O(F) headline, while the
    per-level tags keep the full per-edge byte audit exact
    (``costs.tree_cut_bytes``).  Tree routing composes with ``secure``
    (partial sums of masked cuts still cancel at the root) and is mutually
    exclusive with ``compress`` (codec frames cannot be partial-summed)."""

    cuts: tuple[MessageSpec, ...]
    head_out: MessageSpec
    aux: MessageSpec
    head_jac: MessageSpec
    jacs: tuple[MessageSpec, ...]
    key_pubs: tuple[MessageSpec, ...] = ()
    key_bcasts: tuple[MessageSpec, ...] = ()
    secure: bool = False
    compress: Optional[str] = None
    # duck-typed AggTree (parent/edge_level/top_level/subtree) — kept
    # loose so core does not import runtime.topology
    tree: Optional[object] = None


def step_schedule(num_clients: int, *, secure: bool = False,
                  compress: Optional[str] = None,
                  tree=None) -> StepSchedule:
    compat.check("schedule", secure=secure, compress=compress, tree=tree)
    cut_kind = ("masked_cut" if secure
                else "compressed_cut" if compress is not None else "cut")
    jac_kind = "compressed_jac" if compress is not None else "jac"
    if tree is not None:
        if getattr(tree, "num_clients", None) != num_clients:
            raise ValueError(
                f"tree covers {getattr(tree, 'num_clients', None)} clients, "
                f"schedule has {num_clients}")
        # per-edge routing: client k uplinks to its relay parent (role 0
        # for top level) under the per-LEVEL tree tag; the jacobian
        # arrives back down the same edge.
        def _hop(k):
            p = tree.parent(k)
            return ("role0" if p is None else _role_of(p),
                    tree.edge_level(k))

        cuts = tuple(
            MessageSpec(_role_of(k), _hop(k)[0],
                        f"tree_cut[{_hop(k)[1]}]", "tree_cut", k)
            for k in range(num_clients)
        )
        jacs = tuple(
            MessageSpec(_hop(k)[0], _role_of(k),
                        f"tree_jac[{_hop(k)[1]}]", "tree_jac", k)
            for k in range(num_clients)
        )
    else:
        cuts = tuple(
            MessageSpec(_role_of(k), "role0",
                        f"{cut_kind}[{k}]", cut_kind, k)
            for k in range(num_clients)
        )
        jacs = tuple(
            MessageSpec("role0", _role_of(k),
                        f"{jac_kind}[{k}]", jac_kind, k)
            for k in range(num_clients)
        )
    key_pubs = tuple(
        MessageSpec(_role_of(k), "role0", f"keyx_pub[{k}]",
                    "keyx_pub", k)
        for k in range(num_clients)
    )
    key_bcasts = tuple(
        MessageSpec("role0", _role_of(k), f"keyx_bcast[{k}]",
                    "keyx_bcast", k)
        for k in range(num_clients)
    )
    return StepSchedule(
        cuts=cuts,
        head_out=MessageSpec("role0", "role3", "head_output", "head_out"),
        aux=MessageSpec("role0", "role3", "aux_loss", "aux"),
        head_jac=MessageSpec("role3", "role0", "head_jacobian", "head_jac"),
        jacs=jacs,
        key_pubs=key_pubs,
        key_bcasts=key_bcasts,
        secure=secure,
        tree=tree,
    )


@dataclass(frozen=True)
class ServeSchedule:
    """THE serving message schedule — the inference-time sibling of
    :class:`StepSchedule`, in four per-client message classes:

    * ``prompts``       — role 0 -> client k: the request's int32 prompt
      ids (tag ``serve_prompt[k]``).  The token stream is the shared
      context of the vertical token-LM split, exactly as in training; a
      client's PRIVATE dimension is its embedding-column slice, which
      never leaves it.
    * ``prefill_cuts``  — client k -> role 0: the one-time full-prompt cut
      slice (tag ``serve_prefill_cut[k]``), merged at role 0 into the
      per-session cut activation that is cached, evicted, and
      admission-controlled by the serving driver.
    * ``tokens``        — role 0 -> client k: the last sampled token id,
      one int32 per decode round (tag ``serve_token[k]``).
    * ``cuts``          — client k -> role 0: the one-token decode cut
      frame (tag ``serve_cut[k]``).

    Unlike training there is no jacobian leg — serving is forward-only —
    and no masked/compressed/tree variants: serving frames are raw cut
    tensors (the driver rejects secure/compressed/tree configs at
    construction).  Every message is Ledger-recorded by the serving driver
    and reconciled against ``costs.serve_prefill_bytes`` /
    ``costs.serve_decode_bytes`` in tests, the same way training traffic
    audits against its byte models."""

    prompts: tuple[MessageSpec, ...]
    prefill_cuts: tuple[MessageSpec, ...]
    tokens: tuple[MessageSpec, ...]
    cuts: tuple[MessageSpec, ...]


def serve_schedule(num_clients: int, *, secure: bool = False,
                   compress: Optional[str] = None,
                   tree=None) -> ServeSchedule:
    """The serving schedule for ``num_clients`` feature holders.  Serving
    has no label traffic, but the role naming stays consistent with
    :func:`step_schedule` so one ledger can audit a process that both
    trains and serves.

    Serving frames are raw cut tensors — the compat matrix (serve-secure /
    serve-compress / serve-tree) rejects the training-path overlays right
    here at schedule construction, so a driver cannot even build a serving
    schedule over a masked, compressed, or tree-routed wire."""
    compat.check("schedule", serve=True, secure=secure, compress=compress,
                 tree=tree)
    return ServeSchedule(
        prompts=tuple(
            MessageSpec("role0", _role_of(k),
                        f"serve_prompt[{k}]", "serve_prompt", k)
            for k in range(num_clients)
        ),
        prefill_cuts=tuple(
            MessageSpec(_role_of(k), "role0",
                        f"serve_prefill_cut[{k}]", "serve_prefill_cut", k)
            for k in range(num_clients)
        ),
        tokens=tuple(
            MessageSpec("role0", _role_of(k),
                        f"serve_token[{k}]", "serve_token", k)
            for k in range(num_clients)
        ),
        cuts=tuple(
            MessageSpec(_role_of(k), "role0",
                        f"serve_cut[{k}]", "serve_cut", k)
            for k in range(num_clients)
        ),
    )


def protocol_step(
    tower_fwd,  # (tower_params_k, x_k) -> cut; or a per-client list of K
    server_fwd: Callable,  # (server_params, merged[, batch]) -> logits[, aux]
    loss_fn: Callable,  # (logits, labels) -> scalar
    tower_params: list,
    server_params,
    features: list[jnp.ndarray],  # per-client feature slices
    labels,  # role-3 context: an array or a pytree, batch-major
    merge: str,
    *,
    live_mask: Optional[jnp.ndarray] = None,
    ledger: Optional[Ledger] = None,
    server_takes_batch: bool = False,
    server_aux: bool = False,
    merge_fn: Optional[Callable] = None,
    compress: Optional[str] = None,
    topk_fraction: float = 0.25,
):
    """One paper-protocol training step; returns (loss, tower_grads, server_grads).

    The message schedule follows paper §4.4: feature-holders send cut
    activations to role 0; role 0 sends the head output (plus, for
    families with a server-side auxiliary loss, the ``aux_loss`` scalar —
    ``server_aux``) to role 3; role 3 returns the head jacobian; role 0
    returns per-client cut jacobians.  ``tower_fwd`` may be a list of
    per-client callables (modality splits) and ``merge_fn`` replaces the
    uniform stacked merge for programs with non-uniform cuts (the vlm
    sequence concatenation) — see repro.models.split_program.

    ``live_mask`` (K,) sends the clients it zeroes to their merge's
    neutral element; every client still gets a jacobian.  The uniform
    merge always runs ``merge_stacked`` (under all ones when no mask is
    given); a program ``merge_fn`` receives ``live_mask`` as it is.

    Thin wrapper: the numerics live in
    :class:`repro.runtime.executor.Executor` (serial mode, one microbatch)
    driven over the inline :class:`~repro.transport.SimTransport` — the same
    execution path that runs the pipelined schedule and the real
    inproc/multiproc transports.
    """
    # function-level imports: runtime/transport import this module for the
    # schedule and Ledger definitions
    from repro.runtime.executor import Executor
    from repro.transport.base import SimTransport, TowerWorker

    K = len(tower_params)
    tower_fwds = (list(tower_fwd) if isinstance(tower_fwd, (list, tuple))
                  else [tower_fwd] * K)
    workers = [TowerWorker(k, tower_fwds[k], tower_params[k],
                           compress=compress, topk_fraction=topk_fraction)
               for k in range(K)]
    executor = Executor(
        SimTransport(workers), server_fwd, loss_fn, merge,
        mode="serial", microbatches=1, server_takes_batch=server_takes_batch,
        server_aux=server_aux, merge_fn=merge_fn,
        compress=compress, topk_fraction=topk_fraction,
    )
    if live_mask is None and merge_fn is None:
        live_mask = jnp.ones((K,), jnp.float32)  # selects merge_stacked
    res = executor.run_step(
        server_params, labels, features=list(features),
        merge_mask=live_mask, ledger=ledger, collect_grads=True,
    )
    return res.loss, res.tower_grads, res.server_grads, res.ledger


def assert_equivalent_to_monolithic(
    tower_fwd, server_fwd, loss_fn, tower_params, server_params,
    features, labels, merge: str, atol: float = 1e-5,
):
    """The paper's §3 identity: the protocol == end-to-end backprop."""
    loss_p, tg_p, sg_p, _ = protocol_step(
        tower_fwd, server_fwd, loss_fn, tower_params, server_params,
        features, labels, merge,
    )

    def monolithic(all_params):
        towers, server = all_params
        stacked = jnp.stack([tower_fwd(towers[k], features[k]) for k in range(len(towers))])
        merged = merge_lib.merge_stacked(stacked, merge)
        return loss_fn(server_fwd(server, merged), labels)

    loss_m, (tg_m, sg_m) = jax.value_and_grad(monolithic)((tower_params, server_params))

    import numpy as np

    np.testing.assert_allclose(loss_p, loss_m, atol=atol, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves((tg_p, sg_p)),
                    jax.tree_util.tree_leaves((tg_m, sg_m))):
        np.testing.assert_allclose(a, b, atol=atol, rtol=1e-4)

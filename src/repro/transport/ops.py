"""The worker op table — ONE declarative registry of the wire verbs.

:class:`~repro.transport.base.TowerWorker.handle` dispatches requests from
this table instead of an inline ``if op ==`` chain, so the set of verbs a
worker serves, the handler each maps to, and the response ops each may
emit live in one place the runtime consumes and ``repro.analysis``
statically audits:

* every ``{"op": ...}`` literal a driver submits anywhere in ``src/`` must
  name a registered worker op (rule O001);
* every registered op's handler must exist on ``TowerWorker`` and every
  registered op must be submitted by some driver (rules O002/O003 — no
  phantom verbs in either direction);
* every response op a worker emits must be registered in
  :data:`RESPONSE_OPS` and consumed somewhere (same rules, downlink
  direction);
* the op-contract docstring in ``repro.transport.__init__`` and the
  ROADMAP transport-contract section must document every op (rule D001).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class OpSpec:
    """One worker-served wire verb.

    ``handler`` is the ``TowerWorker`` method ``handle`` dispatches to
    (uniform ``(self, request) -> Optional[dict]`` signature).
    ``responses`` are the response ops the handler may emit; empty means
    fire-and-forget (the driver must not barrier on a reply).
    """

    op: str
    handler: str
    responses: tuple[str, ...]
    doc: str


WORKER_OPS: dict[str, OpSpec] = {spec.op: spec for spec in (
    OpSpec("forward", "_forward", ("cut", "tree_cut"),
           "run one microbatch's tower forward; uplink the (possibly "
           "masked/compressed/relay-accumulated) cut frame"),
    OpSpec("backward", "_backward", ("grad",),
           "apply the cut jacobian through the tower backward; ack"),
    OpSpec("finish_step", "_finish_step", ("step_done",),
           "average the step's tower grads over M, apply the local "
           "optimizer update when configured, return grads iff collect"),
    OpSpec("key_exchange", "_key_exchange", ("pub", "keys_ready"),
           "secure aggregation's one-time DH round: phase 'pub' emits the "
           "public value, phase 'finish' derives pairwise mask seeds"),
    OpSpec("configure_relay", "_configure_relay", ("relay_ready",),
           "one-time: become an aggregation-tree relay for the given "
           "child ids"),
    OpSpec("aggregate", "_aggregate", ("tree_cut",),
           "fold a child's subtree frame into the relay's partial sum; "
           "the combined tree_cut is emitted once all parts landed"),
    OpSpec("serve_prefill", "_serve_prefill", ("serve_prefill_cut",),
           "run the tower's feature slice over the whole prompt once and "
           "open (or reset) the request's tower KV session"),
    OpSpec("serve_decode", "_serve_decode", ("serve_cut",),
           "one autoregressive step against the request's KV session"),
    OpSpec("serve_end", "_serve_end", (),
           "drop the request's tower KV session (fire-and-forget)"),
    OpSpec("get_params", "_get_params", ("params",),
           "return this client's tower params (verification/collection)"),
    OpSpec("shutdown", "_shutdown", ("bye",),
           "close down; the transport retires the worker on the ack"),
)}

#: response op -> doc.  The downlink half of the contract: every response
#: dict a worker (or transport shim) constructs carries one of these.
RESPONSE_OPS: dict[str, str] = {
    "cut": "one microbatch's cut frame {step, mb, cut}",
    "tree_cut": "a relay's combined subtree frame {step, mb, cut}",
    "grad": "backward ack {mb}",
    "step_done": "step finished {step[, grad]}",
    "pub": "DH public value {pub}",
    "keys_ready": "pairwise mask seeds derived {}",
    "relay_ready": "relay configured {}",
    "serve_prefill_cut": "full-prompt serving cut slice {request, cut}",
    "serve_cut": "one-token decode cut frame {request, pos, cut}",
    "params": "tower params {params}",
    "bye": "shutdown ack {}",
    # transport-level, not worker-emitted: threaded/process backends wrap
    # a worker crash and re-raise it on the driver thread
    "error": "worker exception surfaced by the transport {error}",
    # transport-level: a multiproc child's first frame after connecting,
    # mapping its socket to a client id (never reaches TowerWorker.handle)
    "hello": "multiproc connection handshake {client, platform}",
}

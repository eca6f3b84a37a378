"""Role-0 serving driver: prefill/decode rounds over any transport.

The serving sibling of :class:`~repro.runtime.executor.Executor` — the same
shared response pump pattern (drain ``transport.next_response`` and route
each frame into its in-flight buffer), with the trainer's ``(step,
microbatch)`` key generalized to ``(request, position)``: a prefill round
buffers per-request cut slices until all K clients reported, a decode round
buffers per-``(request, position)`` one-token frames.  Because the pump is
shared, frames from different requests at different positions interleave
freely on the wire — the transport-level property continuous batching
rides on.

Every message is Ledger-recorded against the
:class:`~repro.core.protocol.ServeSchedule` specs, so serving traffic
reconciles against ``costs.serve_prefill_bytes`` /
``costs.serve_decode_bytes`` exactly the way training traffic audits
against its byte models (asserted in tests/test_split_serve.py).
"""
from __future__ import annotations

import time
from typing import Optional

import jax.numpy as jnp

from repro.core.protocol import Ledger, ServeSchedule, serve_schedule
from repro.runtime.executor import fast_merge


class ServeDriver:
    """Transport-facing serving half of role 0: ships prompts/tokens down,
    collects cut frames up, merges, and audits bytes.  Model state (slot
    caches, sampling, the cut cache) lives in
    :class:`~repro.serve.split_serve.SplitLMServer`, which drives this."""

    def __init__(self, transport, *, merge: str,
                 ledger: Optional[Ledger] = None, timeout_s: float = 120.0,
                 secure: bool = False, compress: Optional[str] = None,
                 tree=None):
        self.transport = transport
        self.num_clients = transport.num_clients
        self.merge = merge
        # training-path overlays (secure/compressed/tree wires) are passed
        # through to serve_schedule, whose compat gate rejects them — the
        # schedule layer is where a masked serving wire becomes unbuildable
        self.schedule: ServeSchedule = serve_schedule(
            self.num_clients, secure=secure, compress=compress, tree=tree)
        self.ledger = ledger if ledger is not None else Ledger()
        self.timeout_s = timeout_s
        # in-flight response buffers, filled by the shared pump
        self._prefill_buf: dict = {}  # request -> {client: cut (1, S, D)}
        self._decode_buf: dict = {}  # (request, pos) -> {client: cut}

    # -- the shared response pump -------------------------------------------

    def _pump(self, timeout: Optional[float]) -> bool:
        """Route one transport response into its in-flight buffer; returns
        False on timeout.  The serving generalization of the trainer's
        pump: ``serve_prefill_cut`` frames key by ``request``,
        ``serve_cut`` frames by ``(request, position)``."""
        got = self.transport.next_response(timeout)
        if got is None:
            return False
        client, resp = got
        op = resp.get("op")
        if op == "serve_prefill_cut":
            buf = self._prefill_buf.setdefault(resp["request"], {})
        elif op == "serve_cut":
            buf = self._decode_buf.setdefault(
                (resp["request"], int(resp["pos"])), {})
        else:
            raise RuntimeError(
                f"serve driver: unexpected response op {op!r} from client "
                f"{client} — training and serving frames must not share a "
                "driver instance")
        if client in buf:
            raise RuntimeError(
                f"serve driver: duplicate cut frame from client {client} "
                f"for {resp.get('request')!r}")
        buf[client] = jnp.asarray(resp["cut"])
        return True

    def _drain_until(self, done) -> None:
        deadline = time.monotonic() + self.timeout_s
        while not done():
            remaining = deadline - time.monotonic()
            if remaining <= 0.0:
                raise TimeoutError(
                    f"serve driver: clients did not answer within "
                    f"{self.timeout_s:.0f}s")
            if not self._pump(min(remaining, 0.25)):
                # SimTransport ignores the timeout and returns instantly
                # when idle — don't hot-spin while waiting out the deadline
                time.sleep(0.01)

    # -- rounds --------------------------------------------------------------

    def prefill(self, rid, prompt, cache_len: int) -> jnp.ndarray:
        """One request's prefill round: ship the int32 prompt to every
        feature holder, collect all K full-prompt cut slices, merge.
        Returns the merged cut activation (1, S, d) — the per-session
        state the caller caches/evicts/readmits."""
        prompt = jnp.asarray(prompt, jnp.int32).reshape(-1)
        S = int(prompt.shape[0])
        for k in range(self.num_clients):
            self.transport.submit(k, {
                "op": "serve_prefill", "request": rid, "tokens": prompt,
                "cache_len": int(cache_len),
            })
            self.ledger.record_spec_bytes(self.schedule.prompts[k], S * 4)
        self._drain_until(
            lambda: len(self._prefill_buf.get(rid, ())) == self.num_clients)
        cuts = self._prefill_buf.pop(rid)
        for k in range(self.num_clients):
            self.ledger.record_spec(self.schedule.prefill_cuts[k], cuts[k])
        return fast_merge(
            jnp.stack([cuts[k] for k in range(self.num_clients)]), self.merge)

    def decode_round(self, entries: list) -> dict:
        """One decode round for a batch of in-flight requests.

        ``entries`` is ``[(rid, token, pos), ...]`` — the last sampled
        token and absolute position per ACTIVE request (retired slots cost
        no wire traffic, which is continuous batching's byte win).  All
        K * len(entries) token frames are submitted before any cut frame
        is collected, so tower decodes for different requests overlap on
        concurrent transports.  Returns ``{rid: merged (1, 1, d)}``."""
        for rid, token, pos in entries:
            for k in range(self.num_clients):
                self.transport.submit(k, {
                    "op": "serve_decode", "request": rid,
                    "token": int(token), "pos": int(pos),
                })
                self.ledger.record_spec_bytes(self.schedule.tokens[k], 4)
        keys = [(rid, int(pos)) for rid, _, pos in entries]
        self._drain_until(lambda: all(
            len(self._decode_buf.get(key, ())) == self.num_clients
            for key in keys))
        merged = {}
        for rid, _, pos in entries:
            cuts = self._decode_buf.pop((rid, int(pos)))
            for k in range(self.num_clients):
                self.ledger.record_spec(self.schedule.cuts[k], cuts[k])
            merged[rid] = fast_merge(
                jnp.stack([cuts[k] for k in range(self.num_clients)]),
                self.merge)
        return merged

    def end_session(self, rid) -> None:
        """Retire a request at every feature holder (fire-and-forget)."""
        for k in range(self.num_clients):
            self.transport.submit(k, {"op": "serve_end", "request": rid})

"""Multi-process transport: one OS process per feature-holder, TCP loopback.

The role-0 server (the parent) listens on 127.0.0.1; each spawned child
builds its worker from a picklable :class:`WorkerSpec` — so the child holds
ONLY its own tower params and feature source, constructed locally — then
connects and serves requests.  Messages are length-prefixed pickle frames;
array payloads are converted to numpy at the boundary so no jax device
buffers cross processes.

The ``spawn`` start method is used unconditionally: forking a process that
already initialized jax is unsafe, and spawn is what a real multi-host
launcher looks like anyway.
"""
from __future__ import annotations

import pickle
import queue
import socket
import struct
import threading
from dataclasses import dataclass, field
from typing import Callable, Optional

import multiprocessing as mp

import jax
import numpy as np

from repro.transport.base import Transport

_LEN = struct.Struct(">Q")


def send_msg(sock: socket.socket, payload: dict) -> None:
    data = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed the connection")
        buf.extend(chunk)
    return bytes(buf)


def recv_msg(sock: socket.socket) -> dict:
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    return pickle.loads(_recv_exact(sock, n))


def _to_numpy(tree):
    """Convert jax arrays to numpy at the wire boundary; python scalars,
    strings and numpy arrays pass through untouched (dict keys like
    ``step``/``mb`` must stay hashable ints on the far side)."""
    def conv(leaf):
        return np.asarray(leaf) if isinstance(leaf, jax.Array) else leaf

    return jax.tree_util.tree_map(conv, tree)


@dataclass(frozen=True)
class WorkerSpec:
    """Picklable recipe: ``build(client_id, **kwargs) -> TowerWorker``.

    ``build`` must be a module-level callable importable in the child —
    the whole point is that the child constructs its own params/data from
    small config, not that the parent ships tensors over."""

    build: Callable
    kwargs: dict = field(default_factory=dict)


def _client_main(spec: WorkerSpec, client_id: int, port: int) -> None:
    # children compute towers on the host CPU, whatever the environment
    # says: an accelerator belongs to one process, and role 0 (the parent)
    # already holds it.  The hello names the platform, so role 0 reports
    # where the towers really ran.  Importing this module has
    # already read JAX_PLATFORMS, so the pin goes through jax.config; no
    # backend is up before the worker is built.
    jax.config.update("jax_platforms", "cpu")
    worker = spec.build(client_id, **spec.kwargs)
    sock = socket.create_connection(("127.0.0.1", port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        send_msg(sock, {"op": "hello", "client": client_id,
                        "platform": jax.default_backend()})
        while True:
            request = recv_msg(sock)
            try:
                resp = worker.handle(request)
            except Exception as e:
                send_msg(sock, {"op": "error", "client": client_id,
                                "error": repr(e)})
                continue
            if resp is not None:
                send_msg(sock, _to_numpy(resp))
                if resp["op"] == "bye":
                    return
    finally:
        sock.close()


class MultiprocTransport(Transport):
    def __init__(self, worker_specs: list[WorkerSpec], *,
                 connect_timeout_s: float = 120.0):
        self.num_clients = len(worker_specs)
        self._closed = False
        self._procs = []
        self._conns: list[Optional[socket.socket]] = [None] * self.num_clients
        self._responses: queue.SimpleQueue = queue.SimpleQueue()
        self._send_locks = [threading.Lock() for _ in range(self.num_clients)]
        self._readers: list[threading.Thread] = []
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(self.num_clients)
        port = self._listener.getsockname()[1]

        ctx = mp.get_context("spawn")
        self._procs = [
            ctx.Process(target=_client_main, args=(spec, k, port), daemon=True)
            for k, spec in enumerate(worker_specs)
        ]
        for p in self._procs:
            p.start()

        # accept all K hellos (children import jax, so be patient)
        self._listener.settimeout(connect_timeout_s)
        platforms = set()
        try:
            for _ in range(self.num_clients):
                conn, _ = self._listener.accept()
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                hello = recv_msg(conn)
                assert hello["op"] == "hello"
                self._conns[hello["client"]] = conn
                platforms.add(hello["platform"])
        except socket.timeout:
            self.close()
            raise TimeoutError(
                f"not all {self.num_clients} clients connected within "
                f"{connect_timeout_s}s")
        self._tower_platform = ",".join(sorted(platforms))

        self._readers = [
            threading.Thread(target=self._read_loop, args=(k,), daemon=True,
                             name=f"splitnn-reader{k}")
            for k in range(self.num_clients)
        ]
        for t in self._readers:
            t.start()

    @property
    def tower_platform(self) -> str:
        return self._tower_platform

    def _read_loop(self, client: int) -> None:
        conn = self._conns[client]
        try:
            while True:
                resp = recv_msg(conn)
                self._responses.put((client, resp))
                if resp["op"] == "bye":
                    return
        except (ConnectionError, OSError):
            return  # closed during shutdown

    def submit(self, client: int, request: dict) -> None:
        with self._send_locks[client]:
            send_msg(self._conns[client], _to_numpy(request))

    def next_response(self, timeout: Optional[float] = None):
        try:
            client, resp = self._responses.get(timeout=timeout)
        except queue.Empty:
            return None
        if resp.get("op") == "error":
            raise RuntimeError(
                f"client {client} worker failed: {resp['error']}")
        return client, resp

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for k, conn in enumerate(self._conns):
            if conn is None:
                continue
            try:
                with self._send_locks[k]:
                    send_msg(conn, {"op": "shutdown"})
            except OSError:
                pass
        for p in self._procs:
            p.join(timeout=10.0)
        # a child that missed the shutdown message (hung forward, wedged
        # socket) must not outlive the transport: escalate terminate ->
        # kill, JOINING after each signal — a bare terminate() with no
        # follow-up join leaks a zombie and wedges CI on interpreter exit
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
        for p in self._procs:
            if p.is_alive():
                p.kill()
                p.join(timeout=5.0)
        for conn in self._conns:
            if conn is not None:
                conn.close()
        self._listener.close()

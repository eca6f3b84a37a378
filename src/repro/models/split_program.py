"""Per-family SplitProgram: the execution-side contract of the vertical split.

A :class:`SplitProgram` bundles everything the protocol stack needs to train
one config family genuinely split across role-1/3 feature holders and the
role-0 server:

* ``tower_fwd(k)`` — client ``k``'s pure tower callable ``(tower_params,
  feats) -> cut`` (per-client for modality splits, shared for token LMs);
* ``server_fwd`` — the role-0 forward ``(server_params, merged[, batch]) ->
  logits`` or ``(logits, aux)`` when the family carries an auxiliary loss
  (``has_aux``: the moe router load-balance term, shipped role 0 -> role 3
  through the protocol's aux slot);
* ``loss_fn`` — the role-3 loss ``(logits, batch_ctx) -> scalar``;
* ``partition(params)`` — the per-role parameter split of a monolithic
  ``backbone.init_params`` tree;
* ``features`` / ``feature_fn`` — the per-client feature source, driver-side
  (one batch) and worker-side (regenerated from the shared seed so only
  protocol messages ever cross a transport).

The :class:`~repro.runtime.executor.Executor`, ``protocol_step`` and the
transports stay family-agnostic: they consume the program, never the family.
Registered families: dense, ssm, hybrid, moe, audio, vlm — any config in
``repro.configs`` with a vertical section trains over any transport.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models import transformer as tfm
from repro.models.transformer import BlockDims


class TowerServeFns:
    """Client-side serving bundle: per-request tower prefill/decode.

    ``prefill(tower_params, tokens (1, S), cache_len) -> (cut (1, S, D),
    session)`` runs the tower teacher-forced over the prompt and returns
    the full-prompt cut slice plus the request's tower KV session state;
    ``decode(tower_params, session, token (1,)) -> (cut (1, 1, D),
    session)`` advances the session one token.  Sessions are opaque
    pytrees owned by the :class:`~repro.transport.base.TowerWorker` — one
    per in-flight request — so a client serves many interleaved requests
    at heterogeneous positions."""

    def __init__(self, prefill: Callable, decode: Callable):
        self.prefill = prefill
        self.decode = decode


class ServerServeFns:
    """Role-0 serving bundle: per-slot server prefill/decode from MERGED
    cuts (the server never sees tokens beyond the ids it relays).

    ``init_cache(cache_len)`` builds one empty B=1 decode-slot cache;
    ``prefill(server_params, cache, merged (1, S, d)) -> (logits (1, V),
    cache)`` fills it from a session's merged prefill cut;
    ``decode(server_params, cache, merged (1, 1, d)) -> (logits (1, V),
    cache)`` advances one token.  ``decode`` is written per-slot so the
    serving driver can ``jax.vmap`` it over a stacked slot axis — each
    slot carries its own ``index``, which is how one fixed-shape compiled
    step decodes a continuous batch of requests at heterogeneous
    positions."""

    def __init__(self, init_cache: Callable, prefill: Callable,
                 decode: Callable):
        self.init_cache = init_cache
        self.prefill = prefill
        self.decode = decode


class SplitProgram:
    """Family-agnostic contract; subclasses register one family each.

    Class-level defaults describe the *shape* of the program so the
    Executor can be configured statically (``executor_kwargs``):

    * ``server_takes_batch`` — ``server_fwd`` needs the role-0-side batch
      context (e.g. the audio decoder's teacher-forcing tokens);
    * ``has_aux`` — ``server_fwd`` returns ``(logits, aux)`` and the aux
      scalar crosses the role-0 -> role-3 exchange (ledger tag
      ``aux_loss``);
    * ``per_client_towers`` — ``tower_fwd(k)`` differs by client (modality
      splits), so callers must not assume one shared callable;
    * ``merge_fn`` — ``None`` for uniform feature-merges (the cut stack is
      (K, B, ..., D) and ``cfg.vertical.merge`` applies); a callable
      ``(cuts_list, live_mask) -> merged`` for non-uniform programs (the
      vlm sequence concatenation).
    """

    server_takes_batch = False
    has_aux = False
    per_client_towers = False
    merge_fn: Optional[Callable] = None

    def __init__(self, cfg: ArchConfig):
        if cfg.vertical is None:
            raise ValueError(f"{cfg.name}: split execution needs a vertical "
                             "config")
        self.cfg = cfg
        self.merge = cfg.vertical.merge

    # -- structure -----------------------------------------------------------

    @property
    def num_clients(self) -> int:
        return self.cfg.vertical.num_clients

    @property
    def tower_fwds(self) -> list:
        return [self.tower_fwd(k) for k in range(self.num_clients)]

    @property
    def executor_kwargs(self) -> dict:
        """Keyword arguments configuring an Executor for this program."""
        return dict(server_takes_batch=self.server_takes_batch,
                    server_aux=self.has_aux, merge_fn=self.merge_fn)

    # -- contract ------------------------------------------------------------

    def partition(self, params) -> tuple[list, dict]:
        """Monolithic param tree -> (per-client tower trees, server tree)."""
        raise NotImplementedError

    def tower_fwd(self, client: int) -> Callable:
        """Client ``client``'s pure ``(tower_params, feats) -> cut``."""
        raise NotImplementedError

    def features(self, batch: dict) -> list:
        """Driver-side per-client feature arrays for one loader batch (the
        serial ``protocol_step`` reference path)."""
        raise NotImplementedError

    def batch_ctx(self, batch: dict):
        """Role-0/3-side per-step context passed to ``Executor.run_step``
        (an array or pytree, microbatch-sliced along the leading axis)."""
        return jnp.asarray(batch["labels"])

    def feature_fn(self, client: int, *, batch: int, seq: int, seed: int = 0,
                   microbatches: int = 1) -> Callable:
        """Worker-side ``(step, mb) -> feats``: regenerates this client's
        feature stream from the shared seed, so a spawned worker needs no
        tensors from the driver."""
        raise NotImplementedError

    def tower_serve_fns(self, client: int) -> TowerServeFns:
        """Client ``client``'s serving bundle (KV-cached prefill/decode).
        Families without a serving decomposition raise."""
        raise NotImplementedError(
            f"{self.cfg.name}: split serving is not implemented for the "
            f"{self.cfg.family!r} family — the dense token-LM program is "
            "the serving exemplar (stateful tower decode for ssm/hybrid "
            "towers is an open item)")

    def server_serve_fns(self) -> ServerServeFns:
        """Role-0 serving bundle (slot caches + prefill/decode from merged
        cuts).  Families without a serving decomposition raise."""
        raise NotImplementedError(
            f"{self.cfg.name}: split serving is not implemented for the "
            f"{self.cfg.family!r} family — the dense token-LM program is "
            "the serving exemplar (stateful tower decode for ssm/hybrid "
            "towers is an open item)")

    # -- convenience ---------------------------------------------------------

    def protocol_step(self, tower_params, server_params, features, ctx, *,
                      live_mask=None, ledger=None):
        """Serial reference step on this program's decomposition; returns
        (loss, tower_grads, server_grads, ledger) like ``protocol_step``.

        Honors ``cfg.vertical.compression``: the reference workers compress
        their cut uplinks (and the reference executor its jacobian
        downlinks) exactly like the transport path — with zero
        error-feedback residual, which is the step-0 state of any live run,
        so ``train_split`` verifies its compressed step-0 gradients against
        this."""
        from repro.core.protocol import protocol_step

        v = self.cfg.vertical
        return protocol_step(
            self.tower_fwds, self.server_fwd, self.loss_fn, tower_params,
            server_params, features, ctx, self.merge,
            live_mask=live_mask, ledger=ledger,
            compress=v.compression, topk_fraction=v.topk_fraction,
            **self.executor_kwargs)

    def _loader_feature_fn(self, *, batch: int, seq: int, seed: int,
                           microbatches: int, extract: Callable) -> Callable:
        """Iterate the shared-seed ``LMBatchLoader`` lazily; ``extract``
        picks this client's view of each batch dict."""
        from repro.data.loader import LMBatchLoader

        loader_it = iter(LMBatchLoader(self.cfg, batch, seq, seed=seed))
        state = {"step": -1, "batch": None}
        mbsz = batch // microbatches

        def feature_fn(step: int, mb: int):
            while state["step"] < step:  # steps arrive in order
                state["batch"] = next(loader_it)
                state["step"] += 1
            feats = jnp.asarray(extract(state["batch"]))
            return feats[mb * mbsz:(mb + 1) * mbsz]

        return feature_fn


# ---------------------------------------------------------------------------
# token-LM families: dense / ssm / hybrid / moe
# ---------------------------------------------------------------------------

class TokenLMSplitProgram(SplitProgram):
    """Feature-slice towers over a shared token stream.

    Every client holds the shared token ids; its PRIVATE dimension is its
    vertical slice of the embedding table (columns [k*d/K, (k+1)*d/K)), the
    true by-feature partition of the input layer.  The role-0 server keeps
    the trunk, the final norm, and the full table for the unembed head —
    input-embedding columns train at the clients, the head at the server.

    For moe the towers stay dense (experts live at role 0, paper §4.4) and
    ``server_fwd`` returns ``(logits, aux)``: the router load-balance loss
    rides the protocol's role-0 -> role-3 aux slot instead of being
    silently dropped.
    """

    def __init__(self, cfg: ArchConfig):
        super().__init__(cfg)
        self.has_aux = cfg.family == "moe"

    def partition(self, params):
        K = self.num_clients
        ds = self.cfg.d_model // K
        table = params["embed"]["table"]
        towers = []
        for k in range(K):
            tp = dict(jax.tree_util.tree_map(lambda a: a[k],
                                             params["towers"]))
            tp["embed_slice"] = table[:, k * ds:(k + 1) * ds]
            towers.append(tp)
        server = {key: val for key, val in params.items() if key != "towers"}
        return towers, server

    def tower_fwd(self, client: int) -> Callable:
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            dims_t = None
        else:
            from repro.models.backbone import _tower_dims

            dims_t = _tower_dims(cfg)

        def tower_fwd(tp, tokens):
            x = jnp.take(tp["embed_slice"], tokens, axis=0)  # (B, S, d/K)
            positions = jnp.arange(tokens.shape[-1], dtype=jnp.int32)
            h = x @ tp["proj_in"]
            if cfg.family in ("ssm", "hybrid"):
                h = tfm.mamba_stack_apply(tp["blocks"], h, cfg.ssm,
                                          tp["proj_in"].shape[1],
                                          cfg.norm_eps)
            else:
                h = tfm.dense_stack_apply(tp["blocks"], h, dims_t,
                                          causal=True, positions=positions)
            # cut compression happens at the transport boundary
            # (TowerWorker, with error feedback), not in the tower math —
            # the monolithic backbone path keeps its own in-graph STE
            return h @ tp["proj_out"]

        return tower_fwd

    def server_fwd(self, sp, merged):
        from repro.models.backbone import _server_trunk_apply

        cfg = self.cfg
        dims = BlockDims.from_arch(cfg)
        positions = jnp.arange(merged.shape[1], dtype=jnp.int32)
        x, aux = _server_trunk_apply(sp, merged, cfg, dims,
                                     positions=positions)
        x = tfm._norm(sp["final_norm"], x, dims.norm, dims.norm_eps)
        logits = layers.unembed(sp["embed"], x)
        if self.has_aux:
            return logits, aux
        return logits

    def loss_fn(self, logits, labels):
        from repro.models.backbone import lm_loss

        return lm_loss(logits, labels)

    def features(self, batch):
        tokens = jnp.asarray(batch["tokens"])
        return [tokens] * self.num_clients

    def feature_fn(self, client, *, batch, seq, seed=0, microbatches=1):
        return self._loader_feature_fn(
            batch=batch, seq=seq, seed=seed, microbatches=microbatches,
            extract=lambda b: b["tokens"])

    # -- serving -------------------------------------------------------------
    #
    # The split of backbone.prefill_tokens / backbone.decode_step along the
    # cut: the tower half (embedding-column slice -> proj_in -> tower blocks
    # -> proj_out, with the tower KV cache) runs at the client; the server
    # half (server stack -> final norm -> unembed, with the server KV cache)
    # runs at role 0 from the MERGED cut.  Both halves use the same
    # dense_stack_prefill / dense_stack_decode primitives and the same
    # position bookkeeping as the monolithic path, so greedy split decode is
    # token-identical to serve.decode.generate (asserted in
    # tests/test_split_serve.py).  Dense family only: ssm/hybrid towers
    # carry recurrent state whose serving session shape is an open item, and
    # moe serving would need the expert caches slot-aware.

    def _require_dense_serving(self):
        if self.cfg.family != "dense":
            raise NotImplementedError(
                f"{self.cfg.name}: split serving is implemented for the "
                f"dense token-LM family only (got {self.cfg.family!r}) — "
                "stateful ssm/hybrid tower sessions and slot-aware moe "
                "expert caches are open items")

    def tower_serve_fns(self, client: int) -> TowerServeFns:
        self._require_dense_serving()
        from repro.models.backbone import _tower_dims

        cfg = self.cfg
        dims_t = _tower_dims(cfg)

        def prefill(tp, tokens, cache_len):
            S = tokens.shape[1]
            x = jnp.take(tp["embed_slice"], tokens, axis=0)  # (1, S, d/K)
            positions = jnp.arange(S, dtype=jnp.int32)
            h = x @ tp["proj_in"]
            h, ks, vs = tfm.dense_stack_prefill(tp["blocks"], h, dims_t,
                                                positions=positions)
            cut = h @ tp["proj_out"]
            Lt, B, _, Kv, hd = ks.shape
            k = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((Lt, B, cache_len, Kv, hd), ks.dtype), ks, 0, axis=2)
            v = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((Lt, B, cache_len, Kv, hd), vs.dtype), vs, 0, axis=2)
            kv_positions = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((cache_len,), jnp.int32) - 1, positions, 0, axis=0)
            session = {"k": k, "v": v, "kv_positions": kv_positions,
                       "index": jnp.asarray(S, jnp.int32)}
            return cut, session

        def decode(tp, session, token):
            x = jnp.take(tp["embed_slice"], token[:, None], axis=0)  # (1,1,·)
            h = x @ tp["proj_in"]
            h, nk, nv, npos, _ = tfm.dense_stack_decode(
                tp["blocks"], h, session["k"], session["v"],
                session["index"], session["kv_positions"], dims_t,
                position=session["index"])
            cut = h @ tp["proj_out"]
            new = {"k": nk, "v": nv, "kv_positions": npos,
                   "index": session["index"] + 1}
            return cut, new

        return TowerServeFns(prefill=jax.jit(prefill, static_argnums=2),
                             decode=jax.jit(decode))

    def server_serve_fns(self) -> ServerServeFns:
        self._require_dense_serving()
        from repro.models.backbone import _server_layers

        cfg = self.cfg
        dims = BlockDims.from_arch(cfg)
        n_server = _server_layers(cfg)

        def init_cache(cache_len):
            kv = (n_server, 1, cache_len, dims.n_kv_heads, dims.head_dim)
            return {
                "k": jnp.zeros(kv, jnp.float32),
                "v": jnp.zeros(kv, jnp.float32),
                "kv_positions": jnp.zeros((cache_len,), jnp.int32) - 1,
                "index": jnp.zeros((), jnp.int32),
            }

        def prefill(sp, cache, merged):
            S = merged.shape[1]
            positions = jnp.arange(S, dtype=jnp.int32)
            x, ks, vs = tfm.dense_stack_prefill(sp["server"], merged, dims,
                                                positions=positions)
            new = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], ks.astype(cache["k"].dtype), 0, axis=2),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], vs.astype(cache["v"].dtype), 0, axis=2),
                "kv_positions": jax.lax.dynamic_update_slice_in_dim(
                    cache["kv_positions"], positions, 0, axis=0),
                "index": jnp.asarray(S, jnp.int32),
            }
            x = tfm._norm(sp["final_norm"], x, dims.norm, dims.norm_eps)
            logits = layers.unembed(sp["embed"], x[:, -1, :])
            return logits, new

        def decode(sp, cache, merged):
            x, nk, nv, npos, _ = tfm.dense_stack_decode(
                sp["server"], merged, cache["k"], cache["v"], cache["index"],
                cache["kv_positions"], dims, position=cache["index"])
            new = {"k": nk, "v": nv, "kv_positions": npos,
                   "index": cache["index"] + 1}
            x = tfm._norm(sp["final_norm"], x, dims.norm, dims.norm_eps)
            logits = layers.unembed(sp["embed"], x)[:, 0, :]
            return logits, new

        return ServerServeFns(init_cache=init_cache, prefill=prefill,
                              decode=decode)


# ---------------------------------------------------------------------------
# audio: mel-band feature-slice towers on the encoder
# ---------------------------------------------------------------------------

class AudioSplitProgram(SplitProgram):
    """Whisper-style encoder split: client ``k`` holds mel-band group ``k``
    (the feature slice ``frames[..., k*d/K:(k+1)*d/K]``) and runs its
    non-causal tower over it; the merged cut feeds the server's remaining
    encoder layers, and the decoder teacher-forces over the token stream
    held at role 0/3 (``server_takes_batch``)."""

    server_takes_batch = True
    per_client_towers = True

    def partition(self, params):
        K = self.num_clients
        towers = [dict(jax.tree_util.tree_map(lambda a: a[k],
                                              params["towers"]))
                  for k in range(K)]
        server = {key: val for key, val in params.items() if key != "towers"}
        return towers, server

    def tower_fwd(self, client: int) -> Callable:
        from repro.models.backbone import _tower_dims

        cfg = self.cfg
        dims_t = _tower_dims(cfg)
        ds = cfg.d_model // self.num_clients
        lo = client * ds

        def tower_fwd(tp, frame_slice):
            S = frame_slice.shape[1]
            # sinusoidal positions are public (no params): each client adds
            # its own d/K columns locally, matching encode_audio's
            # frames + enc_pos before the feature split
            pos = layers.sinusoidal_positions(S, cfg.d_model,
                                              frame_slice.dtype)
            h = frame_slice + pos[None, :, lo:lo + ds]
            positions = jnp.arange(S, dtype=jnp.int32)
            h = h @ tp["proj_in"]
            h = tfm.dense_stack_apply(tp["blocks"], h, dims_t, causal=False,
                                      positions=positions)
            # compression is the transport boundary's job (TowerWorker,
            # error feedback) — see TokenLMSplitProgram.tower_fwd
            return h @ tp["proj_out"]

        return tower_fwd

    def server_fwd(self, sp, merged, batch):
        from repro.models.backbone import (_audio_decoder_apply,
                                           _audio_encoder_tail)

        cfg = self.cfg
        dims = BlockDims.from_arch(cfg)
        enc_out = _audio_encoder_tail(sp, merged, cfg, dims)
        return _audio_decoder_apply(sp, batch["tokens"], enc_out, cfg, dims)

    def loss_fn(self, logits, batch):
        from repro.models.backbone import lm_loss

        return lm_loss(logits, batch["labels"])

    def batch_ctx(self, batch):
        return {"tokens": jnp.asarray(batch["tokens"]),
                "labels": jnp.asarray(batch["labels"])}

    def features(self, batch):
        frames = jnp.asarray(batch["frames"])
        ds = self.cfg.d_model // self.num_clients
        return [frames[..., k * ds:(k + 1) * ds]
                for k in range(self.num_clients)]

    def feature_fn(self, client, *, batch, seq, seed=0, microbatches=1):
        ds = self.cfg.d_model // self.num_clients
        lo = client * ds
        return self._loader_feature_fn(
            batch=batch, seq=seq, seed=seed, microbatches=microbatches,
            extract=lambda b: b["frames"][..., lo:lo + ds])


# ---------------------------------------------------------------------------
# vlm: by-source modality towers, sequence-concat merge
# ---------------------------------------------------------------------------

class VLMSplitProgram(SplitProgram):
    """The paper's most natural split, by source: client 0 holds the vision
    patches (tower = vision stack, non-causal), client 1 holds the text
    stream (tower = text stack over its own input-embedding copy).  The
    merge is the SEQUENCE concatenation [vision; text] — cuts have
    different lengths, so the program supplies ``merge_fn`` instead of a
    uniform (K, B, S, D) stack, and a dropped modality zeroes its segment
    (the monolithic ``live_mask`` semantics)."""

    per_client_towers = True

    def __init__(self, cfg: ArchConfig):
        super().__init__(cfg)
        if cfg.vertical.num_clients != 2:
            raise ValueError("the vlm by-source split has exactly two "
                             f"clients (vision, text); got "
                             f"{cfg.vertical.num_clients}")
        self.merge_fn = self._merge_seqcat

    def _merge_seqcat(self, cuts, live_mask=None):
        if live_mask is not None:
            lm = jnp.asarray(live_mask)
            cuts = [c * lm[k].astype(c.dtype) for k, c in enumerate(cuts)]
        return jnp.concatenate(list(cuts), axis=1)

    def partition(self, params):
        # the text client's input-embedding copy trains locally while the
        # unembed head trains at the server — the same split as the token
        # LMs' embedding-column slices
        towers = [
            {"blocks": params["vision_tower"]},
            {"embed": params["embed"], "blocks": params["text_tower"]},
        ]
        server = {key: val for key, val in params.items()
                  if key not in ("vision_tower", "text_tower")}
        return towers, server

    def tower_fwd(self, client: int) -> Callable:
        cfg = self.cfg
        dims = BlockDims.from_arch(cfg)
        Sv = cfg.vlm.num_vision_tokens

        if client == 0:
            def vision_fwd(tp, patches):
                x = patches.astype(
                    jax.tree_util.tree_leaves(tp["blocks"])[0].dtype)
                positions = jnp.arange(Sv, dtype=jnp.int32)
                return tfm.dense_stack_apply(tp["blocks"], x, dims,
                                             causal=False,
                                             positions=positions)

            return vision_fwd

        def text_fwd(tp, tokens):
            x = layers.embed(tp["embed"], tokens)
            positions = Sv + jnp.arange(tokens.shape[-1], dtype=jnp.int32)
            return tfm.dense_stack_apply(tp["blocks"], x, dims, causal=True,
                                         positions=positions)

        return text_fwd

    def server_fwd(self, sp, merged):
        cfg = self.cfg
        dims = BlockDims.from_arch(cfg)
        positions = jnp.arange(merged.shape[1], dtype=jnp.int32)
        x = tfm.dense_stack_apply(sp["server"], merged, dims, causal=True,
                                  positions=positions)
        x = tfm._norm(sp["final_norm"], x, dims.norm, dims.norm_eps)
        Sv = cfg.vlm.num_vision_tokens
        return layers.unembed(sp["embed"], x[:, Sv:, :])

    def loss_fn(self, logits, labels):
        from repro.models.backbone import lm_loss

        return lm_loss(logits, labels)

    def features(self, batch):
        return [jnp.asarray(batch["patches"]), jnp.asarray(batch["tokens"])]

    def feature_fn(self, client, *, batch, seq, seed=0, microbatches=1):
        key = "patches" if client == 0 else "tokens"
        return self._loader_feature_fn(
            batch=batch, seq=seq, seed=seed, microbatches=microbatches,
            extract=lambda b: b[key])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_PROGRAMS: dict[str, type] = {
    "dense": TokenLMSplitProgram,
    "ssm": TokenLMSplitProgram,
    "hybrid": TokenLMSplitProgram,
    "moe": TokenLMSplitProgram,
    "audio": AudioSplitProgram,
    "vlm": VLMSplitProgram,
}

SPLIT_EXEC_FAMILIES = tuple(_PROGRAMS)


def get_program(cfg: ArchConfig) -> SplitProgram:
    """The registered :class:`SplitProgram` for ``cfg``'s family."""
    if cfg.vertical is None:
        raise ValueError(f"{cfg.name}: split execution needs a vertical "
                         "config")
    try:
        cls = _PROGRAMS[cfg.family]
    except KeyError:
        raise NotImplementedError(
            f"no SplitProgram registered for family {cfg.family!r} "
            f"(known: {SPLIT_EXEC_FAMILIES})") from None
    return cls(cfg)

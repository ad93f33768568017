"""Top-level Model: embeddings -> stack -> head, plus the three entry
points the launchers lower (``loss_fn`` for train_step, ``prefill`` and
``decode_step`` for serve_step).

Frontend stubs per the assignment:
  * VLM (pixtral): ``patch_embeds`` [B, P, d] are prepended to the text
    embeddings (positions continue through the patch region).
  * audio (whisper): ``frames`` [B, Senc, d] are the encoder input; the
    conv/mel stack is out of scope.

Caches are descriptor trees mirroring the layer layout, so the dry-run
can abstract them (``abstract_cache``) without allocating 32k x 128-batch
KV.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import (embed_desc, embed_apply, norm_desc,
                                 norm_apply, unembed_apply)
from repro.models.module import (ParamDesc, abstract_params, init_params,
                                 is_desc, logical_axes, param_count,
                                 tree_map_with_path)


class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def desc(self):
        cfg = self.cfg
        d = {"embed": embed_desc(cfg),
             "stack": tfm.stack_desc_tree(cfg, cross=cfg.is_encdec),
             "final_norm": norm_desc(cfg)}
        if cfg.is_encdec:
            enc_cfg = cfg.replace(n_layers=cfg.n_encoder_layers, n_experts=0,
                                  attn_layer_period=0)
            d["encoder"] = {
                "stack": tfm.stack_desc_tree(enc_cfg, cross=False),
                "final_norm": norm_desc(cfg),
            }
            if cfg.pos == "learned":
                d["encoder"]["pos"] = ParamDesc(
                    (cfg.encoder_seq, cfg.d_model), jnp.bfloat16,
                    (None, "embed"), "embed")
        return d

    def init(self, rng):
        return init_params(rng, self.desc())

    def abstract(self):
        return abstract_params(self.desc())

    def axes(self):
        return logical_axes(self.desc())

    def n_params(self) -> int:
        return param_count(self.desc())

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def _layer_cache_desc(self, i: int, batch: int, length: int):
        cfg = self.cfg
        kind = cfg.layer_kind(i)
        c = {}
        if kind == "attn":
            if cfg.attention == "mla":
                c["self"] = attn.cache_desc_mla(cfg, batch, length)
            else:
                c["self"] = attn.cache_desc_gqa(cfg, batch, length)
        else:
            c["ssm"] = ssm_mod.ssm_cache_desc(cfg, batch)
        if cfg.is_encdec:
            hd = cfg.head_dim_
            c["cross_k"] = ParamDesc((batch, cfg.encoder_seq, cfg.n_kv_heads, hd),
                                     jnp.bfloat16,
                                     ("batch", None, "kv_heads", "head_dim"),
                                     "zeros")
            c["cross_v"] = ParamDesc((batch, cfg.encoder_seq, cfg.n_kv_heads, hd),
                                     jnp.bfloat16,
                                     ("batch", None, "kv_heads", "head_dim"),
                                     "zeros")
        return c

    def cache_desc(self, batch: int, length: int):
        stack = tfm.stack_desc_tree(self.cfg, cross=self.cfg.is_encdec)
        return tfm.map_stack(stack,
                             lambda i: self._layer_cache_desc(i, batch, length),
                             self.cfg)

    def init_cache(self, batch: int, length: int):
        cache = init_params(jax.random.PRNGKey(0), self.cache_desc(batch, length))
        return self._blank_pos(cache)

    def abstract_cache(self, batch: int, length: int):
        return abstract_params(self.cache_desc(batch, length))

    def paged_cache_desc(self, batch: int, num_blocks: int, block_size: int,
                         max_blocks_per_seq: int):
        """Paged KV cache: per-layer block pools shared across sequences
        plus a [batch, max_blocks_per_seq] block table per layer (all
        layers carry the same table values; see serve.paging).

        Only attention-only decoders page: SSM states are O(1) per
        sequence (nothing to page) and encoder-decoder cross-KV is a
        fixed per-row reservation.
        """
        cfg = self.cfg
        if cfg.is_encdec or any(cfg.layer_kind(i) != "attn"
                                for i in range(cfg.n_layers)):
            raise ValueError("paged cache supports attention-only decoders")
        stack = tfm.stack_desc_tree(cfg, cross=False)
        return tfm.map_stack(
            stack,
            lambda i: {"self": attn.paged_cache_desc(
                cfg, batch, num_blocks, block_size, max_blocks_per_seq)},
            cfg)

    def init_paged_cache(self, batch: int, num_blocks: int, block_size: int,
                         max_blocks_per_seq: int):
        cache = init_params(jax.random.PRNGKey(0), self.paged_cache_desc(
            batch, num_blocks, block_size, max_blocks_per_seq))
        return self._blank_pos(cache)

    def paged_cache_axes(self, batch: int, num_blocks: int, block_size: int,
                         max_blocks_per_seq: int):
        """Logical-axes tree for SHARDING a paged cache.

        The pool descriptors are the contiguous ones with batch ->
        num_blocks, so their leading axis is labelled "batch" — but the
        pool dim must never shard over the data axis (every sequence's
        block table can point anywhere in the pool), and neither may the
        within-block sequence dim that MLA labels "kv_seq" (a block is
        the DMA unit of the fused kernel).  Head axes survive, so
        ``build_shardings`` puts the pool's kv_heads (or, via its
        divisibility fallback, head_dim) on the model axis exactly like
        the contiguous cache.  Block tables are replicated host state.
        """
        def fix(path, d):
            if not is_desc(d):
                return d
            axes = d.axes or (None,) * len(d.shape)
            if path and path[-1] == "block_tables":
                axes = (None,) * len(d.shape)
            else:
                axes = tuple(None if a in ("batch", "kv_seq") else a
                             for a in axes)
            return dataclasses.replace(d, axes=axes)
        desc = tree_map_with_path(fix, self.paged_cache_desc(
            batch, num_blocks, block_size, max_blocks_per_seq))
        return logical_axes(desc)

    @staticmethod
    def _blank_pos(cache):
        """Set every 'pos' / 'block_tables' buffer to -1 (empty)."""
        def fix(path, leaf):
            if path and path[-1] in ("pos", "block_tables"):
                return jnp.full_like(leaf, -1)
            return leaf
        return tree_map_with_path(fix, cache)

    # ------------------------------------------------------------------
    # forward paths
    # ------------------------------------------------------------------
    def _embed(self, params, batch: dict, start_pos=0):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        start = jnp.asarray(start_pos, jnp.int32)
        if start.ndim == 0:
            start = jnp.broadcast_to(start, (b,))

        def pos_for(length):
            return start[:, None] + jnp.arange(length, dtype=jnp.int32)[None]

        positions = pos_for(s)
        if "patch_embeds" in batch:                      # VLM stub frontend
            p = batch["patch_embeds"].shape[1]
            x_txt = embed_apply(params["embed"], tokens)
            x = jnp.concatenate(
                [batch["patch_embeds"].astype(x_txt.dtype), x_txt], axis=1)
            s = x.shape[1]
            positions = pos_for(s)
            if cfg.pos == "learned":
                x = x + jnp.take(params["embed"]["pos"],
                                 jnp.maximum(positions, 0), axis=0)
            return x, positions
        # per-row positions (ragged serving batches); negative positions
        # mark masked left-pads — clamp the table lookup, the attention
        # pos-mask hides the garbage row
        x = embed_apply(params["embed"], tokens,
                        jnp.maximum(positions, 0)
                        if cfg.pos == "learned" else None)
        return x, positions

    def encode(self, params, frames):
        """Whisper encoder over precomputed frame embeddings (stub)."""
        cfg = self.cfg
        enc_cfg = cfg.replace(n_layers=cfg.n_encoder_layers, n_experts=0,
                              attn_layer_period=0)
        b, s, _ = frames.shape
        x = frames
        if "pos" in params["encoder"]:
            x = x + params["encoder"]["pos"][None, :s].astype(x.dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x, _, _ = tfm.stack_apply(params["encoder"]["stack"], enc_cfg, x,
                               positions, causal=False,
                               backend=cfg.backend_preference)
        return norm_apply(params["encoder"]["final_norm"], x)

    def _logits_padded(self, params, batch: dict):
        """[B, S, padded_vocab] — internal; keeps the vocab dim sharded."""
        cfg = self.cfg
        enc_out = None
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"])
        x, positions = self._embed(params, batch)
        x, _, _ = tfm.stack_apply(params["stack"], cfg, x, positions,
                                  enc_out=enc_out,
                                  backend=cfg.backend_preference)
        x = norm_apply(params["final_norm"], x)
        return unembed_apply(params["embed"], x, backend=cfg.backend_preference)

    def forward(self, params, batch: dict):
        """Full-sequence logits (training / eval). Returns [B, S, V]."""
        return self._logits_padded(params, batch)[..., : self.cfg.vocab_size]

    def loss_fn(self, params, batch: dict):
        """Next-token cross-entropy, sharded-vocab-safe.

        NEVER gathers the full logits across the model axis: the target
        logit is extracted with an iota==target mask (stays sharded; the
        vocab reduction becomes a partial-sum + all-reduce of [B, S]
        scalars instead of an all-gather of [B, S, V] floats — the
        difference between ~26 GB and ~128 KB of cross-device traffic for
        a 100k vocab at train_4k scale).
        """
        logits = self._logits_padded(params, batch)   # [B, S, Vpad] f32
        tokens = batch["tokens"]
        if "patch_embeds" in batch:                   # loss only on text part
            p = batch["patch_embeds"].shape[1]
            logits = logits[:, p:]
        targets = tokens[:, 1:].astype(jnp.int32)
        logits = logits[:, :-1].astype(jnp.float32)
        iota_v = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        # mask vocab padding out of the partition function
        logits = jnp.where(iota_v < self.cfg.vocab_size, logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)        # [B, S-1]
        onehot = iota_v == targets[..., None]
        ltgt = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)   # [B, S-1]
        return (lse - ltgt).mean()

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def prefill(self, params, batch: dict, cache, start_pos=0):
        """Run the prompt through the stack, filling the cache.

        ``start_pos`` (scalar or [B]) is the absolute position of the
        first token; a *negative* start marks left-pads — they get
        positions < 0, which the attention pos-mask hides and the cache
        insert treats as dead writes, so padded prompts score exactly
        like unpadded ones.  Returns (last-token logits [B, V], cache).
        """
        cfg = self.cfg
        enc_out = None
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"])
        x, positions = self._embed(params, batch, start_pos)
        x, cache, _ = tfm.stack_apply(params["stack"], cfg, x, positions,
                                      caches=cache,
                                      cache_at=positions[:, 0],
                                      enc_out=enc_out,
                                      backend=cfg.backend_preference,
                                      valid=positions >= 0)
        x = norm_apply(params["final_norm"], x[:, -1:])
        logits = unembed_apply(params["embed"], x,
                               backend=cfg.backend_preference)[:, 0, : cfg.vocab_size]
        return logits, cache

    def prefill_chunk(self, params, batch: dict, cache, start_pos, last_idx,
                      counters: bool = False):
        """One chunk of a chunked prefill: tokens [B, C] at absolute
        positions ``start_pos + [0, C)``, writing straight into the
        (typically paged) cache and attending over everything cached so
        far.  ``last_idx`` [B] selects the row's last *real* token
        (chunks are right-padded to a length bucket; padded positions
        are dead writes and route to no expert).  Returns (logits at
        last_idx [B, V], cache), and the step's counters
        (``tfm.stack_apply``'s stats) third with ``counters=True``.
        """
        cfg = self.cfg
        x, positions = self._embed(params, batch, start_pos)
        b, c = positions.shape
        idx = jnp.asarray(last_idx, jnp.int32)
        if idx.ndim == 0:
            idx = jnp.broadcast_to(idx, (b,))
        valid = (positions >= 0) & \
            (jnp.arange(c, dtype=jnp.int32)[None] <= idx[:, None])
        x, cache, stats = tfm.stack_apply(params["stack"], cfg, x, positions,
                                          caches=cache,
                                          cache_at=positions[:, 0],
                                          backend=cfg.backend_preference,
                                          valid=valid)
        x = x[jnp.arange(b), idx][:, None]               # [B, 1, d]
        x = norm_apply(params["final_norm"], x)
        logits = unembed_apply(params["embed"], x,
                               backend=cfg.backend_preference)[:, 0, : cfg.vocab_size]
        return (logits, cache, stats) if counters else (logits, cache)

    def decode_and_sample(self, params, tokens, cache, pos, keys,
                          temperature, top_k, counters: bool = False):
        """Fused decode + on-device sampling: one decode step followed by
        :func:`sample_tokens`, so only the sampled token ids (int32 [B])
        ever cross the host boundary — the async serving engine jits this
        instead of ``decode_step`` and defers the host sync by a full
        tick.  ``keys`` are per-row uint32 [B, 2] PRNG keys; rows with
        ``temperature <= 0`` ignore their key (greedy argmax).  Returns
        (token ids int32 [B], cache), and the step's counters third with
        ``counters=True``.
        """
        logits, cache, stats = self.decode_step(params, tokens, cache, pos,
                                                counters=True)
        toks = sample_tokens(logits, keys, temperature, top_k)
        return (toks, cache, stats) if counters else (toks, cache)

    def decode_step(self, params, tokens, cache, pos,
                    counters: bool = False):
        """One decode step. tokens: [B, 1]; pos: scalar or [B] absolute
        position of the new token. Returns (logits [B, V], cache), and
        the step's counters third with ``counters=True``.  A row of a
        paged cache whose block table is empty (an idle engine row)
        routes to no expert."""
        cfg = self.cfg
        b = tokens.shape[0]
        pos_arr = jnp.asarray(pos, jnp.int32)
        if pos_arr.ndim == 0:
            pos_arr = jnp.broadcast_to(pos_arr, (b,))
        positions = pos_arr[:, None]
        x = embed_apply(params["embed"], tokens,
                        jnp.maximum(positions, 0)
                        if cfg.pos == "learned" else None)
        valid = (positions >= 0) & _live_rows(cache, b)[:, None]
        x, cache, stats = tfm.stack_apply(params["stack"], cfg, x, positions,
                                          caches=cache, cache_at=pos_arr,
                                          backend=cfg.backend_preference,
                                          valid=valid)
        x = norm_apply(params["final_norm"], x)
        logits = unembed_apply(params["embed"], x,
                               backend=cfg.backend_preference)[:, 0, : cfg.vocab_size]
        return (logits, cache, stats) if counters else (logits, cache)


def _live_rows(cache, batch: int):
    """bool [B]: rows that hold a sequence -- in a paged cache, those
    whose block table has a first block (the engine parks idle rows on
    an all-empty table); every row of a contiguous cache."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if getattr(path[-1], "key", None) == "block_tables":
            tables = leaf.reshape(-1, *leaf.shape[-2:])[0]   # any layer's
            return tables[:, 0] >= 0
    return jnp.ones((batch,), bool)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def sample_tokens(logits, keys, temperature, top_k):
    """Batched token sampling as a pure function of ``(logits, key)``.

    Per row: ``temperature <= 0`` is greedy argmax (ties break to the
    lowest index, matching ``np.argmax``); otherwise logits outside the
    ``top_k`` largest (``top_k <= 0`` means no truncation) are masked to
    ``-inf``, the rest are divided by the temperature and sampled via
    ``jax.random.categorical`` under a per-row key.  Because the result
    depends only on the row's logits and key — never on batch position
    or previous draws — the synchronous host-side sampler and the async
    fused :meth:`Model.decode_and_sample` path produce bit-identical
    tokens for the same request state, which is what the engine's
    sync==async equivalence tests assert.

    logits: [B, V] float; keys: uint32 [B, 2] (raw key data, one per
    row); temperature: float [B]; top_k: int32 [B].  Returns int32 [B].
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.asarray(top_k, jnp.int32)
    kk = jnp.clip(jnp.where(k <= 0, v, k), 1, v)
    # top-k threshold: the k-th largest logit per row; everything below
    # it leaves the candidate set (ties AT the threshold all stay in,
    # which keeps the mask a pure function of the logit values)
    order = jnp.sort(logits, axis=-1)[:, ::-1]
    thresh = jnp.take_along_axis(order, (kk - 1)[:, None], axis=-1)
    masked = jnp.where(logits < thresh, -jnp.inf, logits)
    temp = jnp.asarray(temperature, jnp.float32)
    scaled = masked / jnp.maximum(temp, 1e-6)[:, None]
    keys = jnp.asarray(keys, jnp.uint32)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)

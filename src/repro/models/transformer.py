"""Decoder/encoder blocks and the layer-stack assembler.

Supports heterogeneous stacks (jamba: mamba/attn interleave, MoE on every
2nd layer; deepseek: dense layer 0 then MoE) via per-layer (mixer, mlp)
kinds from the config, and two execution modes:

  * unrolled — plain python loop (smoke tests, CPU examples, roofline
    cost extraction where while-loop bodies would be undercounted);
  * scan     — the stack after an unrolled prefix is grouped into the
    architecture's repeating *period* (lcm of mixer/MoE patterns); params
    of each position-within-period are stacked with a leading "layers"
    axis and one lax.scan step executes a full period in true layer order.
    O(1) HLO size for 60-72-layer models -> fast 512-device compiles.

Activation checkpointing (remat) wraps each block on the train path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp_desc, mlp_apply, norm_desc, norm_apply
from repro.models.module import ParamDesc, is_desc


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def block_desc(cfg, kind: str, mlp_kind: str, cross: bool = False,
               d_ff: Optional[int] = None):
    """Params of one block: norm->mixer[->norm->cross][->norm->mlp]."""
    p = {"ln1": norm_desc(cfg)}
    p["mixer"] = attn.attn_desc(cfg) if kind == "attn" else ssm_mod.ssm_desc(cfg)
    if cross:
        p["ln_cross"] = norm_desc(cfg)
        p["cross"] = attn.cross_attn_desc(cfg)
    if cfg.d_ff or mlp_kind == "moe":
        p["ln2"] = norm_desc(cfg)
        p["mlp"] = (moe_mod.moe_desc(cfg) if mlp_kind == "moe"
                    else mlp_desc(cfg, d_ff))
    return p


def block_apply(params, cfg, kind: str, mlp_kind: str, x, positions, *,
                cache=None, cache_at=None, causal=True, enc_out=None,
                backend=None, valid=None):
    """Returns (x, new_cache, load); cache is None on the train path.

    ``valid`` (bool [B, S], the serving steps) routes an MoE layer's
    valid tokens dropless and returns its per-expert rows as ``load``
    (int32 [E]); otherwise ``load`` is None."""
    h = norm_apply(params["ln1"], x)
    if kind == "attn":
        mixer = attn.mla_apply if cfg.attention == "mla" else attn.gqa_apply
        kw = {} if cfg.attention == "mla" else {"causal": causal}
        if cache is not None and "self" in (cache or {}):
            h, self_c = mixer(params["mixer"], cfg, h, positions,
                              cache=cache["self"], cache_at=cache_at,
                              backend=backend, **kw)
            cache = {**cache, "self": self_c}
        else:
            h = mixer(params["mixer"], cfg, h, positions, backend=backend, **kw)
    else:
        if cache is not None and "ssm" in cache:
            h, ssm_c = ssm_mod.ssm_apply(params["mixer"], cfg, h,
                                         cache=cache["ssm"], backend=backend)
            cache = {**cache, "ssm": ssm_c}
        else:
            h = ssm_mod.ssm_apply(params["mixer"], cfg, h, backend=backend)
    x = x + h.astype(x.dtype)

    if "cross" in params and (enc_out is not None or
                              (cache is not None and "cross_k" in cache)):
        h = norm_apply(params["ln_cross"], x)
        if enc_out is not None:
            ck, cv = attn.cross_kv(params["cross"], cfg, enc_out, backend)
            if cache is not None:
                cache = {**cache, "cross_k": ck.astype(cache["cross_k"].dtype),
                         "cross_v": cv.astype(cache["cross_v"].dtype)}
        else:
            ck, cv = cache["cross_k"], cache["cross_v"]
        h = attn.cross_attend(params["cross"], cfg, h, ck, cv, backend=backend)
        x = x + h.astype(x.dtype)

    load = None
    if "mlp" in params:
        h = norm_apply(params["ln2"], x)
        if mlp_kind == "moe" and valid is not None:
            h, load = moe_mod.moe_dropless(params["mlp"], cfg, h, valid,
                                           backend=backend)
        elif mlp_kind == "moe":
            h = moe_mod.moe_apply(params["mlp"], cfg, h, backend=backend)
        else:
            h = mlp_apply(params["mlp"], h, backend=backend)
        x = x + h.astype(x.dtype)
    return x, cache, load


# ---------------------------------------------------------------------------
# layer plan / scan grouping
# ---------------------------------------------------------------------------


def layer_plan(cfg):
    """(mixer_kind, mlp_kind) per decoder layer."""
    return [(cfg.layer_kind(i), cfg.mlp_kind(i)) for i in range(cfg.n_layers)]


def scan_grouping(cfg):
    """(prefix, period, repeats): layers[prefix:] tile with ``period``."""
    plan = layer_plan(cfg)
    pre = cfg.first_dense_layers
    body = plan[pre:]
    if not body:
        return pre, 0, 0
    for period in range(1, len(body) + 1):
        if len(body) % period:
            continue
        if all(body[i] == body[i % period] for i in range(len(body))):
            return pre, period, len(body) // period
    raise AssertionError("unreachable: period=len(body) always tiles")


def stack_descs(tree, n: int):
    """Add a leading stacked-layers dim to every ParamDesc in a tree."""
    def f(d):
        if not is_desc(d):
            return d
        axes = ("layers", *(d.axes if d.axes else (None,) * len(d.shape)))
        return ParamDesc((n, *d.shape), d.dtype, axes, d.init, d.scale)
    return jax.tree_util.tree_map(f, tree, is_leaf=is_desc)


def stack_desc_tree(cfg, cross: bool = False):
    """Decoder-stack descriptors: {'layers': [...]} or {'prefix','scan'}."""
    plan = layer_plan(cfg)
    if not cfg.scan_layers:
        return {"layers": [block_desc(cfg, k, m, cross) for k, m in plan]}
    pre, period, reps = scan_grouping(cfg)
    out = {}
    if pre:
        out["prefix"] = [block_desc(cfg, *plan[i], cross) for i in range(pre)]
    if reps:
        out["scan"] = [stack_descs(block_desc(cfg, *plan[pre + j], cross), reps)
                       for j in range(period)]
    return out


def map_stack(desc_or_params, fn_layer, cfg):
    """Apply fn_layer(layer_index, subtree) over every physical layer slot.

    Used to build per-layer caches matching the param layout.
    """
    plan = layer_plan(cfg)
    if "layers" in desc_or_params:
        return {"layers": [fn_layer(i) for i in range(len(plan))]}
    pre, period, reps = scan_grouping(cfg)
    out = {}
    if pre:
        out["prefix"] = [fn_layer(i) for i in range(pre)]
    if reps:
        # group j stacks layers pre+j, pre+j+period, ... — kinds identical,
        # so one representative cache desc stacked over repeats
        out["scan"] = [stack_descs(fn_layer(pre + j), reps)
                       for j in range(period)]
    return out


# ---------------------------------------------------------------------------
# stack execution
# ---------------------------------------------------------------------------


def _run_block(bparams, cfg, kind, mlpk, x, positions, cache, cache_at,
               causal, enc_out, backend, valid=None):
    if cfg.remat and cache is None:
        from repro.parallel.sharding import shard_act

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def fn(bp, x_in):
            y, _, _ = block_apply(bp, cfg, kind, mlpk, x_in, positions,
                                  cache=None, cache_at=None, causal=causal,
                                  enc_out=enc_out, backend=backend)
            return y
        # shard the remat stash: the saved per-layer block input is the
        # dominant train-memory term ([L, B, S, d] bf16); sharding its
        # embed dim over the model axis cuts it 16x for one extra
        # all-gather per layer in the backward recompute ("act_embed"
        # rule, enabled by the train launcher).
        x = shard_act(x, ("batch", None, "act_embed"))
        return fn(bparams, x), None, None
    return block_apply(bparams, cfg, kind, mlpk, x, positions, cache=cache,
                       cache_at=cache_at, causal=causal, enc_out=enc_out,
                       backend=backend, valid=valid)


def _count(stats, load):
    """Fold one MoE layer's per-expert rows into the step's counters."""
    if load is None:
        return stats
    return {"experts_touched": stats["experts_touched"]
            + jnp.sum(load > 0).astype(jnp.int32),
            "expert_load_max": jnp.maximum(stats["expert_load_max"],
                                           jnp.max(load).astype(jnp.int32))}


def stack_apply(params, cfg, x, positions, *, caches=None, cache_at=None,
                causal=True, enc_out=None, backend=None, valid=None):
    """Run the decoder stack; returns (x, new_caches-or-None, stats).

    ``valid`` (bool [B, S]: the tokens a serving step computes for real)
    turns on dropless MoE routing; ``stats`` then holds the step's
    ``experts_touched`` (distinct (MoE layer, expert) pairs with a valid
    row) and ``expert_load_max`` (most rows one expert got in one layer),
    int32 scalars.  Without MoE layers or ``valid`` it is ``{}``."""
    plan = layer_plan(cfg)
    stats = {}
    if valid is not None and any(m == "moe" for _, m in plan):
        stats = {"experts_touched": jnp.int32(0),
                 "expert_load_max": jnp.int32(0)}

    if "layers" in params:                                   # unrolled
        new = [] if caches is not None else None
        for i, bp in enumerate(params["layers"]):
            c = caches["layers"][i] if caches is not None else None
            x, c2, load = _run_block(bp, cfg, *plan[i], x, positions, c,
                                     cache_at, causal, enc_out, backend,
                                     valid)
            stats = _count(stats, load)
            if new is not None:
                new.append(c2)
        return x, ({"layers": new} if new is not None else None), stats

    pre, period, reps = scan_grouping(cfg)
    new_caches = {} if caches is not None else None

    if "prefix" in params:
        outs = []
        for j, bp in enumerate(params["prefix"]):
            c = caches["prefix"][j] if caches is not None else None
            x, c2, load = _run_block(bp, cfg, *plan[j], x, positions, c,
                                     cache_at, causal, enc_out, backend,
                                     valid)
            stats = _count(stats, load)
            outs.append(c2)
        if new_caches is not None:
            new_caches["prefix"] = outs

    if "scan" in params:
        groups = params["scan"]
        cstacks = caches["scan"] if caches is not None else None

        def body(carry, layer_slice):
            y, st = carry
            bps, cs = layer_slice
            new_cs = []
            for j in range(period):
                kind, mlpk = plan[pre + j]
                cj = cs[j] if cs is not None else None
                y, c2, load = _run_block(bps[j], cfg, kind, mlpk, y,
                                         positions, cj, cache_at, causal,
                                         enc_out, backend, valid)
                st = _count(st, load)
                new_cs.append(c2)
            return (y, st), (new_cs if cs is not None else None)

        if caches is None:
            (x, stats), _ = jax.lax.scan(
                lambda c, g: body(c, (g, None)), (x, stats), groups)
        else:
            (x, stats), cs_new = jax.lax.scan(body, (x, stats),
                                              (groups, cstacks))
            new_caches["scan"] = cs_new
    return x, new_caches, stats

"""Mixture-of-Experts: top-k router, dropless grouped dispatch for
serving, capacity-based scatter dispatch for training.

Routing (both paths): router logits -> softmax -> top-k; the k gates are
renormalised to sum 1 only where the config says so (``norm_topk_prob``;
DeepSeek-V2 does not) and scaled by ``routed_scaling_factor``.  Shared
experts (deepseek-style) run on every token as dense linears.

Serving (:func:`moe_dropless`: every step that passes the stack a
``valid`` mask, as the engines' prefill and decode do) is dropless:
every valid (token, k) assignment is sorted by expert into whole row
tiles (``kernels.grouped_matmul.expert_layout``), the expert FFN runs
once over the sorted rows, and the outputs are scatter-added back to
their tokens weighted by gate.  There is no capacity, so no
assignment is dropped, and padding tokens (idle decode rows, the pad of
a prefill bucket) are not routed.  A BCQ expert bank runs through the
grouped BCQ kernel, which reads only the planes of experts that got
rows; any other bank (dense, or a backend without a Pallas kernel)
through ``jax.lax.ragged_dot`` over the sorted rows.  The layer also
returns each expert's valid rows, which the engine turns into a step's
``experts_touched`` and ``expert_load_max`` counters.

Training and the no-cache forward (:func:`moe_apply`) keep the GShard
capacity dispatch, which keeps shapes static and the dispatch
row-local under pjit:

  1. each (token, k) assignment gets a position inside its expert via a
     cumulative count; assignments beyond capacity C are dropped
     (C = ceil(T * k / E) * capacity_factor),
  2. expert inputs are gathered into [E, C, d] (scatter by (expert, pos)),
  3. experts run as a batched einsum over E — with the "experts" logical
     axis sharded over the model axis this is expert parallelism,
  4. outputs are gathered back to token order, weighted by gates, summed
     over k.

FIGLUT integration: every expert weight is a quantizable linear (the
bit-plane format is per-2D-matrix, so the stacked [E, f, d] expert bank is
quantized per expert by ``repro.quant.ptq``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bcq import BCQWeight, dequantize
from repro.models.module import ParamDesc


def moe_desc(cfg):
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.n_experts
    p = {
        "router": ParamDesc((e, d), jnp.float32, ("experts", "embed")),
        "gate": ParamDesc((e, f, d), jnp.bfloat16, ("experts", "mlp", "embed")),
        "up": ParamDesc((e, f, d), jnp.bfloat16, ("experts", "mlp", "embed")),
        "down": ParamDesc((e, d, f), jnp.bfloat16, ("experts", "embed", "mlp")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared_gate"] = ParamDesc((fs, d), jnp.bfloat16, ("mlp", "embed"))
        p["shared_up"] = ParamDesc((fs, d), jnp.bfloat16, ("mlp", "embed"))
        p["shared_down"] = ParamDesc((d, fs), jnp.bfloat16, ("embed", "mlp"))
    return p


def _expert_bank(w, shape3d):
    """Dense [E, out, in] view of an expert weight (dequantize if BCQ).

    Expert banks are quantized with E as a leading batch dim (packed
    [E, q, out, in/8]) so the dequantized dense bank keeps the expert-
    parallel sharding — folding E into the row dim merges a sharded dim
    and forces a whole-bank all-gather on every layer.  Reconstruction is
    vmapped over E in bf16 (the serve compute dtype).
    """
    if isinstance(w, BCQWeight):
        if w.packed.ndim == 4:          # [E, q, out, in/8]
            def sub(p, a, z=None):
                return dequantize(
                    BCQWeight(packed=p, alpha=a, z=z,
                              group_size=w.group_size,
                              in_features=w.in_features,
                              out_features=w.out_features, kind=w.kind),
                    jnp.bfloat16)
            if w.z is None:             # ternary banks carry no offset row
                dense = jax.vmap(sub)(w.packed, w.alpha)
            else:
                dense = jax.vmap(sub)(w.packed, w.alpha, w.z)
            return dense.reshape(shape3d)
        return dequantize(w, jnp.bfloat16).reshape(shape3d)
    return w


def route(router, cfg, x):
    """Gates and experts [..., k] of each token: softmax over the experts,
    top-k, renormalised only under ``norm_topk_prob``, times
    ``routed_scaling_factor``."""
    logits = jnp.einsum("...d,ed->...e", x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates * cfg.routed_scaling_factor, experts


def _shared(params, x, backend):
    from repro.core.quantized_linear import linear_apply
    sg = linear_apply(params["shared_gate"], x, backend=backend)
    su = linear_apply(params["shared_up"], x, backend=backend)
    sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x.dtype) * su
    return linear_apply(params["shared_down"], sh,
                        backend=backend).astype(jnp.float32)


def _grouped_kernel_backend(bank, backend) -> Optional[bool]:
    """For a BCQ expert bank: whether the grouped Pallas kernel runs it
    (``None`` -> not at all, interpret -> ``True`` off the chip).  The
    bank negotiates like one expert's matrix would: the preference's
    chain must land on a Pallas plane kernel."""
    from repro.core.lut_gemm import interpret_mode
    from repro.quant.backends import get_backend, resolve_backend
    if not isinstance(bank, BCQWeight) or bank.kind != "bcq" \
            or bank.z is None or bank.packed.ndim != 4:
        return None
    one = BCQWeight(packed=bank.packed[0], alpha=bank.alpha[0], z=bank.z[0],
                    group_size=bank.group_size,
                    in_features=bank.in_features,
                    out_features=bank.out_features, kind=bank.kind)
    if get_backend(resolve_backend(backend, one)).kernel is None:
        return None
    return interpret_mode()


def _swiglu(rows, mm):
    """The routed SwiGLU over expert-sorted rows; ``mm(x, role)`` is one
    role's grouped matmul."""
    g = mm(rows, "gate")
    u = mm(rows, "up")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(rows.dtype) * u
    return mm(h, "down")


def moe_dropless(params, cfg, x, valid, backend=None):
    """The serving dispatch: x [B, S, d], valid bool [B, S] (tokens to
    route).  Returns (y [B, S, d], load int32 [E]: valid rows each
    expert ran)."""
    from repro.kernels.grouped_matmul import (bcq_grouped_matmul,
                                              expert_layout, gather_rows,
                                              row_tile, scatter_rows)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    dt = x.dtype
    xt = x.reshape(b * s, d)
    gates, experts = route(params["router"], cfg, xt)      # [T, k]
    a = b * s * k
    flat_e = experts.reshape(a).astype(jnp.int32)
    tok = jnp.arange(a, dtype=jnp.int32) // k
    ok = valid.reshape(b * s)[tok]
    interp = _grouped_kernel_backend(params["gate"], backend)
    if interp is not None:
        # BCQ banks: whole row tiles per expert, read by the kernel
        layout = expert_layout(flat_e, ok, e, row_tile(a, e))
        y_pad = _swiglu(scatter_rows(xt[tok], layout),
                        lambda r, role: bcq_grouped_matmul(
                            r, params[role], layout, interpret=interp,
                            out_dtype=dt))
        y_a, load = gather_rows(y_pad, layout), layout.load
    else:
        # any other bank: rows sorted by expert, the invalid ones last
        # (past the groups' total, so ragged_dot leaves them zero)
        key = jnp.where(ok, flat_e, e)
        order = jnp.argsort(key, stable=True)
        load = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        f = cfg.moe_d_ff or cfg.d_ff
        shapes = {"gate": (e, f, d), "up": (e, f, d), "down": (e, d, f)}

        def mm(r, role):
            w = _expert_bank(params[role], shapes[role])
            return jax.lax.ragged_dot(
                r, jnp.swapaxes(w, 1, 2).astype(dt), load,
                preferred_element_type=jnp.float32).astype(dt)
        y_sorted = _swiglu(xt[tok[order]], mm)
        y_a = jnp.zeros_like(y_sorted).at[order].set(y_sorted)
    w = jnp.where(ok, gates.reshape(a), 0.0)
    y = jnp.zeros((b * s, d), jnp.float32).at[tok].add(
        y_a.astype(jnp.float32) * w[:, None])
    if "shared_gate" in params:
        y = y + _shared(params, x, backend).reshape(b * s, d)
    return y.reshape(b, s, d).astype(dt), load


def moe_apply(params, cfg, x, backend=None):
    """x: [B, S, d] -> [B, S, d].  Static shapes throughout (pjit-safe).

    Dispatch is GROUPED per batch row (GShard groups): each row gets its
    own expert-capacity quota and computes positions-in-expert locally, so
    the dispatch scatter never crosses the data axis.  With xin sharded
    (experts->model, rows->data), cross-device traffic is the intended
    [tokens, d] all-to-all — a GLOBAL argsort dispatch instead produces a
    partial-sum [E, C, d] buffer that GSPMD resolves with a full
    all-reduce (~30 TB/device/step measured on deepseek train_4k).
    """
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.experts_per_token
    n = s * k                                              # assignments/row
    cap = int(-(-s * k // e) * cfg.capacity_factor)
    cap = max(4, min(cap, s))

    # ---- 1. route ----------------------------------------------------
    gates, experts = route(params["router"], cfg, x)       # [B, S, k]

    # ---- 2. positions within (row, expert) via per-row sort ranking ----
    flat_e = experts.reshape(b, n)                         # [B, S*k]
    order = jnp.argsort(flat_e, axis=1, stable=True)       # token priority
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    counts = jnp.zeros((b, e), jnp.int32).at[rows, flat_e].add(1)
    starts = jnp.cumsum(counts, axis=1) - counts           # [B, E]
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    ranks_sorted = (jnp.arange(n, dtype=jnp.int32)[None]
                    - jnp.take_along_axis(starts, sorted_e, axis=1))
    flat_pos = jnp.zeros((b, n), jnp.int32).at[rows, order].set(ranks_sorted)
    keep = flat_pos < cap

    # ---- 3. dispatch: [E, B, C, d] (row-local scatter) -----------------
    token_idx = jnp.arange(n, dtype=jnp.int32) // k        # [n], within row
    safe_e = jnp.where(keep, flat_e, 0)
    safe_p = jnp.where(keep, flat_pos, cap - 1)
    from repro.parallel.sharding import shard_act
    contrib = jnp.where(keep[..., None], x[:, jnp.arange(n) // k, :], 0
                        ).astype(x.dtype)                  # [B, n, d]
    # pin batch sharding on the dispatch/combine operands: their autodiff
    # cotangents otherwise come out replicated and partial-summed — a
    # 120 GiB f32 all-reduce per MoE layer on deepseek train_4k
    contrib = shard_act(contrib, ("batch", None, None))

    # vmapped row-local scatter/gather: lowers to gather/scatter WITH
    # batch dims, which GSPMD partitions along the data axis (a flat
    # fancy-index over [E, B, C, d] gets replicated instead)
    def disp_row(c_r, se_r, sp_r):
        return jnp.zeros((e, cap, d), x.dtype).at[se_r, sp_r].add(
            c_r, mode="drop")

    xin = jax.vmap(disp_row)(contrib, safe_e, safe_p)      # [B, E, C, d]
    xin = shard_act(xin, ("batch", "experts", None, None))

    # ---- 4. batched expert FFN (EP over experts, DP over rows) ---------
    f = cfg.moe_d_ff or cfg.d_ff
    wg = _expert_bank(params["gate"], (e, f, d))
    wu = _expert_bank(params["up"], (e, f, d))
    wd = _expert_bank(params["down"], (e, d, f))
    xin_c = xin.astype(wg.dtype)
    g = jnp.einsum("becd,efd->becf", xin_c, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("becd,efd->becf", xin_c, wu,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    yout = jnp.einsum("becf,edf->becd", h.astype(wd.dtype), wd,
                      preferred_element_type=jnp.float32)  # [B, E, C, d]
    # NOTE: casting the combine path to bf16 does NOT shrink the EP
    # cross-shard all-reduces — XLA promotes the reduction back to f32
    # ("add.clone_promoted"); the identified next lever is a shard_map
    # all-to-all dispatch (est. ~16x on this term), see EXPERIMENTS §Perf.
    yout = shard_act(yout, ("batch", "experts", None, None))

    # ---- 5. combine (row-local gather) ---------------------------------
    vals = jax.vmap(lambda yo_r, se_r, sp_r: yo_r[se_r, sp_r])(
        yout, safe_e, safe_p)                              # [B, n, d]
    vals = shard_act(vals, ("batch", None, None))
    vals = jnp.where(keep[..., None], vals, 0.0) * \
        gates.reshape(b, n)[..., None].astype(x.dtype)
    y = jax.vmap(lambda v_r: jnp.zeros((s, d), jnp.float32)
                 .at[token_idx].add(v_r.astype(jnp.float32)))(vals)
    y = shard_act(y, ("batch", None, None))

    if "shared_gate" in params:
        y = y + _shared(params, x, backend)

    return y.astype(x.dtype)


def router_aux_loss(params, x, cfg):
    """Load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = jnp.einsum("td,ed->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    frac = jnp.mean(jax.nn.one_hot(experts, cfg.n_experts).sum(1), axis=0)
    return cfg.n_experts * jnp.sum(frac * probs.mean(0))

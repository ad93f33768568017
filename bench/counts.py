"""Operations and bytes the algorithm needs, computed from shapes.

Nothing here reads the program's own estimates (``roofline.analysis.
serve_analytic_bytes`` charges q/8 bytes per 3-bit weight and leaves out
the scale and offset rows, 25% low at q=3, g=128).  A quantized linear
is described by the bundle that is actually stored: its logical
``[n, k]`` and the bytes of every array it holds (planes, scale rows,
offset rows), read from the live arrays' shapes and dtypes, so a change
of the stored format changes the count.

A linear ``y[m, n] = x[m, k] @ W[n, k]^T`` costs ``2 m n k`` operations
whichever backend computes it, and moves the stored weight bytes once
plus the activations in and out.

The architecture is read from what the configuration file states
(:func:`shape_of`): a file with ``kv_lora_rank`` has latent attention
(MLA), any other grouped-query attention (MHA and MQA are its edges).  A
bundle with an expert axis is a routed expert role: each token runs
``experts_per_token`` of its matrices, and a step reads each (layer,
expert) matrix it touched once.  Shared experts are dense roles.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Linear:
    """One linear role of the stack (e.g. ``mixer/q``), per layer."""
    name: str
    n: int                  # output features
    k: int                  # input features
    stored_bytes: int       # bytes of one layer's (one expert's) weight
    layers: int             # how many layers hold this role
    experts: int = 1        # routed experts per layer; 1 for a dense role


@dataclasses.dataclass(frozen=True)
class Head:
    """The LM head as stored."""
    rows: int               # rows (padded vocabulary)
    stored_bytes: int
    bundle: bool            # a quantized bundle: runs through the linear kernels


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the step and attention counts need of a model."""
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_rows: int          # rows of the LM head as stored (padded vocab)
    head_bytes: int          # stored bytes of the LM head
    kv_entry_bytes: int      # KV bytes per (token, layer)
    block_size: int
    act_bytes: int = 2       # activations between layers (bf16)
    head_gemm: bool = False  # the head is a quantized bundle (linear kernels)
    kv_lora_rank: int = 0    # > 0: latent attention (MLA)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    experts_per_token: int = 0


def kv_entry_bytes(c: dict) -> int:
    """KV bytes per (token, layer), bf16: MLA's latent and rotary key;
    else K and V of every KV head."""
    if c.get("kv_lora_rank"):
        return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * 2
    nkv = c.get("num_key_value_heads", c["num_attention_heads"])
    return 2 * nkv * c["head_dim"] * 2


def shape_of(c: dict, head: Head) -> Shape:
    """The :class:`Shape` of a configuration file and its stored LM
    head."""
    mla = bool(c.get("kv_lora_rank"))
    return Shape(
        layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c.get("num_key_value_heads", c["num_attention_heads"]),
        head_dim=c.get("head_dim", 0) if mla else c["head_dim"],
        vocab_rows=head.rows, head_bytes=head.stored_bytes,
        kv_entry_bytes=kv_entry_bytes(c),
        block_size=c["engine"]["block_size"], head_gemm=head.bundle,
        kv_lora_rank=c.get("kv_lora_rank") or 0,
        qk_nope_head_dim=c.get("qk_nope_head_dim") or 0,
        qk_rope_head_dim=c.get("qk_rope_head_dim") or 0,
        v_head_dim=c.get("v_head_dim") or 0,
        experts_per_token=c.get("num_experts_per_tok") or 0)


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, o: "Work") -> "Work":
        self.flops += o.flops
        self.bytes += o.bytes
        return self


def gemm(m: int, lin: Linear, act_bytes: int = 2, out_bytes: int = 2) -> Work:
    """One call of one dense linear on ``m`` rows."""
    return Work(2.0 * m * lin.n * lin.k,
                lin.stored_bytes + m * lin.k * act_bytes
                + m * lin.n * out_bytes)


def split_routed(
        linears: Sequence[Linear]) -> Tuple[List[Linear], List[Linear]]:
    """The dense roles (shared experts among them) and the routed expert
    roles."""
    return ([l for l in linears if l.experts == 1],
            [l for l in linears if l.experts > 1])


def touched_per_layer(routed: Sequence[Linear], touched: int) -> float:
    """Experts a step reads per MoE layer: its ``touched`` distinct
    (layer, expert) pairs over the layers that hold the routed roles."""
    groups = {l.name.rsplit("/", 1)[0]: l.layers for l in routed}
    return touched / sum(groups.values())


def routed_gemm(m: int, lin: Linear, top_k: int, per_layer: float,
                act_bytes: int = 2, out_bytes: int = 2) -> Work:
    """One layer's call of a routed expert role on ``m`` token rows: each
    row runs ``top_k`` experts, and the ``per_layer`` touched experts'
    matrices are read once."""
    a = m * top_k
    return Work(2.0 * a * lin.n * lin.k,
                per_layer * lin.stored_bytes + a * lin.k * act_bytes
                + a * lin.n * out_bytes)


def lm_head(m: int, s: Shape) -> Work:
    """The LM head on ``m`` rows (f32 logits out)."""
    return Work(2.0 * m * s.vocab_rows * s.d_model,
                s.head_bytes + m * s.d_model * s.act_bytes
                + m * s.vocab_rows * 4)


def blocks_for(tokens: int, block_size: int) -> int:
    return -(-max(tokens, 0) // block_size)


def decode_attention(kv_lens: Iterable[int], s: Shape) -> Work:
    """One decode step of paged attention over all layers.

    ``kv_lens``: for each live row, the tokens it attends to (its cached
    context plus the token being decoded).  Bytes are the live blocks
    read through the block table, plus q in and the output.  MLA as the
    fused kernel computes it, absorbed: scores over the latent and the
    rotary key (``r + p``), the output over the latent (``r``)."""
    kv_lens = list(kv_lens)
    ctx = sum(kv_lens)
    blocks = sum(blocks_for(n, s.block_size) for n in kv_lens)
    kv = blocks * s.block_size * s.kv_entry_bytes
    if s.kv_lora_rank:
        r, p = s.kv_lora_rank, s.qk_rope_head_dim
        qo = len(kv_lens) * s.n_heads * (2 * r + p) * s.act_bytes
        per_layer = Work(2.0 * s.n_heads * ctx * (2 * r + p), kv + qo)
    else:
        qo = len(kv_lens) * s.n_heads * s.head_dim * s.act_bytes * 2
        per_layer = Work(4.0 * ctx * s.n_heads * s.head_dim, kv + qo)
    return Work(per_layer.flops * s.layers, per_layer.bytes * s.layers)


def prefill_attention(start: int, length: int, s: Shape) -> Work:
    """One chunk of ``length`` real tokens at ``start``, causal, over all
    layers: token ``i`` attends to ``start + i + 1`` positions.  MLA at
    its widths: q and k ``nope + rope``, v ``v_head``, over the latent
    read once."""
    ctx = length * start + length * (length + 1) // 2
    blocks = blocks_for(start + length, s.block_size)
    kv = blocks * s.block_size * s.kv_entry_bytes
    if s.kv_lora_rank:
        qk, v = s.qk_nope_head_dim + s.qk_rope_head_dim, s.v_head_dim
        qo = length * s.n_heads * (qk + v) * s.act_bytes
        per_layer = Work(2.0 * ctx * s.n_heads * (qk + v), kv + qo)
    else:
        qo = length * s.n_heads * s.head_dim * s.act_bytes * 2
        per_layer = Work(4.0 * ctx * s.n_heads * s.head_dim, kv + qo)
    return Work(per_layer.flops * s.layers, per_layer.bytes * s.layers)


def weight_bytes(linears: Sequence[Linear]) -> int:
    """Every stored byte of the linears, every expert's included."""
    return sum(l.stored_bytes * l.layers * l.experts for l in linears)


@dataclasses.dataclass
class Step:
    """One dispatched device step, as the host saw it.

    ``kind``: "decode" or "prefill".  ``ctx``: for a decode step, each
    live row's attended length; for a prefill chunk, ``[start, length]``.
    ``bucket``: the rows a prefill chunk was padded to.  ``counters``:
    what the program counted on the step (``experts_touched``: distinct
    (layer, expert) pairs its routed rows ran).
    """
    kind: str
    ctx: List[int]
    bucket: int = 0
    counters: dict = dataclasses.field(default_factory=dict)


def step_model_work(step: Step, linears: Sequence[Linear],
                    s: Shape) -> Optional[Work]:
    """Useful operations and the least bytes of one step: stored weights
    read once (a routed role's only for the experts the step touched),
    the LM head where logits are produced, live KV read.  ``None`` for a
    step of a model with routed experts that carries no
    ``experts_touched``."""
    dense, routed = split_routed(linears)
    w = weight_bytes(dense)
    if step.kind == "decode":
        rows = len(step.ctx)
        flops = sum(2.0 * l.n * l.k * l.layers for l in dense) * rows
        att = decode_attention(step.ctx, s)
        head = lm_head(rows, s)
    else:
        start, rows = step.ctx
        flops = sum(2.0 * l.n * l.k * l.layers for l in dense) * rows
        att = prefill_attention(start, rows, s)
        head = lm_head(1, s)
    if routed:
        touched = step.counters.get("experts_touched")
        if touched is None:
            return None
        per = touched_per_layer(routed, touched)
        flops += sum(2.0 * l.n * l.k * l.layers for l in routed) \
            * rows * s.experts_per_token
        w += sum(per * l.layers * l.stored_bytes for l in routed)
    return Work(flops + att.flops + head.flops, w + att.bytes + head.bytes)


def total_work(steps: Iterable[Step], linears: Sequence[Linear],
               s: Shape) -> Optional[Work]:
    """:func:`step_model_work` summed over ``steps``; ``None`` where any
    step's is."""
    total = Work()
    for step in steps:
        w = step_model_work(step, linears, s)
        if w is None:
            return None
        total += w
    return total

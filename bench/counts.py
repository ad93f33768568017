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
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Sequence


@dataclasses.dataclass(frozen=True)
class Linear:
    """One linear role of the stack (e.g. ``mixer/q``), per layer."""
    name: str
    n: int                  # output features
    k: int                  # input features
    stored_bytes: int       # bytes of one layer's stored weight
    layers: int             # how many layers hold this role


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


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, o: "Work") -> "Work":
        self.flops += o.flops
        self.bytes += o.bytes
        return self


def gemm(m: int, lin: Linear, act_bytes: int = 2, out_bytes: int = 2) -> Work:
    """One call of one linear on ``m`` rows."""
    return Work(2.0 * m * lin.n * lin.k,
                lin.stored_bytes + m * lin.k * act_bytes
                + m * lin.n * out_bytes)


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
    read through the block table, plus q in and the output."""
    kv_lens = list(kv_lens)
    ctx = sum(kv_lens)
    blocks = sum(blocks_for(n, s.block_size) for n in kv_lens)
    qo = len(kv_lens) * s.n_heads * s.head_dim * s.act_bytes * 2
    per_layer = Work(4.0 * ctx * s.n_heads * s.head_dim,
                     blocks * s.block_size * s.kv_entry_bytes + qo)
    return Work(per_layer.flops * s.layers, per_layer.bytes * s.layers)


def prefill_attention(start: int, length: int, s: Shape) -> Work:
    """One chunk of ``length`` real tokens at ``start``, causal, over all
    layers: token ``i`` attends to ``start + i + 1`` positions."""
    ctx = length * start + length * (length + 1) // 2
    blocks = blocks_for(start + length, s.block_size)
    qo = length * s.n_heads * s.head_dim * s.act_bytes * 2
    per_layer = Work(4.0 * ctx * s.n_heads * s.head_dim,
                     blocks * s.block_size * s.kv_entry_bytes + qo)
    return Work(per_layer.flops * s.layers, per_layer.bytes * s.layers)


def weight_bytes(linears: Sequence[Linear]) -> int:
    return sum(l.stored_bytes * l.layers for l in linears)


@dataclasses.dataclass
class Step:
    """One dispatched device step, as the host saw it.

    ``kind``: "decode" or "prefill".  ``ctx``: for a decode step, each
    live row's attended length; for a prefill chunk, ``[start, length]``.
    ``bucket``: the rows a prefill chunk was padded to.
    """
    kind: str
    ctx: List[int]
    bucket: int = 0


def step_model_work(step: Step, linears: Sequence[Linear], s: Shape) -> Work:
    """Useful operations and the least bytes of one step: stored weights
    read once, the LM head where logits are produced, live KV read."""
    w = weight_bytes(linears)
    if step.kind == "decode":
        rows = len(step.ctx)
        flops = sum(2.0 * l.n * l.k * l.layers for l in linears) * rows
        att = decode_attention(step.ctx, s)
        head = lm_head(rows, s)
    else:
        start, length = step.ctx
        flops = sum(2.0 * l.n * l.k * l.layers for l in linears) * length
        att = prefill_attention(start, length, s)
        head = lm_head(1, s)
    return Work(flops + att.flops + head.flops, w + att.bytes + head.bytes)

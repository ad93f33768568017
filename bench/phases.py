"""The engine's request phase spans, joined to the window's requests.

The engine writes three spans on each request's lane that tile its
wait for the first token: ``queue`` (submit to first admission),
``prefill_wait`` (to the dispatch of its first prefill chunk) and
``prefill`` (to the emission of its first token).  The four readers of
the parts of time to first token take the same requests: those due in
the window that were answered and whose three spans the trace holds.
So the submit lag (``queue`` start minus due) and the three means add
up to the mean time to first token of those requests.  A program that
writes no such spans gives no requests, and the readers return
``None``.
"""
from __future__ import annotations

from bench import stats

PHASES = ("queue", "prefill_wait", "prefill")


def answered(ctx) -> list:
    """``(rec, {phase: event})`` for every answered window request whose
    three phase spans the trace holds."""
    by_uid = {}
    for name in PHASES:
        for e in ctx.obs(name):
            if e["ph"] == "X":
                by_uid.setdefault(e["args"]["uid"], {})[name] = e
    out = []
    for r in ctx.recs:
        spans = by_uid.get(r.req.uid) if r.req is not None else None
        if r.times and spans is not None and len(spans) == len(PHASES):
            out.append((r, spans))
    return out


def mean_ms(ctx, phase: str):
    """Mean duration of ``phase`` over :func:`answered`, in ms."""
    rows = answered(ctx)
    if not rows:
        return None
    return stats.mean(spans[phase]["dur"] for _, spans in rows) * 1e-3

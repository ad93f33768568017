"""The profiler-trace reduction (bench/devtrace.py, bench/layers.py).

A synthetic trace checks the arithmetic by hand; a small slice of a
trace recorded on the chip (``data/trace_small.json.gz``, from
``bench/tools/trace_look.py``) checks that the real lines and names are
read: program executions by jitted name, kernels by their names.  A
synthetic tracer checks how engine events become steps.
"""
import os
import re

import pytest

from bench import devtrace, layers
from bench.devtrace import Ev, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_small.json.gz")


def _synthetic():
    ops = [Ev("%fusion.1 = f32[8] fusion(f32[8] %lut_gemm_tiled.2)", 0.0, 1.0),
           Ev("%lut_gemm_tiled.2 = f32[8,32] custom-call(u8[3,8,4] %p)",
              0.5, 1.0),
           Ev("%paged_attention_tiled.3 = f32[8] custom-call()", 3.0, 1.0)]
    mods = [Ev("jit_decode_and_sample(7)", 0.0, 1.5),
            Ev("jit_prefill_chunk(9)", 3.0, 1.0)]
    host = {"python": [Ev(devtrace.ANCHOR, 0.0, 0.0001),
                       Ev("tick", 1.0, 3.0), Ev("device_sync", 2.0, 0.5)]}
    return Trace({"/device:TPU:0": {devtrace.OPS: ops,
                                    devtrace.MODULES: mods}}, host)


def test_busy_and_idle_by_hand():
    t = _synthetic()
    # ops cover [0, 1.5] and [3, 4]
    assert t.busy_s(0.0, 5.0) == pytest.approx(2.5)
    assert t.busy_s(1.0, 3.5) == pytest.approx(1.0)
    assert t.idle_gaps(0.0, 5.0) == [(1.5, 3.0), (4.0, 5.0)]
    assert t.span_at(2.1, layers.HOST_SPANS) == "device_sync"
    assert t.span_at(1.6, layers.HOST_SPANS) == "tick"
    assert t.span_at(4.5, layers.HOST_SPANS) is None
    assert t.anchor() == 0.0


def test_programs_and_kernels_by_name():
    t = _synthetic()
    assert [e.name for e in t.modules(layers.PROGRAMS["decode"])] == \
        ["jit_decode_and_sample(7)"]
    # matched on the op's own name, never on an operand's
    gemm = [e.op for e in t.ops() if layers.KERNELS["gemm"].match(e.op)]
    attn = [e.op for e in t.ops() if layers.KERNELS["attn"].match(e.op)]
    assert gemm == ["lut_gemm_tiled.2"]
    assert attn == ["paged_attention_tiled.3"]


# the kernel families as the first benchmark matched them, name by name
OLD_KERNELS = {
    "gemm": re.compile(r"(lut_gemm|bcq_matmul|ternary_matmul)_tiled\b"),
    "attn": re.compile(r"(paged_attention|paged_prefill)_tiled\b"),
}
# the top device ops of both cells' traced runs on the chip, and
# the kernels the name rule is for
SEEN = ["bcq_matmul_tiled.59", "constant_dynamic-slice_fusion.42",
        "constant_dynamic-update-slice_fusion.8", "sort.5",
        "paged_prefill_tiled.9", "paged_attention_tiled.3",
        "lut_gemm_tiled.2", "ternary_matmul_tiled.4", "fusion.1",
        "copy.12", "while.3", "reshape.562"]


def _family(op, kernels):
    return [f for f, pat in kernels.items() if pat.match(op)]


def test_kernel_name_rule_keeps_every_op_it_knew(chip):
    for op in SEEN + [e.op for e in chip.ops()]:
        assert _family(op, layers.KERNELS) == _family(op, OLD_KERNELS), op


@pytest.mark.parametrize("op,family", [
    ("paged_attention_mla_tiled.7", "attn"),
    ("bcq_matmul_grouped_tiled.3", "gemm"),
    ("grouped_bcq_matmul_tiled.2", "gemm"),
    ("lut_gemm_experts_tiled.5", "gemm")])
def test_kernel_name_rule_takes_new_kernels(op, family):
    assert _family(op, layers.KERNELS) == [family]


def _tracer_context(events):
    import time
    from repro import obs
    tr = obs.Tracer(clock=lambda: 0.0)
    for name, ph, ts, args in events:
        tr.emit(name, ph, ts, "engine/test", dur=1.0 if ph == "X" else None,
                args=args)
    ctx = layers.Context(engine={}, linears=[], shape=None,
                         window=(0.0, 1.0), trace_window=(0.0, 1.0),
                         device=Trace({}, {}), tracer=tr, recs=[],
                         kind="TPU v5 lite")
    zero = ctx.obs_time({"ts": 0.0})
    assert zero == pytest.approx(time.perf_counter(), abs=60.0)
    return ctx, zero


def test_step_counters_join_their_step():
    ctx, zero = _tracer_context([
        ("admit", "i", 0.0, {"uid": 1, "prefill_target": 10}),
        ("step_counters", "i", 5.0, {"step": 3, "experts_touched": 99}),
        ("prefill_chunk", "X", 10.0, {"start": 0, "length": 10,
                                      "bucket": 16, "step": 2}),
        ("decode_dispatch", "X", 20.0, {"uids": [1], "step": 3}),
        ("decode_dispatch", "X", 30.0, {"uids": [1], "step": 4}),
        ("decode_dispatch", "X", 40.0, {"uids": [1]}),
        ("step_counters", "i", 50.0, {"step": 3, "experts_touched": 40,
                                      "tick": 7}),
        ("step_counters", "i", 60.0, {"step": 2, "experts_touched": 300}),
        ("step_counters", "i", 70.0, {"step": 8, "experts_touched": 1}),
    ])
    steps = ctx.steps(zero - 1.0, zero + 1.0)
    assert [(s.kind, s.ctx) for s in steps] == [
        ("prefill", [0, 10]), ("decode", [11]), ("decode", [12]),
        ("decode", [13])]
    # only later instants join; the tracer's tick is not a counter; a
    # step with no instant, or no id, has no counters
    assert [s.counters for s in steps] == [
        {"experts_touched": 300}, {"experts_touched": 40}, {}, {}]
    # steps dispatched outside the asked range are left out
    assert ctx.steps(zero + 25e-6, zero + 1.0) == steps[2:]


def test_json_round_trip(tmp_path):
    t = _synthetic()
    p = str(tmp_path / "t.json.gz")
    devtrace.save(t, p)
    back = devtrace.read_saved(p)
    assert back.to_json() == t.to_json()


@pytest.fixture(scope="module")
def chip():
    """About 0.9 s of OPT-6.7B serving the chat mix on one TPU v5e:
    the tail of a prefill of bucket 512, a decode step, a prefill of
    bucket 128, a decode step, and the head of the next prefill."""
    return devtrace.read_saved(DATA)


def _inside(t, m, family):
    return [o for o in t.ops() if m.start <= o.start < m.end
            and layers.KERNELS[family].match(o.op)]


def test_chip_programs(chip):
    dec = sorted(chip.modules(layers.PROGRAMS["decode"]),
                 key=lambda e: e.start)
    pre = sorted(chip.modules(layers.PROGRAMS["prefill"]),
                 key=lambda e: e.start)
    assert [round(e.dur, 9) for e in dec] == [0.195689761, 0.195695128]
    assert [round(e.dur, 9) for e in pre] == [1.640411608, 0.444398002,
                                              1.693015201]
    assert chip.anchor() == pytest.approx(0.043873365)


def test_chip_kernels_by_hand(chip):
    dec = sorted(chip.modules(layers.PROGRAMS["decode"]),
                 key=lambda e: e.start)[0]
    # 6 linears x 32 layers, one paged-attention call per layer
    gemm, attn = _inside(chip, dec, "gemm"), _inside(chip, dec, "attn")
    assert len(gemm) == 192 and len(attn) == 32
    assert sum(o.dur for o in gemm) == pytest.approx(0.063675854, abs=1e-9)
    assert sum(o.dur for o in attn) == pytest.approx(0.103782936, abs=1e-9)
    pre = sorted(chip.modules(layers.PROGRAMS["prefill"]),
                 key=lambda e: e.start)[1]
    assert len(_inside(chip, pre, "gemm")) == 192
    assert sum(o.dur for o in _inside(chip, pre, "attn")) == \
        pytest.approx(0.158371529, abs=1e-9)


def test_chip_busy_covers_back_to_back_programs(chip):
    dec = sorted(chip.modules(layers.PROGRAMS["decode"]),
                 key=lambda e: e.start)
    lo, hi = dec[0].start, dec[1].end
    mods = [m for m in chip.modules() if m.start >= lo and m.end <= hi]
    assert chip.busy_s(lo, hi) == pytest.approx(
        sum(m.dur for m in mods), rel=1e-3)
    assert 0 < chip.busy_s(lo, hi) <= hi - lo
    assert layers.HOST_SPANS[0] in {e.name for e in chip.host_events()}

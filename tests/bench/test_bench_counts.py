"""Operations and bytes, against counts made by hand.

Every linear of both configurations at their published widths (read
from the program's format by ``jax.eval_shape``: no weight is drawn),
and one paged-attention call.  A 3-bit bundle at group size 128 stores
3/8 byte of sign planes per weight, 3 float32 scales and 1 float32
offset per 128 weights: 0.375 + 0.125 = 0.5 byte per weight.
"""
import json
import os

import pytest

from bench import counts, harness, weights
from bench.peaks import PEAKS, bound_s, peaks

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")


def _linears(name):
    from repro.models import Model
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        c = json.load(f)
    cfg = harness.model_config(c)
    abstract = weights.abstract_quantized(Model(cfg), cfg.quant)
    return {l.name.split("/", 3)[-1]: l for l in weights.linears(abstract)}


# (n, k, stored bytes of one layer) by hand
OPT = {"mixer/q": (4096, 4096, 8_388_608), "mixer/k": (4096, 4096, 8_388_608),
       "mixer/v": (4096, 4096, 8_388_608), "mixer/o": (4096, 4096, 8_388_608),
       "mlp/up": (16384, 4096, 33_554_432),
       "mlp/down": (4096, 16384, 33_554_432)}
PHI = {"mixer/q": (3072, 3072, 4_718_592), "mixer/k": (1024, 3072, 1_572_864),
       "mixer/v": (1024, 3072, 1_572_864), "mixer/o": (3072, 3072, 4_718_592),
       "mlp/gate": (8192, 3072, 12_582_912),
       "mlp/up": (8192, 3072, 12_582_912),
       "mlp/down": (3072, 8192, 12_582_912)}


@pytest.mark.parametrize("name,want", [("opt-6.7b-bcq3", OPT),
                                       ("phi4-mini-bcq3", PHI)])
def test_every_linear_by_hand(name, want):
    got = _linears(name)
    assert set(got) == set(want)
    for key, lin in got.items():
        n, k, nbytes = want[key]
        assert (lin.n, lin.k, lin.stored_bytes, lin.layers) == \
            (n, k, nbytes, 32), key
        assert lin.stored_bytes == n * k // 2          # 0.5 B per weight
        g = counts.gemm(32, lin)
        assert g.flops == 2 * 32 * n * k
        assert g.bytes == nbytes + 32 * k * 2 + 32 * n * 2


def test_decode_step_of_opt_by_hand():
    lins = list(_linears("opt-6.7b-bcq3").values())
    w = counts.Work()
    for lin in lins:
        g = counts.gemm(32, lin)
        w += counts.Work(g.flops * lin.layers, g.bytes * lin.layers)
    params = 32 * (4 * 4096 * 4096 + 2 * 4096 * 16384)       # 6.44e9
    assert w.flops == 2 * 32 * params
    assert counts.weight_bytes(lins) == params // 2
    # activations in and out of each call, bf16, 32 rows, 32 layers
    act = 32 * 32 * 2 * (4 * (4096 + 4096) + 2 * (4096 + 16384))
    assert w.bytes == params // 2 + act
    # bytes bound: (3.22 GB + 0.15 GB) at 819 GB/s is 4.12 ms
    t = bound_s(w.flops, w.bytes, peaks("TPU v5 lite"))
    assert t == (params // 2 + act) / 819e9


def test_paged_attention_call_by_hand():
    s = counts.Shape(layers=32, d_model=4096, n_heads=32, n_kv_heads=32,
                     head_dim=128, vocab_rows=50432,
                     head_bytes=50432 * 4096 * 2,
                     kv_entry_bytes=2 * 32 * 128 * 2, block_size=16)
    # two rows attending to 17 and 33 positions: 2 + 3 live blocks
    w = counts.decode_attention([17, 33], s)
    per_layer_bytes = 5 * 16 * 16384 + 2 * 32 * 128 * 2 * 2
    assert w.bytes == 32 * per_layer_bytes == 32 * 1_343_488
    assert w.flops == 32 * 4 * 50 * 32 * 128
    # a prefill chunk of 3 tokens at position 16: 17 + 18 + 19 positions
    p = counts.prefill_attention(16, 3, s)
    assert p.flops == 32 * 4 * (17 + 18 + 19) * 32 * 128
    assert p.bytes == 32 * (2 * 16 * 16384 + 3 * 32 * 128 * 2 * 2)


def test_step_model_work_counts_the_head_where_logits_come():
    lin = counts.Linear("x", 8, 4, 16, 2)
    s = counts.Shape(layers=2, d_model=4, n_heads=1, n_kv_heads=1,
                     head_dim=4, vocab_rows=10, head_bytes=80,
                     kv_entry_bytes=16, block_size=4)
    dec = counts.step_model_work(counts.Step("decode", [3, 5]), [lin], s)
    assert dec.flops == 2 * 8 * 4 * 2 * 2 + 4 * 8 * 4 * 2 \
        + 2 * 2 * 10 * 4
    pre = counts.step_model_work(counts.Step("prefill", [0, 3], 4), [lin], s)
    assert pre.flops == 2 * 8 * 4 * 2 * 3 + 4 * 6 * 4 * 2 + 2 * 10 * 4


def test_peaks_table_has_its_source_and_refuses_unknown_kinds():
    assert PEAKS["TPU v5 lite"].bf16_flops == 197e12
    assert PEAKS["TPU v5 lite"].hbm_bytes_per_s == 819e9
    assert "Google Cloud" in PEAKS["TPU v5 lite"].source
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")

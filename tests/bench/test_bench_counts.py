"""Operations and bytes, against counts made by hand.

Every linear of both configurations at their published widths (read
from the program's format by ``jax.eval_shape``: no weight is drawn),
and one paged-attention call.  A 3-bit bundle at group size 128 stores
3/8 byte of sign planes per weight, 3 float32 scales and 1 float32
offset per 128 weights: 0.375 + 0.125 = 0.5 byte per weight.

Both cells' counts are pinned to what the dense-only counting of the
first benchmark gave on the same steps, and their drawn weights to its
draw.  DeepSeek-V2-Lite at its published widths (a test-local
configuration, ``eval_shape`` only) checks latent attention, routed and
shared experts and an untied 3-bit head by hand.
"""
import hashlib
import json
import os
import types

import numpy as np
import pytest

import jax

from bench import counts, harness, weights
from bench.layers import gemm_bound_s
from bench.peaks import PEAKS, bound_s, peaks

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _abstract(c):
    from repro.models import Model
    cfg = harness.model_config(c)
    return weights.abstract_quantized(Model(cfg), cfg.quant)


def _linears(name):
    abstract = _abstract(_config(name))
    return {l.name.split("/", 3)[-1]: l for l in weights.linears(abstract)}


def _ctx(c, abstract):
    return types.SimpleNamespace(
        linears=weights.linears(abstract),
        shape=counts.shape_of(c, weights.head(abstract)),
        peaks=peaks("TPU v5 lite"))


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


# What the first benchmark's dense-only counting (its ``harness.build``
# Shape, ``weights.linears``, ``layers.gemm_bound_s``) gave on these
# steps at the cells' published widths; the counts must not move.
DECODE = [17, 130, 259, 512, 1000]
PREFILL = [(0, 128, 128), (128, 300, 512), (500, 12, 32)]
PINNED = {
    "opt-6.7b-bcq3": {
        "kv_entry_bytes": 16384,
        "decode_work": (67495788544.0, 4669834240),
        "decode_attention": (1005584384.0, 1034420224),
        "prefill_work": [(1654009102336.0, 3768792064),
                         (3909687967744.0, 4018353152),
                         (158218584064.0, 3909301248)],
        "prefill_attention": [(4328521728.0, 134217728),
                              (43804262400.0, 383778816),
                              (3186622464.0, 274726912)],
        "gemm_bound_s": {1: 0.0039388816410256405, 5: 0.003961927267399267,
                         32: 0.004117485245421245},
    },
    "phi4-mini-bcq3": {
        "kv_entry_bytes": 4096,
        "decode_work": (39116341248.0, 3104542720),
        "decode_attention": (754188288.0, 259915776),
        "prefill_work": [(829110091776.0, 2908508160),
                         (1966818459648.0, 3015987200),
                         (80929357824.0, 2913226752)],
        "prefill_attention": [(3246391296.0, 67108864),
                              (32853196800.0, 174587904),
                              (2389966848.0, 71827456)],
        "gemm_bound_s": {1: 0.0019708011526251524,
                         5: 0.0019877652942612944,
                         32: 0.0021022732503052505},
    },
}


def _pair(w):
    return (w.flops, w.bytes)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_both_cells_count_what_they_counted(name):
    want = PINNED[name]
    c = _config(name)
    ctx = _ctx(c, _abstract(c))
    s, lins = ctx.shape, ctx.linears
    assert counts.kv_entry_bytes(c) == s.kv_entry_bytes == \
        want["kv_entry_bytes"]
    assert not s.head_gemm and all(l.experts == 1 for l in lins)
    assert _pair(counts.step_model_work(counts.Step("decode", DECODE),
                                        lins, s)) == want["decode_work"]
    assert _pair(counts.decode_attention(DECODE, s)) == \
        want["decode_attention"]
    for (start, length, bucket), work, att in zip(
            PREFILL, want["prefill_work"], want["prefill_attention"]):
        step = counts.Step("prefill", [start, length], bucket)
        assert _pair(counts.step_model_work(step, lins, s)) == work
        assert _pair(counts.prefill_attention(start, length, s)) == att
    for m, t in want["gemm_bound_s"].items():
        # a tied bf16 head runs outside the linear kernels: head rows
        # change nothing
        assert gemm_bound_s(m, ctx) == gemm_bound_s(m, ctx, m) == t


# sha256 of a tiny tree of each cell drawn from seed 2**33 + 5 by the
# first benchmark's rules (norm gains found by the leaf name ``scale``)
DRAWN = {
    ("opt-6.7b-bcq3", False):
        "01630b7abb156da7eaa1387264863a3f9307c5c40fb54f769abe2625e79650ed",
    ("opt-6.7b-bcq3", True):
        "85970e02446a58380a858913630a00f9c7eefb51efd372b800f2967668b26e5e",
    ("phi4-mini-bcq3", False):
        "ae7a9ed0175a87ef19594f15d7f3d5484c47a4650085c35d3cff57edf718d0df",
    ("phi4-mini-bcq3", True):
        "f1622dce63d45951e15103e64065d7573c472e03ab680420f9c9da25e669f9fb",
}


@pytest.mark.parametrize("name,scan", sorted(DRAWN))
def test_drawn_leaves_are_unchanged(name, scan):
    from conftest import tiny_config
    from repro.models import Model
    c = tiny_config(name)
    c["program"]["scan_layers"] = scan
    cfg = harness.model_config(c)
    params = weights.draw(Model(cfg), cfg.quant, 2**33 + 5)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode() + str(a.dtype).encode()
                 + a.tobytes())
    assert h.hexdigest() == DRAWN[(name, scan)]


def test_every_norm_gain_is_drawn_near_one():
    from repro.models import Model
    c = dict(DSV2_LITE, num_hidden_layers=3, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=256, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
             moe_intermediate_size=32)
    cfg = harness.model_config(c)
    model = Model(cfg)
    gains = weights.norm_gains(model)
    assert ("stack", "scan", "0", "mixer", "kv_a_norm") in gains
    assert ("stack", "prefix", "0", "ln2", "scale") in gains
    params = weights.draw(model, cfg.quant, 2**33 + 5)
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        mean = float(np.asarray(leaf, np.float32).mean())
        if weights._name(path) in gains:
            assert abs(mean - 1.0) < 0.1, path
        elif weights._name(path)[-1] == "router":
            assert abs(mean) < 0.01, path


# DeepSeek-V2-Lite's published config.json, the keys the counts read
DSV2_LITE = {
    "num_hidden_layers": 27, "hidden_size": 2048,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "intermediate_size": 10944, "vocab_size": 102400,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 64,
    "n_shared_experts": 2, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1408, "first_k_dense_replace": 1,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "program": {"arch": "deepseek_v2_236b", "max_seq_len": 2048},
    "quant": {"format": "bcq", "bits": 3, "group_size": 128,
              "backend": "auto"},
    "engine": {"block_size": 16},
}


@pytest.fixture(scope="module")
def dsv2():
    abstract = _abstract(DSV2_LITE)
    return abstract, _ctx(DSV2_LITE, abstract)


def test_deepseek_v2_lite_roles_and_head_by_hand(dsv2):
    abstract, ctx = dsv2
    lins = {l.name: l for l in ctx.linears}
    for role in ("gate", "up", "down"):
        lin = lins[f"stack/scan/0/mlp/{role}"]
        assert (lin.experts, lin.layers, lin.stored_bytes) == \
            (64, 26, 1_441_792)                     # 1408 x 2048 / 2
        shared = lins[f"stack/scan/0/mlp/shared_{role}"]
        assert (shared.experts, shared.layers, shared.stored_bytes) == \
            (1, 26, 2_883_584)                      # 2816 x 2048 / 2
        assert lins[f"stack/prefix/0/mlp/{role}"].experts == 1
    # the untied 3-bit head once, as the head, and no linear under embed
    s = ctx.shape
    assert (s.head_gemm, s.vocab_rows, s.head_bytes) == \
        (True, 102_400, 104_857_600)                # 102400 x 2048 / 2
    assert not [n for n in lins if n.startswith("embed")]
    others = sum(weights._nbytes(a) for a in jax.tree_util.tree_leaves(
        abstract, is_leaf=weights.is_bundle) if not weights.is_bundle(a))
    assert weights.total_bytes(abstract) == \
        counts.weight_bytes(ctx.linears) + s.head_bytes + others
    # one latent entry and its rotary key, bf16
    assert s.kv_entry_bytes == counts.kv_entry_bytes(DSV2_LITE) == 1_152


def test_deepseek_v2_lite_attention_by_hand(dsv2):
    s = dsv2[1].shape
    # two rows attending to 17 and 33 positions: 2 + 3 live blocks;
    # absorbed: scores over r + p = 576, output over r = 512
    w = counts.decode_attention([17, 33], s)
    assert w.flops == 27 * 2 * 16 * 50 * (2 * 512 + 64)
    assert w.bytes == 27 * (5 * 16 * 1_152 + 2 * 16 * (576 + 512) * 2)
    # 3 tokens at position 16: 17 + 18 + 19 positions, q and k 192 wide,
    # v 128, the latent of 2 blocks read once
    p = counts.prefill_attention(16, 3, s)
    assert p.flops == 27 * 2 * 54 * 16 * (192 + 128)
    assert p.bytes == 27 * (2 * 16 * 1_152 + 3 * 16 * (192 + 128) * 2)


def test_deepseek_v2_lite_moe_step_by_hand(dsv2):
    _, ctx = dsv2
    s, lins = ctx.shape, ctx.linears
    # weights a token runs (n x k): attention 27 x (q 3072x2048 + kv_a
    # 576x2048 + kv_b 4096x512 + o 2048x2048); layer 0's dense MLP
    # 3 x 10944x2048; 26 layers of shared experts 3 x 2816x2048 and of 6
    # routed experts 3 x 1408x2048
    per_token = (27 * 13_762_560 + 3 * 10944 * 2048 + 26 * 3 * 2816 * 2048
                 + 26 * 6 * 3 * 1408 * 2048)
    assert per_token == 2_238_185_472
    att = counts.decode_attention([17, 33], s)

    def step(touched):
        return counts.step_model_work(counts.Step(
            "decode", [17, 33], counters={"experts_touched": touched}),
            lins, s)
    w = step(130)
    assert w.flops == 2 * 2 * per_token + att.flops + 2 * 2 * 102_400 * 2048
    # 104 more touched (layer, expert) pairs read 3 matrices each
    assert w.bytes - step(26).bytes == 104 * 3 * 1_441_792
    # no counter: no guess
    assert counts.step_model_work(counts.Step("decode", [17, 33]),
                                  lins, s) is None
    assert gemm_bound_s(5, ctx) is None
    # the routed roles' calls at 5 rows and 130 touched (5 a layer), each
    # bytes-bound: 5 experts' matrices plus 30 assignments in and out
    v5e = peaks("TPU v5 lite")
    dense = gemm_bound_s(5, types.SimpleNamespace(
        linears=counts.split_routed(lins)[0], shape=s, peaks=v5e))
    routed = 26 * 3 * (5 * 1_441_792 + 30 * (2048 + 1408) * 2) / 819e9
    assert gemm_bound_s(5, ctx, 0, 130) == pytest.approx(dense + routed,
                                                         rel=1e-12)
    # the head, through the linear kernels, at the rows with logits
    head = counts.lm_head(5, s)
    assert gemm_bound_s(5, ctx, 5, 130) - gemm_bound_s(5, ctx, 0, 130) == \
        pytest.approx(bound_s(head.flops, head.bytes, v5e), rel=1e-9)

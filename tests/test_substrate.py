"""Substrate tests: data pipeline, optimizer, checkpointing, trainer
fault-tolerance (failure recovery, straggler detection), serving engine.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.data.pipeline import SyntheticLM, MemmapTokens
from repro.models import Model
from repro.optim import adamw
from repro.serve.engine import ServeEngine, Request
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer, TrainConfig

CFG = get_reduced("opt_6_7b").replace(remat=False)


class TestDataPipeline:
    def test_deterministic_replay(self):
        p1 = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4, seed=3)
        p2 = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4, seed=3)
        np.testing.assert_array_equal(p1.batch_at(7)["tokens"],
                                      p2.batch_at(7)["tokens"])

    def test_shards_partition(self):
        full = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4, seed=3)
        s0 = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4, seed=3,
                         data_shard=0, data_shards=2)
        s1 = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4, seed=3,
                         data_shard=1, data_shards=2)
        assert s0.batch_at(0)["tokens"].shape == (2, 16)
        assert not np.array_equal(s0.batch_at(0)["tokens"],
                                  s1.batch_at(0)["tokens"])

    def test_has_learnable_structure(self):
        p = SyntheticLM(vocab_size=64, seq_len=512, global_batch=2, seed=0)
        toks = p.batch_at(0)["tokens"]
        # bigram structure: successor entropy < unconditional entropy
        succ = {}
        for row in toks:
            for a, b in zip(row[:-1], row[1:]):
                succ.setdefault(int(a), []).append(int(b))
        top_frac = np.mean([
            max(np.bincount(v).max(), 1) / len(v)
            for v in succ.values() if len(v) >= 5])
        assert top_frac > 0.2, top_frac   # way above 1/64 uniform

    def test_memmap_source(self, tmp_path):
        arr = np.arange(1024, dtype=np.int32)
        f = tmp_path / "toks.bin"
        arr.tofile(f)
        p = MemmapTokens(path=str(f), seq_len=32, global_batch=2)
        b = p.batch_at(0)["tokens"]
        assert b.shape == (2, 32)
        np.testing.assert_array_equal(b[0], np.arange(32))


class TestAdamW:
    def test_descends(self):
        w = {"w": jnp.array([2.0, -3.0])}
        opt = adamw.init_state(w)
        cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                                weight_decay=0.0)
        loss = lambda p: jnp.sum(p["w"] ** 2)
        for _ in range(50):
            g = jax.grad(loss)(w)
            w, opt, _ = adamw.apply_updates(w, g, opt, cfg)
        assert float(loss(w)) < 0.1

    def test_clipping(self):
        w = {"w": jnp.zeros(3)}
        opt = adamw.init_state(w)
        cfg = adamw.AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=1)
        g = {"w": jnp.full(3, 1e6)}
        _, _, m = adamw.apply_updates(w, g, opt, cfg)
        assert float(m["grad_norm"]) > 1e5   # reported pre-clip

    def test_compression_roundtrip_with_error_feedback(self):
        g = {"a": jnp.array(np.random.default_rng(0).normal(size=(64,)) * 1e-3,
                            jnp.float32)}
        q, s, resid = adamw.compress_grads(g)
        assert q["a"].dtype == jnp.int8
        deq = adamw.decompress_grads(q, s)
        err1 = float(jnp.abs(deq["a"] - g["a"]).max())
        # residual carries the error: feeding it back reduces bias
        q2, s2, _ = adamw.compress_grads(g, resid)
        deq2 = adamw.decompress_grads(q2, s2)
        two_step = (deq["a"] + deq2["a"]) / 2
        err2 = float(jnp.abs(two_step - g["a"]).max())
        assert err2 <= err1 * 1.01


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3),
                "b": [jnp.ones(4), {"c": jnp.zeros((2, 2), jnp.bfloat16)}],
                "step": jnp.int32(7)}
        ckpt.save(str(tmp_path), 7, tree)
        out, step, _ = ckpt.restore(str(tmp_path))
        assert step == 7
        np.testing.assert_array_equal(out["a"], tree["a"])
        assert out["b"][1]["c"].dtype == np.dtype("bfloat16") or \
            out["b"][1]["c"].dtype == jnp.bfloat16

    def test_atomic_commit_ignores_partial(self, tmp_path):
        ckpt.save(str(tmp_path), 1, {"x": jnp.ones(2)})
        # a crashed write leaves a .tmp dir — must be invisible
        os.makedirs(tmp_path / "step_00000009.tmp")
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_async_and_gc(self, tmp_path):
        ac = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            ac.save_async(s, {"x": jnp.full(3, s)})
        ac.wait()
        assert ckpt.list_steps(str(tmp_path)) == [3, 4]
        out, s, _ = ckpt.restore(str(tmp_path))
        assert s == 4 and float(out["x"][0]) == 4


class TestTrainerFaultTolerance:
    def _trainer(self, tmp_path, steps=8, **kw):
        model = Model(CFG)
        tc = TrainConfig(steps=steps, ckpt_every=2, ckpt_dir=str(tmp_path),
                         log_every=100, **kw)
        oc = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps)
        return Trainer(model, oc, tc)

    def _pipe(self):
        return SyntheticLM(vocab_size=CFG.vocab_size, seq_len=32,
                           global_batch=4, seed=1)

    def test_loss_decreases(self, tmp_path):
        tr = self._trainer(tmp_path, steps=20)
        _, hist = tr.run(self._pipe())
        first = np.mean([h["loss"] for h in hist[:4]])
        last = np.mean([h["loss"] for h in hist[-4:]])
        assert last < first

    def test_failure_recovery_resumes_from_checkpoint(self, tmp_path):
        tr = self._trainer(tmp_path, steps=8)
        state, hist = tr.run(self._pipe(), inject_failure_at=5)
        # failed at 5, resumed from ckpt at 4, finished all 8 steps
        assert int(state["step"]) == 8
        assert len(hist) >= 8

    def test_restart_after_kill_resumes(self, tmp_path):
        tr = self._trainer(tmp_path, steps=4)
        tr.run(self._pipe())
        # new trainer process picks up where the old one stopped
        tr2 = self._trainer(tmp_path, steps=6)
        state, hist = tr2.run(self._pipe())
        assert int(state["step"]) == 6
        assert len(hist) == 2          # only 2 fresh steps

    def test_deterministic_resume_matches_uninterrupted(self, tmp_path):
        pA = self._pipe()
        trA = self._trainer(tmp_path / "a", steps=6)
        stateA, _ = trA.run(pA)
        trB1 = self._trainer(tmp_path / "b", steps=6)
        stateB, _ = trB1.run(self._pipe(), inject_failure_at=4)
        la = jax.tree_util.tree_leaves(stateA["params"])
        lb = jax.tree_util.tree_leaves(stateB["params"])
        for a, b in zip(la, lb):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-5)

    def test_straggler_detection(self, tmp_path):
        import time as _t
        tr = self._trainer(tmp_path, steps=10, straggler_factor=2.0)
        pipe = self._pipe()
        orig = pipe.batch_at

        def slow_batch(step):
            if step == 7:
                _t.sleep(4.0)          # simulated slow host
            return orig(step)
        pipe.batch_at = slow_batch
        tr.run(pipe)
        assert 7 in tr.stragglers or 8 in tr.stragglers


class TestServeEngine:
    def test_batched_generation(self):
        model = Model(CFG.replace(max_seq_len=256))
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, slots=4, cache_len=96,
                          prefill_buckets=(16, 32))
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, CFG.vocab_size, size=(8 + i,)),
                        max_new_tokens=6) for i in range(6)]
        done = eng.run(reqs, max_ticks=200)
        assert len(done) == 6
        for r in done:
            assert len(r.out_tokens) == 6
            assert all(0 <= t < CFG.vocab_size for t in r.out_tokens)

    def test_continuous_batching_overlap(self):
        """More requests than slots: engine must recycle slots."""
        model = Model(CFG.replace(max_seq_len=256))
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, slots=2, cache_len=64,
                          prefill_buckets=(16,))
        rng = np.random.default_rng(1)
        reqs = [Request(uid=i, prompt=rng.integers(0, CFG.vocab_size, (8,)),
                        max_new_tokens=4) for i in range(5)]
        done = eng.run(reqs, max_ticks=200)
        assert len(done) == 5

    def test_greedy_decode_deterministic(self):
        model = Model(CFG.replace(max_seq_len=256))
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.arange(10) % CFG.vocab_size
        outs = []
        for _ in range(2):
            eng = ServeEngine(model, params, slots=1, cache_len=64,
                              prefill_buckets=(16,))
            done = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=5)])
            outs.append(done[0].out_tokens)
        assert outs[0] == outs[1]


def test_importing_repro_initializes_no_backend():
    """Importing any module of the package must not touch a JAX backend:
    a process that merely imports ``repro`` must not claim the chip, and
    interpret mode is decided where a kernel launches."""
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import importlib, pkgutil
        import repro
        from jax._src import xla_bridge
        for m in pkgutil.walk_packages(repro.__path__, "repro."):
            if m.name == "repro.launch.dryrun":   # fakes devices on import
                continue
            importlib.import_module(m.name)
            assert not xla_bridge._backends, m.name
        print("ok")
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": src,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]

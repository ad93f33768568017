"""Distribution-layer tests: sharding rules, divisibility fallback,
BCQWeight field shardings, elastic re-mesh on small fake meshes.

Uses 8 fake CPU devices (set before jax init via a session-scoped env
check — these tests run in their own module so the device count is safe
to pin here as long as no other test initialized jax first with 1 dev;
to stay robust we spawn a subprocess when the live device count is 1).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> dict:
    """Run code under 8 fake devices in a clean interpreter; returns JSON."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(code))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=570,
                       env={**os.environ, "PYTHONPATH": SRC,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_spec_divisibility_fallback():
    out = run_sub("""
    import jax, json
    from repro.parallel import sharding as shd
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = shd.make_rules()
    specs = {
        "divisible": str(shd.spec_for((16, 64), ("heads", "embed"), mesh, rules)),
        "indivisible": str(shd.spec_for((6, 64), ("heads", "embed"), mesh, rules)),
        "conflict": str(shd.spec_for((8, 8, 64), ("experts", "mlp", "embed"),
                                     mesh, rules)),
        "conflict_fallback": str(shd.spec_for((6, 8, 64), ("experts", "mlp", "embed"),
                                              mesh, rules)),
    }
    print(json.dumps(specs))
    """)
    assert out["divisible"] == "PartitionSpec('model',)"
    assert out["indivisible"] == "PartitionSpec()"          # 6 % 4 -> replicate
    assert out["conflict"] == "PartitionSpec('model',)"     # experts claims it
    assert out["conflict_fallback"] == "PartitionSpec(None, 'model')"  # EP->TP


def test_bcq_weight_shardings_and_lowering():
    out = run_sub("""
    import jax, json
    import jax.numpy as jnp
    from repro.parallel import sharding as shd
    from repro.quant.ptq import abstract_quantized_params
    from repro.models.module import ParamDesc, abstract_params, logical_axes
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = shd.make_rules()
    desc = {"q": ParamDesc((64, 32), jnp.bfloat16, ("heads", "embed"))}
    ap = abstract_params(desc)
    axes = logical_axes(desc)
    qp = abstract_quantized_params(ap, axes, bits=4, group_size=32)
    sh = shd.build_shardings(mesh, qp, axes, rules)
    w = qp["q"]; s = sh["q"]
    out = {"packed": str(s.packed.spec), "alpha": str(s.alpha.spec),
           "z": str(s.z.spec), "packed_shape": list(w.packed.shape)}
    # prove it lowers: y = x @ dequant(w).T under the mesh
    from repro.core.lut_gemm import bcq_apply
    x = jax.ShapeDtypeStruct((8, 32), jnp.bfloat16)
    with mesh:
        c = jax.jit(lambda xx, ww: bcq_apply(xx, ww, "bcq_xla"),
                    in_shardings=(None, s)).lower(x, qp["q"]).compile()
    out["lowered"] = True
    print(json.dumps(out))
    """)
    assert "model" in out["packed"]
    assert out["lowered"]


def test_elastic_remesh_checkpoint_roundtrip(tmp_path):
    """Save on a 2x4 mesh, restore onto 4x2 and 1x8 — topology-agnostic."""
    out = run_sub(f"""
    import jax, json, numpy as np
    import jax.numpy as jnp
    from repro.parallel import sharding as shd
    from repro.train import checkpoint as ckpt
    from repro.launch.mesh import make_mesh_for
    from repro.launch.mesh import make_mesh
    mesh1 = make_mesh((2, 4), ("data", "model"))
    rules = shd.make_rules()
    tree = {{"w": jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)}}
    axes = {{"w": ("heads", "embed")}}
    sh1 = shd.build_shardings(mesh1, tree, axes, rules)
    tree = jax.tree_util.tree_map(jax.device_put, tree, sh1)
    ckpt.save(r"{tmp_path}", 3, tree)
    ok = []
    for shape in ((4, 2), (1, 8), (8, 1)):
        mesh2 = make_mesh(shape, ("data", "model"))
        sh2 = shd.build_shardings(mesh2, tree, axes, rules)
        out, step, _ = ckpt.restore(r"{tmp_path}", 3, shardings=sh2)
        ok.append(bool(np.array_equal(np.asarray(out["w"]),
                                      np.arange(64*32).reshape(64, 32))))
    print(json.dumps({{"ok": ok}}))
    """)
    assert out["ok"] == [True, True, True]


def test_distributed_train_step_runs():
    """End-to-end: 2x4 mesh, real (tiny) model, two sharded train steps
    EXECUTE (not just compile) and losses are finite."""
    out = run_sub("""
    import jax, json
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.models import Model
    from repro.optim import adamw
    from repro.parallel import sharding as shd
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = shd.make_rules(fsdp=True, act_shard=True)
    shd.set_activation_rules(mesh, rules)
    cfg = get_reduced("phi4_mini_3_8b").replace(
        d_model=64, n_heads=8, n_kv_heads=4, head_dim=8, d_ff=128,
        vocab_size=512, n_layers=2, scan_layers=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    axes = model.axes()
    p_sh = shd.build_shardings(mesh, params, axes, rules)
    params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
    opt = adamw.init_state(params)
    ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1)
    pipe = SyntheticLM(vocab_size=512, seq_len=32, global_batch=8, seed=0)

    @jax.jit
    def step(params, opt, batch):
        loss, g = jax.value_and_grad(model.loss_fn)(params, batch)
        p2, o2, m = adamw.apply_updates(params, g, opt, ocfg)
        return p2, o2, loss

    losses = []
    with mesh:
        for i in range(2):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            params, opt, loss = step(params, opt, batch)
            losses.append(float(loss))
    print(json.dumps({"losses": losses}))
    """)
    assert all(np.isfinite(l) for l in out["losses"])

"""Multi-device tests (8 fake CPU devices in a subprocess): ring collective
matmuls, checkpoint resharding (elastic re-mesh), sharded train step, and a
mini dry-run.  Subprocesses are used because XLA_FLAGS must be set before
jax initializes."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestRingCollectives:
    def test_ag_and_rs_matmul(self):
        out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import overlap
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
w = jax.random.normal(jax.random.PRNGKey(1), (32, 48))
for maker in (overlap.make_sharded_ag_matmul, overlap.make_sharded_rs_matmul):
    for ring in (False, True):
        fn = maker(mesh, "model", ring=ring)
        assert np.allclose(fn(x, w), x @ w, atol=1e-4), (maker, ring)
txt = jax.jit(overlap.make_sharded_ag_matmul(mesh, "model", ring=True)).lower(x, w).compile().as_text()
assert "collective-permute" in txt and "all-gather" not in txt
print("OK")
""")
        assert "OK" in out

    def test_ring_overlappability_in_hlo(self):
        """The ring version's wire bytes are collective-permute (overlappable)
        instead of all-gather (blocking) — the cluster-level stream claim."""
        out = run_sub("""
import jax, jax.numpy as jnp
from repro.core import overlap, hloanalysis
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
w = jax.random.normal(jax.random.PRNGKey(1), (32, 48))
costs = {}
for ring in (False, True):
    fn = overlap.make_sharded_ag_matmul(mesh, "model", ring=ring)
    txt = jax.jit(fn).lower(x, w).compile().as_text()
    c = hloanalysis.analyse_hlo_text(txt)
    costs[ring] = c.collective_by_op
assert costs[False]["all-gather"] > 0 and costs[False]["collective-permute"] == 0
assert costs[True]["collective-permute"] > 0 and costs[True]["all-gather"] == 0
print("OK")
""")
        assert "OK" in out


class TestElasticResharding:
    def test_checkpoint_across_meshes(self, tmp_path):
        out = run_sub(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh
ck = Checkpointer({str(tmp_path)!r})
mesh_a = make_mesh((8, 1), ("data", "model"))
tree = {{"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
        NamedSharding(mesh_a, P("data", None)))}}
ck.save(0, tree, blocking=True)
# restart on a DIFFERENT mesh shape (elastic re-mesh: lost half the nodes)
mesh_b = make_mesh((2, 2), ("data", "model"))
shardings = {{"w": NamedSharding(mesh_b, P("data", "model"))}}
got, meta = ck.restore(shardings=shardings)
assert np.allclose(np.asarray(got["w"]), np.arange(64.0).reshape(8, 8))
assert got["w"].sharding.mesh.shape["data"] == 2
print("OK")
""")
        assert "OK" in out


class TestShardedTrainStep:
    def test_sharded_equals_local(self):
        """One sharded train step on a 4x2 mesh matches the single-device
        step (same math under SPMD)."""
        out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
import repro.configs as C
from repro.launch import sharding, steps
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.models import transformer as T
cfg = C.get_smoke_config("qwen3-4b")
params = T.init_params(cfg, jax.random.PRNGKey(0))
opt_cfg = adamw.AdamWConfig()
opt = adamw.init_state(params)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
fn = steps.make_train_step(cfg, opt_cfg, accum=2)
p1, o1, m1 = jax.jit(fn)(params, opt, batch)

mesh = make_mesh((4, 2), ("data", "model"))
pshape = jax.eval_shape(lambda: params)
pspecs = sharding.param_specs(pshape, mesh)
ospecs = sharding.opt_state_specs(pspecs)
with jax.set_mesh(mesh):
    p_sh = jax.device_put(params, sharding.to_named(pspecs, mesh))
    o_sh = jax.device_put(opt, sharding.to_named(ospecs, mesh))
    p2, o2, m2 = jax.jit(fn,
        in_shardings=(sharding.to_named(pspecs, mesh),
                      sharding.to_named(ospecs, mesh), None))(p_sh, o_sh, batch)
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
assert max(jax.tree.leaves(d)) < 1e-3, sorted(jax.tree.leaves(d))[-3:]
print("OK")
""")
        assert "OK" in out


class TestMiniDryRun:
    def test_mini_multipod_mesh_compiles(self):
        """A 2x2x2 'multi-pod' mesh compiles a smoke-config train step with
        the production sharding rules (same code path as the 512-chip run)."""
        out = run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.configs as C
from repro.launch import sharding, steps
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.models import transformer as T
cfg = C.get_smoke_config("mixtral-8x7b")
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
params_shape = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
pspecs = sharding.param_specs(params_shape, mesh)
params_in = sharding.shaped(params_shape, pspecs, mesh)
opt_cfg = adamw.AdamWConfig()
opt_shape = jax.eval_shape(adamw.init_state, params_shape)
ospecs = sharding.opt_state_specs(pspecs)
opt_in = sharding.shaped(opt_shape, ospecs, mesh)
bshapes = steps.batch_shapes(cfg, global_batch=8, seq_len=32)
bspecs = sharding.batch_specs(bshapes, mesh)
batch_in = sharding.shaped(bshapes, bspecs, mesh)
fn = steps.make_train_step(cfg, opt_cfg, accum=2)
metrics_specs = {k: P() for k in ("loss", "ce", "aux", "grad_norm", "lr")}
with jax.set_mesh(mesh):
    compiled = jax.jit(fn,
        in_shardings=(sharding.to_named(pspecs, mesh),
                      sharding.to_named(ospecs, mesh),
                      sharding.to_named(bspecs, mesh)),
        out_shardings=(sharding.to_named(pspecs, mesh),
                       sharding.to_named(ospecs, mesh),
                       sharding.to_named(metrics_specs, mesh)),
        donate_argnums=(0, 1)).lower(params_in, opt_in, batch_in).compile()
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes > 0
print("OK")
""")
        assert "OK" in out


class TestChipSmokeShardedPhase:
    def test_sharded_step_matches_local_on_four_devices(self):
        """``chip_smoke.py --chips 4``'s phase on four virtual devices, in
        bf16 as on the chip: the (data=2, model=2) step matches device 0."""
        out = run_sub(f"""
import dataclasses, importlib.util
import jax, jax.numpy as jnp
import repro.configs as C
spec = importlib.util.spec_from_file_location(
    "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cfg = dataclasses.replace(C.get_smoke_config(cs.ARCH),
                          param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
res = cs.sharded_step_matches_local(cfg, jax.devices()[:4], seq=32)
print(res)
print("OK")
""")
        assert "OK" in out

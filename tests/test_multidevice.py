"""Multi-device semantics (8 forced host devices, separate subprocess —
jax locks the device count at first init, so these scenarios each run via
a child interpreter)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, n_dev: int = 8) -> str:
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(ROOT, "src"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


@pytest.mark.slow
def test_sharded_decode_matches_local():
    out = _run("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_test_mesh
from repro.models.attention import decode_attention_local, decode_attention_sharded
mesh = make_test_mesh(2, 4)
B, S, H, KV, hd = 4, 32, 4, 2, 16
ks = jax.random.split(jax.random.key(0), 5)
q = jax.random.normal(ks[0], (B, 1, H, hd))
kn = jax.random.normal(ks[1], (B, 1, KV, hd))
vn = jax.random.normal(ks[2], (B, 1, KV, hd))
kc = jax.random.normal(ks[3], (B, S, KV, hd))
vc = jax.random.normal(ks[4], (B, S, KV, hd))
t = jnp.int32(17)
ref, kr, vr = decode_attention_local(q, kn, vn, kc, vc, 17)
with mesh:
    got, kg, vg = jax.jit(lambda *a: decode_attention_sharded(
        *a, mesh=mesh, dp_axes=("data",)))(q, kn, vn, kc, vc, t)
import numpy as np
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
np.testing.assert_allclose(np.asarray(kg), np.asarray(kr), atol=1e-6)
print("OK sharded-decode")
""")
    assert "OK sharded-decode" in out


@pytest.mark.slow
def test_compressed_psum_close_to_exact():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.optim.grad_compression import compressed_psum_tree
mesh = make_test_mesh(8, 1)
g = jax.random.normal(jax.random.key(0), (8, 64))  # row i on device i

def body(g_loc):
    grads = {"w": g_loc[0]}
    err = {"w": jnp.zeros_like(g_loc[0])}
    red, new_err = compressed_psum_tree(grads, err, mesh=mesh,
                                        dp_axes=("data",))
    return red["w"]

with mesh:
    got = jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                        out_specs=P(None), check_vma=False)(g)
exact = g.mean(0)
err = float(jnp.max(jnp.abs(got - exact)))
scale = float(jnp.max(jnp.abs(g))) / 127.0
assert err < 3 * scale, (err, scale)
print("OK compressed-psum", err)
""")
    assert "OK compressed-psum" in out


def test_elastic_remesh_after_failure():
    out = _run("""
import jax, numpy as np
from repro.launch.elastic import plan_remesh, build_mesh, simulate_failure_and_remesh
mesh = build_mesh(plan_remesh(8, prefer_model=4))
host = {"w": np.arange(32.0).reshape(8, 4)}
axes = {"w": ("batch", "ff")}
new_mesh, tree = simulate_failure_and_remesh(
    host, axes, old_mesh=mesh, lost_devices=2, prefer_model=4)
assert new_mesh.size == 6, new_mesh.size
assert dict(zip(new_mesh.axis_names, new_mesh.devices.shape))["model"] in (2, 3)
np.testing.assert_array_equal(np.asarray(tree["w"]), host["w"])
print("OK elastic", new_mesh.devices.shape)
""")
    assert "OK elastic" in out


def test_small_mesh_dryrun_end_to_end():
    """The dry-run driver machinery on a small (2,4) mesh with a reduced
    model: lower + compile + roofline terms all produced."""
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.models.model_zoo import build_model
from repro.models.transformer import RunConfig
from repro.parallel.sharding_rules import AxisRules, tree_specs
from repro.roofline.analysis import collective_bytes
from repro.roofline.jaxpr_cost import step_cost
mesh = make_test_mesh(2, 4)
rules = AxisRules.pod()
rcfg = RunConfig(rules=rules, attn_expand_kv=True, mesh=mesh,
                 q_block=8, kv_block=8)
m = build_model("yi-9b", rcfg, reduced=True)
param_sds, axes = m.abstract_params()
pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tree_specs(axes, rules))
batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
bshard = {k: NamedSharding(mesh, P("data", None)) for k in batch}
with mesh:
    fn = jax.jit(lambda p, b: m.loss(p, b)[0],
                 in_shardings=(pshard, bshard))
    compiled = fn.lower(param_sds, batch).compile()
    cost = step_cost(fn, param_sds, batch)
coll = collective_bytes(compiled.as_text())
assert cost.flops > 0 and coll["total"] > 0
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes >= 0
print("OK dryrun-small", int(cost.flops), coll["total"] > 0)
""")
    assert "OK dryrun-small" in out

"""Distribution substrate: sharding rules, checkpoint, fault tolerance,
compression, and multi-device collectives (subprocess with forced device
count so the main test process keeps 1 device)."""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def test_param_specs_cover_all_leaves():
    import jax
    from repro.configs.base import get_config
    from repro.distributed import sharding as shd
    from repro.launch.steps import params_struct
    for arch in ["qwen3-14b", "deepseek-v2-236b", "rwkv6-7b", "zamba2-7b",
                 "whisper-base", "paligemma-3b"]:
        cfg = get_config(arch)
        ps = params_struct(cfg)
        specs = shd.param_specs(ps, cfg, fsdp=True)
        for (path, leaf), (_, spec) in zip(
                jax.tree.flatten_with_path(ps)[0],
                jax.tree.flatten_with_path(specs)[0]):
            assert len([a for a in spec if a is not None]) <= leaf.ndim


def test_moe_expert_rule_divisibility():
    """Every sharded dim must divide by its mesh-axis size (16)."""
    import jax
    from repro.configs.base import get_config
    from repro.distributed import sharding as shd
    from repro.launch.steps import params_struct
    sizes = {"data": 16, "model": 16, "pod": 2}
    for arch in ["mixtral-8x7b", "deepseek-v2-236b"]:
        cfg = get_config(arch)
        ps = params_struct(cfg)
        combos = [(True, False), (False, False)]
        if cfg.n_experts % 16 == 0:      # expert_data needs E % data == 0
            combos.append((False, True))
        for fsdp, ed in combos:
            from jax.sharding import PartitionSpec
            specs = shd.param_specs(ps, cfg, fsdp=fsdp, expert_data=ed)
            flat_l = jax.tree.flatten_with_path(ps)[0]
            flat_s = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
            for (path, leaf), spec in zip(flat_l, flat_s):
                for dim, ax in enumerate(spec):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    nshard = int(np.prod([sizes[a] for a in axes]))
                    assert leaf.shape[dim] % nshard == 0, \
                        (arch, path, leaf.shape, spec)


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_retention():
    from repro.checkpoint import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        state = {"params": {"w": np.arange(12.0).reshape(3, 4),
                            "blocks": {"a": np.ones((2, 2))}},
                 "opt": {"m": np.zeros(3)}}
        for s in (5, 10, 15):
            cm.save(s, state)
        assert cm.all_steps() == [10, 15]
        step, rec = cm.restore_latest()
        assert step == 15
        np.testing.assert_array_equal(rec["params"]["w"],
                                      state["params"]["w"])
        np.testing.assert_array_equal(rec["params"]["blocks"]["a"],
                                      state["params"]["blocks"]["a"])


def test_checkpoint_bf16_roundtrip():
    """np.savez stores bf16 as raw void — the manager must view-shim it."""
    import jax.numpy as jnp
    import ml_dtypes
    from repro.checkpoint import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=1)
        w = np.asarray(jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3))
        cm.save(1, {"params": {"w": w, "b": np.ones(2, np.float32)}})
        _, rec = cm.restore_latest()
        assert rec["params"]["w"].dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(
            rec["params"]["w"].astype(np.float32), w.astype(np.float32))


def test_checkpoint_bare_array_state():
    """Top-level bare-array state entries survive the roundtrip."""
    from repro.checkpoint import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=1)
        cm.save(1, {"w": np.arange(4.0)})
        _, rec = cm.restore_latest()
        np.testing.assert_array_equal(rec["w"], np.arange(4.0))


def test_checkpoint_async_write():
    from repro.checkpoint import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=3, async_write=True)
        for s in range(3):
            cm.save(s, {"x": {"v": np.full((4,), s, np.float32)}})
        cm.wait()
        assert cm.all_steps() == [0, 1, 2]
        _, rec = cm.restore_latest()
        assert rec["x"]["v"][0] == 2


def test_checkpoint_ignores_stale_tmp():
    from repro.checkpoint import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        os.makedirs(os.path.join(d, "step_00000007.tmp-999"))
        cm = CheckpointManager(d, keep=2)
        assert cm.all_steps() == []
        cm.save(1, {"x": {"v": np.ones(2)}})
        assert cm.all_steps() == [1]
        assert not any(".tmp-" in n for n in os.listdir(d))


# ---------------------------------------------------------------------------
# fault tolerance: elastic re-mesh + watchdog (simulated failures)
# ---------------------------------------------------------------------------


def test_elastic_runner_survives_node_loss():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.fault_tolerance import ElasticRunner, FaultInjector, reshard, to_host

def make_step(mesh):
    ndev = mesh.devices.size
    def step(state):
        return jax.tree.map(lambda x: x + 1.0, state)
    jit_step = jax.jit(step)
    shard = lambda host: reshard(host, {"w": P("data")}, mesh)
    unshard = to_host
    return (lambda s: jit_step(s)), shard, unshard

inj = FaultInjector(node_loss_steps={3: 4})   # lose 4 devices at step 3
r = ElasticRunner(make_step, model_parallel=1, injector=inj)
state = r.run({"w": np.zeros((8,), np.float32)}, n_steps=6)
assert np.allclose(state["w"], 6.0), state
assert len(r.log) == 1 and "remesh" in r.log[0]
assert r.mesh.devices.size == 4
print("ELASTIC_OK")
"""
    assert "ELASTIC_OK" in run_with_devices(code, n=8)


def test_watchdog_flags_stragglers():
    from repro.distributed.fault_tolerance import StepWatchdog
    wd = StepWatchdog(factor=3.0)
    for i in range(8):
        wd.observe(i, 0.1)
    assert not wd.flagged
    assert wd.observe(9, 1.0)
    assert wd.flagged and wd.flagged[0][0] == 9


def test_checkpoint_restart_resumes_state():
    from repro.checkpoint import CheckpointManager
    from repro.distributed.fault_tolerance import ElasticRunner, FaultInjector
    import jax
    import numpy as np

    def make_step(mesh):
        def step(state):
            return {"w": state["w"] + 1.0}
        return step, (lambda h: h), (lambda d: d)

    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        r = ElasticRunner(make_step, model_parallel=1, ckpt_manager=cm,
                          ckpt_every=2)
        r.run({"w": np.zeros(2)}, n_steps=5)
        step, state = r.resume()       # simulated restart
        assert step == 4
        np.testing.assert_allclose(state["w"], 4.0)


# ---------------------------------------------------------------------------
# multi-device collectives (subprocess)
# ---------------------------------------------------------------------------


def test_sharded_topk_exact():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import sharded_topk, local_topk
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
q = rng.normal(size=(6, 32)).astype(np.float32)
c = rng.normal(size=(64, 32)).astype(np.float32)
q /= np.linalg.norm(q, axis=1, keepdims=True)
c /= np.linalg.norm(c, axis=1, keepdims=True)
with mesh:
    v, i = sharded_topk(jnp.asarray(q), jnp.asarray(c), 4, mesh)
vr, ir = local_topk(jnp.asarray(q), jnp.asarray(c), 4)
assert np.allclose(np.asarray(v), np.asarray(vr), atol=1e-6)
assert np.array_equal(np.asarray(i), np.asarray(ir))
print("TOPK_OK")
"""
    assert "TOPK_OK" in run_with_devices(code, n=8)


def test_ring_allreduce_matches_psum():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import ring_allreduce_schedule
mesh = jax.make_mesh((8,), ("x",))
data = np.arange(8 * 5, dtype=np.float32).reshape(8, 5)
def kern(x):
    return ring_allreduce_schedule(x[0], "x")
fn = jax.shard_map(kern, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                   check_vma=False)
out = np.asarray(fn(data)).reshape(8, 5)
expect = data.sum(axis=0)
for r in range(8):
    assert np.allclose(out[r], expect), (r, out[r], expect)
print("RING_OK")
"""
    assert "RING_OK" in run_with_devices(code, n=8)


def test_pipeline_forward_matches_sequential():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_forward, bubble_fraction
mesh = jax.make_mesh((4,), ("stage",))
rng = np.random.default_rng(0)
S, layers_per = 4, 1
ws = jnp.asarray(rng.normal(size=(S, 16, 16)).astype(np.float32) * 0.3)
x = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
def stage_fn(w, xm):
    return jnp.tanh(xm @ w)
out = pipeline_forward(stage_fn, ws, x, mesh=mesh, axis="stage",
                       n_microbatches=4)
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ ws[s])
assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5), \
    np.abs(np.asarray(out) - np.asarray(ref)).max()
assert abs(bubble_fraction(4, 4) - 3/7) < 1e-9
print("PIPE_OK")
"""
    assert "PIPE_OK" in run_with_devices(code, n=4)


def test_compressed_psum_close_to_exact():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
g = rng.normal(size=(8, 64)).astype(np.float32)
def kern(x):
    return compressed_psum({"g": x[0]}, "data")["g"]
fn = jax.shard_map(kern, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                   check_vma=False)
out = np.asarray(fn(g)).reshape(8, 64)
exact = g.mean(axis=0)
rel = np.linalg.norm(out[0] - exact) / np.linalg.norm(exact)
assert rel < 0.05, rel
print("COMPRESS_OK")
"""
    assert "COMPRESS_OK" in run_with_devices(code, n=8)

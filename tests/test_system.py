"""End-to-end behaviour tests for the paper's system: workload in ->
analytics-steered serving out, plus the distributed/dry-run machinery in a
subprocess with fake devices."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_end_to_end_policy_pipeline():
    """Workload -> controller -> scheduler: the recommended configuration
    must not be worse than the unconfigured default on the same stream."""
    from repro.core.control import AdaptiveController
    from repro.core.distributions import LogNormalTokens
    from repro.core.latency_model import (
        BatchLatencyModel, PAPER_A100_LLAMA2_7B)
    from repro.data.pipeline import make_request_stream
    from repro.serving.metrics import summarize
    from repro.serving.scheduler import (
        DynamicBatchScheduler, ElasticBatchScheduler, ModelClock)

    dist = LogNormalTokens(7.0, 0.7)
    blat = BatchLatencyModel(k1=0.05, k2=0.5, k3=1e-4, k4=0.002)
    clock = ModelClock(PAPER_A100_LLAMA2_7B, blat)
    reqs = make_request_stream(30_000, lam=0.5, dist=dist, vocab=100, seed=0)

    ctrl = AdaptiveController(PAPER_A100_LLAMA2_7B, blat, theta=119 / 120,
                              elastic_available=True, min_samples=64)
    for r in reqs[:512]:
        ctrl.observe_arrival(r.arrival)
        ctrl.observe_completion(r.target_output_tokens)
    rec = ctrl.recommendation(force=True)
    assert rec.policy == "elastic" and rec.n_max is not None

    base = summarize(DynamicBatchScheduler(clock).run(reqs))
    tuned = summarize(ElasticBatchScheduler(
        clock, n_max=rec.n_max, b_max=rec.b_max).run(reqs))
    # controller-tuned serving strictly reduces e2e latency and queue wait
    assert tuned["mean_e2e"] < base["mean_e2e"]
    assert tuned["mean_wait"] <= base["mean_wait"] * 1.05


def _run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_small_mesh():
    """Lower + compile + RUN a sharded train step on an 8-device fake mesh;
    loss must match the single-device value (GSPMD correctness)."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models.model import param_specs
from repro.models.params import init_params
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_step import TrainConfig, make_train_step
from repro.distributed.sharding import ShardCtx, DEFAULT_RULES
from repro.data.pipeline import SyntheticLMDataset
from repro.launch.mesh import auto_mesh

cfg = get_smoke_config("internlm2-1.8b")
mesh = auto_mesh((2, 4), ("data", "model"))
tcfg = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=0))
params = init_params(param_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
opt = adamw_init(params, tcfg.adamw)
ds = SyntheticLMDataset(cfg, 32, 8, seed=0)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}

ref_step = jax.jit(make_train_step(cfg, tcfg))
_, _, ref_metrics = ref_step(params, opt, batch)

ctx = ShardCtx(mesh=mesh, rules=dict(DEFAULT_RULES))
step = make_train_step(cfg, tcfg, ctx)
with jax.set_mesh(mesh):
    batch_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    p2, o2, metrics = jax.jit(step)(params, opt, batch_sh)
err = abs(float(metrics["loss"]) - float(ref_metrics["loss"]))
assert err < 5e-4, err
print("OK", float(metrics["loss"]))
"""
    assert "OK" in _run_sub(code)


def test_checkpoint_reshard_restore():
    """Save on a (2,4) mesh, restore onto (4,2) — elastic scaling path."""
    code = """
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.training.checkpoint import CheckpointManager

d = tempfile.mkdtemp()
mesh1 = jax.make_mesh((2, 4), ("data", "model"))
state = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                             NamedSharding(mesh1, P("data", "model")))}
mgr = CheckpointManager(d, async_write=False)
mgr.save(3, state)

mesh2 = jax.make_mesh((4, 2), ("data", "model"))
tgt_shard = NamedSharding(mesh2, P("model", "data"))
restored, step, _ = mgr.restore(state, shardings={"w": tgt_shard})
assert step == 3
np.testing.assert_array_equal(np.asarray(restored["w"]),
                              np.arange(64.0).reshape(8, 8))
print("OK")
"""
    assert "OK" in _run_sub(code)


def test_degraded_mesh_lowering():
    """The same serve step lowers + compiles on a degraded (1,8) mesh —
    lose-half-the-hosts elasticity at dry-run fidelity."""
    code = """
import jax
from repro.configs import get_config
from repro.launch.mesh import make_degraded_mesh
from repro.launch.specs import build_cell

cfg = get_config("qwen2.5-3b")
mesh = make_degraded_mesh(data=1, model=8)
cell = build_cell(cfg, "decode_32k", mesh)
with jax.set_mesh(mesh):
    compiled = jax.jit(cell.step_fn,
                       donate_argnums=cell.donate).lower(*cell.args).compile()
flops = float(compiled.cost_analysis()["flops"])
print("OK", flops > 0)
"""
    out = _run_sub(code)
    assert "OK True" in out


def test_dryrun_artifacts_complete():
    """All 40 (arch x shape) cells x both meshes are present and ok/skipped
    (the sweep is run by scripts/run_dryruns.sh; this asserts its outcome)."""
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    if not os.path.isdir(out_dir):
        pytest.skip("dry-run artifacts not generated in this checkout")
    from repro.configs import ARCH_IDS, SHAPE_IDS
    missing, bad = [], []
    for arch in ARCH_IDS:
        for shape in SHAPE_IDS:
            for mesh in ("single", "multi"):
                p = os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")
                if not os.path.exists(p):
                    missing.append((arch, shape, mesh))
                    continue
                rec = json.load(open(p))
                if rec["status"] not in ("ok", "skipped_by_design"):
                    bad.append((arch, shape, mesh, rec["status"]))
    assert not missing, missing
    assert not bad, bad

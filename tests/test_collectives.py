"""Compressed gradient reduction on a fake 8-device mesh (subprocess)."""

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_compressed_mean_matches_fp32_mean():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.collectives import compressed_mean_rows

mesh = jax.make_mesh((8,), ("data",))
n, size = 8, 8 * 512
rng = np.random.default_rng(0)
g = rng.normal(0, 1.0, (n, size)).astype(np.float32)
gd = jax.device_put(g, NamedSharding(mesh, P("data")))
out = np.asarray(compressed_mean_rows(gd, mesh, "data"))
ref = g.mean(axis=0)
# int8 quantization + bf16 gather error bound: ~max|g|/127 + bf16 eps
err = np.abs(out - ref[None]).max()
assert err < np.abs(g).max() / 127.0 + 0.02, err
# all rows identical (replicated mean)
assert np.abs(out - out[0:1]).max() < 1e-6
print("OK", err)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_wire_bytes_are_compressed():
    """The lowered HLO's collective payloads must be int8/bf16, not fp32."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.collectives import compressed_mean_rows
from repro.utils.hlo import analyze_hlo_text

mesh = jax.make_mesh((8,), ("data",))
n, size = 8, 8 * 512
sds = jax.ShapeDtypeStruct((n, size), jnp.float32,
                           sharding=NamedSharding(mesh, P("data")))
with jax.set_mesh(mesh):
    comp = jax.jit(lambda g: compressed_mean_rows(g, mesh, "data")) \
        .lower(sds).compile()
cost = analyze_hlo_text(comp.as_text())
wire = cost.collective_wire_bytes
# fp32 ring all-reduce baseline wire: 2 * 4B * size * (n-1)/n per device
fp32_wire = 2 * 4 * size * (n - 1) / n
assert wire < fp32_wire * 0.8, (wire, fp32_wire)
print("OK", wire, fp32_wire)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout

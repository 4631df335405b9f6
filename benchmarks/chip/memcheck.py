"""Compile a cell's largest programs for a described TPU v5e, with no chip,
and print what each holds in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/memcheck.py <cell> [<cell> ...]

For each cell: the prefill at the batch cap and the largest prompt
bucket, the decode chunk at the batch cap, and the compaction from the
batch cap to half of it.  Beside them, the resident weights and the KV
cache one batch holds.  A program's peak is roughly its arguments plus
its temporaries plus its outputs less what it aliases.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import engine_io  # noqa: E402
from spec import load_cell  # noqa: E402
from traffic import prompt_buckets  # noqa: E402


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def report(name: str, compiled) -> int:
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    print(f"  {name}: args {ma.argument_size_in_bytes} temp "
          f"{ma.temp_size_in_bytes} out {ma.output_size_in_bytes} alias "
          f"{ma.alias_size_in_bytes} -> peak {peak} bytes", flush=True)
    return peak


def main(cells) -> None:
    from jax.experimental import topologies
    from repro.kernels.compaction import fused_compact
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in cells:
        cell = load_cell(name)
        eng = engine_io.build_engine(cell, params=None)
        cfg, ecfg = eng.cfg, eng.ecfg
        params = _sds(engine_io.abstract_params(cfg), chip)
        b = ecfg.max_batch
        s = max(prompt_buckets(cell.traffic, ecfg))
        cache = _sds(jax.eval_shape(lambda: eng.new_cache(b)), chip)
        i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
            shape, jnp.int32, sharding=chip)
        wbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(params))
        cbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
        print(f"{name}: weights {wbytes} bytes, KV cache at bucket {b} x "
              f"{ecfg.max_seq}: {cbytes} bytes", flush=True)
        pre = eng._get_prefill(b, s).lower(
            params, cache, i32(b, s), i32(b)).compile()
        report(f"prefill b={b} s={s}", pre)
        keys = jax.ShapeDtypeStruct((b, 2), jnp.uint32, sharding=chip)
        chunk = eng._get_decode_chunk(b, ecfg.decode_chunk).lower(
            params, cache, i32(b), i32(b), i32(b), i32(b), keys).compile()
        report(f"decode_chunk b={b} steps={ecfg.decode_chunk}", chunk)
        comp = jax.jit(lambda *a: fused_compact(
            *a, nb=b // 2, interpret=False)).lower(
            cache, i32(b), i32(b), None, i32(b), i32(b)).compile()
        report(f"compact {b}->{b // 2}", comp)


if __name__ == "__main__":
    main(sys.argv[1:])

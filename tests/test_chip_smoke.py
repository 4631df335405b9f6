"""``chip_smoke.py``'s phases at smoke widths on the CPU, so that a change
to the served path that would break the chip check fails here first.  The
ragged decode kernel is forced on (interpret mode off the chip)."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import serve
from repro.serving.engine import Engine

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()
SMOKE_ARGV = cs.SERVE_ARGV + ["--smoke"]


@pytest.fixture(scope="module")
def smoke_engine():
    args = serve.parse_args(SMOKE_ARGV)
    cfg = dataclasses.replace(serve.model_config(args),
                              decode_attention_impl="ragged")
    eng = Engine(cfg, serve.engine_config(args, cfg))
    return eng, serve.request_stream(args, cfg)


def test_serve_phase():
    summary = cs.serve_phase(SMOKE_ARGV)
    assert summary["requests"] == 16
    assert summary["compactions"] > 0


def test_continuous_phase(smoke_engine):
    eng, reqs = smoke_engine
    out = cs.continuous_phase(eng, reqs[:cs.CONTINUOUS_REQUESTS])
    assert out["tokens"] == sum(r.target_output_tokens
                                for r in reqs[:cs.CONTINUOUS_REQUESTS])


def test_ragged_vs_dense_phase(smoke_engine):
    eng, reqs = smoke_engine
    out = cs.ragged_vs_dense(eng, reqs)
    assert out["rel_l2"] <= cs.LOGITS_REL_TOL
    assert out["argmax_agree"] == 1.0


def test_compaction_phase(smoke_engine):
    eng, reqs = smoke_engine
    out = cs.compaction_phase(eng, reqs)
    assert out["bucket"] == eng.ecfg.max_batch // 2


def test_kernel_check_refuses_interpret_mode(smoke_engine):
    """Off the chip the kernels are interpreted: the compiled programs hold
    no ``tpu_custom_call``, and the check must say so."""
    eng, reqs = smoke_engine
    programs = cs.compiled_programs(eng, reqs)
    assert set(programs) == {"decode_chunk", "compaction"}
    with pytest.raises(cs.SmokeFailure, match="holds no Pallas kernel"):
        cs.check_kernels_compiled(eng.cfg, programs)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_script_fails_without_chip(where, tmp_path):
    """On the CPU, and in a directory with none of the rest of the repo,
    the script exits non-zero and never prints the success line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if where == "alone":
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        cwd = tmp_path
        env.pop("PYTHONPATH", None)
    else:
        cwd = ROOT
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout

"""Model-zoo smoke tests (deliverable f): every assigned architecture at
reduced scale — one forward/train step on CPU, shape + finiteness asserts,
serving-path consistency, and the Mamba2 SSD oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models.config import ModelConfig
from repro.models.model import (
    cache_specs, decode_step, forward, init_cache, param_specs, prefill)
from repro.models.params import init_params
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import TrainConfig, make_train_step
from repro.utils.tree import tree_num_params

RNG = jax.random.PRNGKey(0)


def _dropless(cfg):
    if cfg.num_experts:
        cfg = dataclasses.replace(
            cfg, capacity_factor=float(cfg.num_experts) / cfg.num_experts_per_tok)
    return cfg


def _inputs(cfg, b, s, rng=RNG):
    kw = {}
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    if cfg.embeddings_input:
        kw["embeds"] = jax.random.normal(
            rng, (b, s, cfg.d_model), jnp.float32) * 0.02
    if cfg.vision_seq:
        kw["cross_kv"] = jax.random.normal(
            rng, (b, cfg.vision_seq, cfg.d_model), jnp.float32) * 0.02
    return tokens, kw


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_smoke(arch):
    cfg = get_smoke_config(arch)
    params = init_params(param_specs(cfg), RNG, jnp.float32)
    tokens, kw = _inputs(cfg, 2, 64)
    logits, aux = forward(
        cfg, params, None if cfg.embeddings_input else tokens, **kw)
    assert logits.shape == (2, 64, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    params = init_params(param_specs(cfg), RNG, jnp.float32)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=1))
    step = jax.jit(make_train_step(cfg, tcfg))
    from repro.training.optimizer import adamw_init
    opt = adamw_init(params, tcfg.adamw)
    tokens, kw = _inputs(cfg, 2, 32)
    batch = {"labels": jax.random.randint(RNG, (2, 32), 0, cfg.vocab_size)}
    if cfg.embeddings_input:
        batch["embeds"] = kw["embeds"][:, :32]
    else:
        batch["tokens"] = tokens
    if cfg.vision_seq:
        batch["image_embeds"] = kw["cross_kv"]
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params must actually move
    delta = sum(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serving_consistency(arch):
    """prefill + decode == full forward (the engine's correctness basis)."""
    cfg = _dropless(get_smoke_config(arch))
    params = init_params(param_specs(cfg), RNG, jnp.float32)
    B, S, EXTRA = 2, 32, 3
    tokens, kw = _inputs(cfg, B, S + EXTRA)
    if cfg.embeddings_input:
        # decode consumes LM-table embeddings of generated tokens: build the
        # oracle input the same way
        table = params["embed"]
        emb = jnp.concatenate(
            [kw["embeds"][:, :S], table[tokens[:, S:]].astype(jnp.float32)],
            axis=1)
        full, _ = forward(cfg, params, embeds=emb)
        cache = init_cache(cfg, B, 64, jnp.float32)
        last, cache = prefill(cfg, params, embeds=emb[:, :S], cache=cache)
    else:
        full, _ = forward(cfg, params, tokens, **kw)
        cache = init_cache(cfg, B, 64, jnp.float32)
        last, cache = prefill(cfg, params, tokens[:, :S], cache=cache, **kw)
    errs = [float(jnp.abs(last - full[:, S - 1]).max())]
    kv_lens = jnp.full((B,), S, jnp.int32)
    for t in range(EXTRA):
        sl, cache = decode_step(cfg, params, cache, tokens[:, S + t], kv_lens)
        kv_lens = kv_lens + 1
        errs.append(float(jnp.abs(sl - full[:, S + t]).max()))
    assert max(errs) < 5e-4, errs


@pytest.mark.parametrize("arch", ["yi-9b", "mixtral-8x7b", "jamba-1.5-large-398b"])
def test_blockwise_attention_matches_dense(arch):
    cfg = _dropless(get_smoke_config(arch))
    params = init_params(param_specs(cfg), RNG, jnp.float32)
    tokens, kw = _inputs(cfg, 2, 64)
    dense, _ = forward(cfg, params, tokens, **kw)
    cfg_blk = dataclasses.replace(cfg, attn_dense_max_seq=16,
                                  attn_chunk_q=16, attn_chunk_kv=16)
    blk, _ = forward(cfg_blk, params, tokens, **kw)
    assert float(jnp.abs(dense - blk).max()) < 5e-4


def test_param_counts_match_published():
    """Full configs' parameter formulas land near the published sizes."""
    tol = {"gemma-7b": 0.02, "yi-9b": 0.02, "qwen2.5-3b": 0.04,
           "internlm2-1.8b": 0.03, "musicgen-large": 0.25,
           "moonshot-v1-16b-a3b": 0.10, "mixtral-8x7b": 0.02,
           "llama-3.2-vision-90b": 0.10, "jamba-1.5-large-398b": 0.08,
           "mamba2-2.7b": 0.05}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        n = cfg.param_count()
        exp = cfg.expected_params
        assert abs(n - exp) / exp < tol[arch], (arch, n, exp)


def test_smoke_param_specs_consistent():
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        params = init_params(param_specs(cfg), RNG, jnp.float32)
        assert tree_num_params(params) == cfg.param_count(), arch


def test_mamba2_ssd_matches_naive_recurrence():
    """Chunked SSD == step-by-step linear recurrence (oracle)."""
    from repro.models.mamba import _ssd_chunked
    from repro.distributed.sharding import NULL_CTX
    cfg = get_smoke_config("mamba2-2.7b")
    cfg = dataclasses.replace(cfg, ssm_chunk=8)
    b, s, h, p, g, n = 2, 40, cfg.ssm_heads, cfg.ssm_head_dim, \
        cfg.ssm_n_groups, cfg.ssm_state
    rng = np.random.default_rng(0)
    xh = jnp.asarray(rng.normal(0, 1, (b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = jnp.asarray(rng.normal(0, 1, (b, s, g, n)), jnp.float32)
    C = jnp.asarray(rng.normal(0, 1, (b, s, g, n)), jnp.float32)
    y, hT = _ssd_chunked(xh, dt, A, B, C, cfg, NULL_CTX)
    # naive recurrence
    rep = h // g
    Bh = np.repeat(np.asarray(B), rep, axis=2)
    Ch = np.repeat(np.asarray(C), rep, axis=2)
    state = np.zeros((b, h, p, n))
    y_ref = np.zeros((b, s, h, p))
    for t in range(s):
        dec = np.exp(np.asarray(dt)[:, t] * np.asarray(A)[None, :])
        xb = np.einsum("bhp,bhn->bhpn", np.asarray(xh)[:, t], Bh[:, t])
        state = state * dec[:, :, None, None] + \
            np.asarray(dt)[:, t][:, :, None, None] * xb
        y_ref[:, t] = np.einsum("bhn,bhpn->bhp", Ch[:, t], state)
    assert np.abs(np.asarray(y) - y_ref).max() < 1e-3
    assert np.abs(np.asarray(hT) - state).max() < 1e-3


def test_sliding_window_cache_ring_buffer():
    """Decode with window < prompt behaves like full recompute with window."""
    cfg = get_smoke_config("mixtral-8x7b")
    cfg = _dropless(cfg)
    cfg = dataclasses.replace(cfg, sliding_window=16)
    params = init_params(param_specs(cfg), RNG, jnp.float32)
    B, S, EXTRA = 1, 12, 10   # prompt < window; decode grows past window
    tokens, _ = _inputs(cfg, B, S + EXTRA)
    full, _ = forward(cfg, params, tokens)
    cache = init_cache(cfg, B, 16, jnp.float32)   # span == window
    last, cache = prefill(cfg, params, tokens[:, :S], cache=cache)
    errs = [float(jnp.abs(last - full[:, S - 1]).max())]
    kv_lens = jnp.full((B,), S, jnp.int32)
    for t in range(EXTRA):
        sl, cache = decode_step(cfg, params, cache, tokens[:, S + t], kv_lens)
        kv_lens = kv_lens + 1
        errs.append(float(jnp.abs(sl - full[:, S + t]).max()))
    assert max(errs) < 5e-4, errs


# (arch, overrides): every cache-update mode and layout, the ring buffer,
# SSM state, a hybrid stack and cross-attention image K/V
_CARRY_CASES = {
    "scatter": ("qwen2.5-3b", {"decode_cache_update": "scatter"}),
    "uniform": ("qwen2.5-3b", {"decode_cache_update": "uniform"}),
    "onehot": ("qwen2.5-3b", {"decode_cache_update": "onehot"}),
    "bhsd-scatter": ("qwen2.5-3b", {"decode_cache_update": "scatter",
                                    "cache_layout": "bhsd"}),
    "bhsd-uniform": ("qwen2.5-3b", {"decode_cache_update": "uniform",
                                    "cache_layout": "bhsd"}),
    "bhsd-onehot": ("qwen2.5-3b", {"decode_cache_update": "onehot",
                                   "cache_layout": "bhsd"}),
    "ragged-interpret": ("qwen2.5-3b", {"decode_cache_update": "scatter",
                                        "decode_attention_impl": "ragged"}),
    "sliding-window": ("mixtral-8x7b", {"decode_cache_update": "scatter",
                                        "sliding_window": 16}),
    "mamba2": ("mamba2-2.7b", {}),
    "jamba": ("jamba-1.5-large-398b", {"decode_cache_update": "scatter"}),
    "cross-attn": ("llama-3.2-vision-90b", {"decode_cache_update": "scatter"}),
}


@pytest.mark.parametrize("case", list(_CARRY_CASES))
def test_decode_carry_matches_unrolled(case):
    """decode_step with the stacked cache in the layer scan's carry equals
    the unrolled path (per-group leaves, static indices) from one prefilled
    cache, step after step: logits and every cache leaf of every group."""
    from repro.models.model import stack_group_cache
    arch, overrides = _CARRY_CASES[case]
    cfg = _dropless(get_smoke_config(arch))
    groups = 3 if len(cfg.group_pattern) == 1 else 2
    cfg = dataclasses.replace(cfg, num_layers=groups * len(cfg.group_pattern),
                              **overrides)
    assert cfg.num_groups == groups
    unrolled = dataclasses.replace(cfg, decode_unroll_layers=True)
    params = init_params(param_specs(cfg), RNG, jnp.float32)
    B, S, STEPS = 2, 12, 6        # 12 + 6 wraps the 16-slot window's ring
    max_seq = 16 if cfg.sliding_window else 32
    tokens, kw = _inputs(cfg, B, S + STEPS)
    lens = (jnp.full((B,), S, jnp.int32)
            if cfg.decode_cache_update == "uniform"
            else jnp.array([S, S - 3], jnp.int32))
    _, cache = prefill(cfg, params, tokens[:, :S], prompt_lens=lens,
                       cache=init_cache(cfg, B, max_seq, jnp.float32), **kw)
    step = jax.jit(lambda c, t, n: decode_step(cfg, params, c, t, n))
    step_u = jax.jit(lambda c, t, n: decode_step(unrolled, params, c, t, n))
    cache_u, kv_lens = cache, lens
    for t in range(STEPS):
        logits, cache = step(cache, tokens[:, S + t], kv_lens)
        logits_u, split = step_u(cache_u, tokens[:, S + t], kv_lens)
        cache_u = stack_group_cache(split, cfg.num_groups)
        # float32 rounding only: the two programs fuse the same ops apart
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            (logits, cache), (logits_u, cache_u))
        kv_lens = kv_lens + 1
    # the steps wrote every group's leaves, not one index of the stack
    k = next(iter(jax.tree.leaves(cache)))
    assert all(float(jnp.abs(k[g]).max()) > 0 for g in range(cfg.num_groups))

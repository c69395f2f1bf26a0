"""Serving correctness: token-by-token decode against a cache must match
teacher-forced full-sequence forward logits (ring cache, SSM recurrence vs
chunked scan, cross-attention prefill)."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import get_config, get_model, smoke_variant

CASES = [
    "tinyllama-1.1b", "qwen3-0.6b", "olmo-1b", "granite-34b",
    "mamba2-130m", "hymba-1.5b", "whisper-tiny", "llama-3.2-vision-90b",
]


def _extras(cfg, B, key):
    if cfg.family == "encdec":
        return {"audio_embeds": 0.1 * jax.random.normal(
            key, (B, cfg.n_audio_frames, cfg.d_model))}
    if cfg.family == "vlm":
        return {"image_embeds": 0.1 * jax.random.normal(
            key, (B, cfg.n_image_tokens, cfg.d_model))}
    return {}


@pytest.mark.parametrize("arch", CASES)
def test_decode_matches_forward(arch, rng_key):
    cfg = smoke_variant(get_config(arch))
    if cfg.family == "moe":
        cfg = cfg.replace(capacity_factor=16.0)
    api = get_model(cfg)
    params = api.init(cfg, rng_key)
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.fold_in(rng_key, 1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, **_extras(cfg, B, jax.random.fold_in(rng_key, 2))}
    full, _ = api.forward(cfg, params, batch)

    cache = api.init_cache(cfg, B, S)
    if api.prefill_cross is not None:
        emb = batch.get("audio_embeds", batch.get("image_embeds"))
        cache = api.prefill_cross(cfg, params, cache, emb)
    outs = []
    for t in range(S):
        lg, cache = api.decode_step(
            cfg, params, cache,
            {"token": tokens[:, t], "pos": jnp.full((B,), t, jnp.int32)})
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.max(jnp.abs(full))) + 1e-9
    rel = float(jnp.max(jnp.abs(dec - full))) / scale
    assert rel < 2e-3, f"{arch}: decode/forward rel err {rel}"


def test_moe_decode_capacity_semantics(rng_key):
    """At tight capacity, train-time token dropping makes decode differ —
    documents (and pins) the capacity semantics."""
    cfg = smoke_variant(get_config("deepseek-moe-16b")).replace(
        capacity_factor=16.0)
    api = get_model(cfg)
    params = api.init(cfg, rng_key)
    B, S = 2, 12
    tokens = jax.random.randint(rng_key, (B, S), 0, cfg.vocab_size)
    full_hi, _ = api.forward(cfg, params, {"tokens": tokens})
    cfg_lo = cfg.replace(capacity_factor=0.25)
    full_lo, _ = api.forward(cfg_lo, params, {"tokens": tokens})
    # tight capacity must actually change outputs (tokens dropped)
    assert float(jnp.max(jnp.abs(full_hi - full_lo))) > 1e-6


def test_sliding_window_ring_cache(rng_key):
    """Sliding-window decode: a model with window W must give identical
    logits whether the cache holds W slots (ring) or the full context."""
    cfg = smoke_variant(get_config("tinyllama-1.1b")).replace(
        sliding_window=8, global_attn_layers=())
    api = get_model(cfg)
    params = api.init(cfg, rng_key)
    B, S = 1, 20
    tokens = jax.random.randint(rng_key, (B, S), 0, cfg.vocab_size)

    def run(cache_len):
        cache = api.init_cache(cfg, B, cache_len)
        outs = []
        for t in range(S):
            lg, cache = api.decode_step(
                cfg, params, cache,
                {"token": tokens[:, t], "pos": jnp.full((B,), t, jnp.int32)})
            outs.append(lg)
        return jnp.stack(outs, 1)

    ring = run(8)        # exactly W slots
    full = run(S)        # plenty of slots
    assert float(jnp.max(jnp.abs(ring - full))) < 1e-4


def test_int8_kv_cache_parity(rng_key):
    """Quantized KV cache: logits within quantization tolerance, top-1
    prediction preserved (the serving §Perf lever)."""
    cfg = smoke_variant(get_config("tinyllama-1.1b"))
    api = get_model(cfg)
    params = api.init(cfg, rng_key)
    B, S = 2, 12
    tokens = jax.random.randint(rng_key, (B, S), 0, cfg.vocab_size)
    full, _ = api.forward(cfg, params, {"tokens": tokens})

    cfg8 = cfg.replace(kv_cache_dtype="int8")
    cache = api.init_cache(cfg8, B, S)
    assert cache["segments"][0]["attn"]["k"].dtype == jnp.int8
    outs = []
    for t in range(S):
        lg, cache = api.decode_step(
            cfg8, params, cache,
            {"token": tokens[:, t], "pos": jnp.full((B,), t, jnp.int32)})
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    rel = float(jnp.max(jnp.abs(dec - full))) / float(jnp.max(jnp.abs(full)))
    assert rel < 0.05, rel
    agree = float(jnp.mean(
        (jnp.argmax(dec, -1) == jnp.argmax(full, -1)).astype(jnp.float32)))
    assert agree > 0.95

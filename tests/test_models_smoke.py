"""Per-architecture smoke tests: reduced same-family configs, one forward
+ one decode step on CPU, asserting shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # per-arch forward/decode XLA compiles

from repro.configs import ARCH_IDS, get_config
from repro.models import model_zoo
from repro.models.inputs import make_decode_tokens, make_train_batch

B, S = 2, 32


@pytest.fixture(scope="module")
def zoo():
    out = {}
    for a in ARCH_IDS:
        cfg = get_config(a, smoke=True)
        params = model_zoo.init_params(cfg, jax.random.PRNGKey(0))
        out[a] = (cfg, params)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(zoo, arch):
    cfg, params = zoo[arch]
    batch = make_train_batch(cfg, B, S)
    logits, aux = model_zoo.forward(cfg, params, batch)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_loss_and_grads_finite(zoo, arch):
    cfg, params = zoo[arch]
    batch = make_train_batch(cfg, B, S)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model_zoo.loss_fn(cfg, p, batch), has_aux=True)(params)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    # loss near log(vocab) at random init (logits ~ small)
    assert 0.5 * np.log(cfg.vocab) < float(metrics["ce"]) \
        < 2.5 * np.log(cfg.vocab)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(zoo, arch):
    cfg, params = zoo[arch]
    cache = model_zoo.init_cache(cfg, B, S)
    if cfg.family == "audio":
        from repro.models import encdec
        frames = jnp.zeros((B, cfg.enc_frames, cfg.d_model),
                           cfg.compute_dtype)
        cache = encdec.prime_cross_cache(cfg, params, cache, frames)
    toks = make_decode_tokens(cfg, B)
    logits, cache2 = model_zoo.decode_step(cfg, params, cache, toks)
    assert logits.shape == (B, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert int(cache2["pos"]) == 1
    logits3, _ = model_zoo.decode_step(cfg, params, cache2, toks)
    assert bool(jnp.isfinite(logits3.astype(jnp.float32)).all())


def test_vlm_image_embeds_path(zoo):
    cfg, params = zoo["llava_next_34b"]
    batch = make_train_batch(cfg, B, S)
    img = jnp.zeros((B, cfg.img_tokens, cfg.d_model), cfg.compute_dtype)
    logits, _ = model_zoo.forward(
        cfg, params, {**batch, "extra_embeds": img})
    assert logits.shape == (B, S, cfg.padded_vocab)


def test_moe_gather_equals_einsum():
    """Both dispatch implementations route identically -> same outputs."""
    import dataclasses
    from repro.models.mlp import init_moe, moe_einsum, moe_gather
    cfg = get_config("deepseek_moe_16b", smoke=True).with_(moe_shards=2)
    params = init_moe(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    yg, ag = moe_gather(cfg, params, x)
    ye, ae = moe_einsum(cfg, params, x)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ye),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(ag), float(ae), rtol=1e-5)


def test_moe_capacity_drops_consistently():
    from repro.models.mlp import init_moe, moe_einsum, moe_gather
    cfg = get_config("granite_moe_1b_a400m", smoke=True).with_(
        capacity_factor=0.5)
    params = init_moe(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    yg, _ = moe_gather(cfg, params, x)
    ye, _ = moe_einsum(cfg, params, x)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ye),
                               rtol=2e-5, atol=2e-5)


def test_serve_launcher_engine_matches_uncached_forward():
    """launch/serve.py's path on the smoke config: --smoke is opt-in, and
    the engine's cached-decode logits match the uncached forward on the
    same tokens, teacher-forced (the check chip_smoke.py makes at full
    width, with the same bf16 bound)."""
    from repro.launch import serve as serve_launch
    assert not serve_launch.parse_args([]).smoke
    args = serve_launch.parse_args(["--smoke", "--prompt-len", "16",
                                    "--new-tokens", "8"])
    eng = serve_launch.build_engine(args)
    cfg = eng.cfg
    prompts = serve_launch.make_prompts(eng, args)
    toks, logits = eng.generate_with_logits(prompts)
    np.testing.assert_array_equal(toks, eng.generate(prompts))
    assert logits.shape == (args.batch, args.new_tokens, cfg.padded_vocab)
    assert serve_launch.check_against_forward(eng, prompts, toks,
                                              logits)["ok"]


def _cache_one_behind(cache, prompt_len):
    return {**cache, "pos": cache["pos"] - 1}


def _cache_slot_zeroed(cache, prompt_len):
    return {**cache, "layers": jax.tree_util.tree_map(
        lambda a: a.at[:, :, prompt_len // 2].set(0), cache["layers"])}


@pytest.mark.parametrize("fault", [_cache_one_behind, _cache_slot_zeroed])
def test_serve_logit_check_catches_cache_fault(fault):
    """The engine-vs-forward check fails when the KV cache is broken
    after prefill: decode writing one position behind (each new token
    overwrites its predecessor's slot), or one prompt slot's k/v zeroed
    in every layer."""
    from repro.launch import serve as serve_launch
    args = serve_launch.parse_args(["--smoke", "--prompt-len", "16",
                                    "--new-tokens", "8"])
    eng = serve_launch.build_engine(args)
    prefill = eng._prefill

    def broken_prefill(params, batch):
        logits, cache = prefill(params, batch)
        return logits, fault(cache, args.prompt_len)

    eng._prefill = broken_prefill
    prompts = serve_launch.make_prompts(eng, args)
    toks, logits = eng.generate_with_logits(prompts)
    got = serve_launch.check_against_forward(eng, prompts, toks, logits)
    assert got["max_abs_dlogit"] > serve_launch.LOGIT_MAX_BOUND
    assert got["mean_abs_dlogit"] > serve_launch.LOGIT_MEAN_BOUND
    assert not got["ok"]


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The env var wins and nothing is set in code; otherwise the cache
    sits at the fixed <repo>/.jax_cache."""
    import os
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        assert compile_cache.enable_compile_cache() == os.path.join(
            repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
config of the same family and runs one forward + one train step on CPU,
asserting output shapes and finiteness (the assignment's required per-arch
smoke)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable, whisper_dec_len
from repro.models import transformer as T
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_step, train_state_init


def _batch(cfg, B=2, S=32):
    key = jax.random.PRNGKey(9)
    if cfg.family == "audio":
        d = max(8, S // 2)
        b = {"tokens": jax.random.randint(key, (B, d), 0, cfg.vocab),
             "targets": jax.random.randint(key, (B, d), 0, cfg.vocab),
             "enc_frames": jax.random.normal(key, (B, S, cfg.d_model))}
    else:
        b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
             "targets": jax.random.randint(key, (B, S), 0, cfg.vocab)}
        if cfg.family == "vlm":
            b["img"] = jax.random.normal(key, (B, cfg.n_img_tokens, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)

    logits, aux = T.forward(params, batch["tokens"], cfg, training=True,
                            rng=jax.random.PRNGKey(1),
                            img=batch.get("img"),
                            enc_frames=batch.get("enc_frames"))
    assert logits.shape == (*batch["tokens"].shape, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all())

    opt = OptConfig(lr=1e-3)
    state = train_state_init(params, opt, jax.random.PRNGKey(2))
    step = jax.jit(make_train_step(cfg, opt))
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_dimensions_match_assignment(arch):
    """The FULL configs carry the exact assigned dimensions (exercised via
    dry-run only; here we pin the numbers so a config edit can't drift)."""
    expect = {
        "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
    }[arch]
    c = get_config(arch)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv, c.d_ff, c.vocab) == expect


def test_moe_extras():
    c = get_config("qwen3-moe-30b-a3b")
    assert (c.n_experts, c.topk) == (128, 8)
    c = get_config("mixtral-8x7b")
    assert (c.n_experts, c.topk) == (8, 2)
    assert c.swa_all and c.window == 4096


def test_long_500k_applicability_split():
    """Exactly the sub-quadratic archs run long_500k (DESIGN.md §5)."""
    eligible = {a for a in ARCH_IDS
                if applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert eligible == {"gemma3-27b", "rwkv6-7b", "zamba2-1.2b", "mixtral-8x7b"}


def test_whisper_decoder_length_rule():
    assert whisper_dec_len(4096) == 448
    assert whisper_dec_len(512) == 64
    assert whisper_dec_len(32768) == 448


def test_quantized_vs_fp_configs_share_code_path():
    """Flipping quant mode changes weights' support, not shapes."""
    from repro.core.quantize import QuantSpec
    cfg = get_config("qwen3-0.6b").reduced()
    batch = _batch(cfg)
    for mode in ("none", "binary", "ternary"):
        c = cfg.with_quant(QuantSpec(mode=mode, norm="channel"))
        params = T.model_init(jax.random.PRNGKey(0), c)
        logits, _ = T.forward(params, batch["tokens"], c, training=True,
                              rng=jax.random.PRNGKey(1))
        assert bool(jnp.isfinite(logits).all())


def test_pattern_expansion_counts():
    from repro.models.transformer import expand_pattern
    pat, rep, tail = expand_pattern(get_config("gemma3-27b"))
    assert len(pat) == 6 and rep == 10 and len(tail) == 2
    # a hybrid is its mamba layers; the shared-block calls are extra
    cfg = get_config("zamba2-1.2b")
    pat, rep, tail = expand_pattern(cfg)
    assert pat == ("mamba",) and rep == 38 and tail == ()
    assert cfg.hybrid_ids == (6, 12, 18, 24, 30, 36) and cfg.n_shared == 1
    pat, rep, tail = expand_pattern(get_config("llama-3.2-vision-90b"))
    assert len(pat) == 5 and rep == 20 and not tail


def test_unrolled_forward_matches_scan():
    """cfg.unroll (dry-run scan-correction path) is numerically identical."""
    import dataclasses
    cfg = get_config("gemma3-27b").reduced()
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    l1, _ = T.forward(params, batch["tokens"], cfg, training=False)
    l2, _ = T.forward(params, batch["tokens"],
                      dataclasses.replace(cfg, unroll=True), training=False)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-4,
                               atol=2e-4)

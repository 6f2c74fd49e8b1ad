"""Per-assigned-architecture smoke tests (deliverable f): reduced config of
the same family — one forward + one train step on CPU, asserting output
shapes and no NaNs.  Full configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_configs
from repro.core.policy import preset
from repro.models import build_model
from repro.nn.module import unbox
from repro.optim.adamw import AdamW
from repro.train.step import TrainStepConfig, make_train_step

ASSIGNED = [
    "h2o-danube-1.8b",
    "granite-3-8b",
    "gemma2-9b",
    "qwen2-7b",
    "zamba2-7b",
    "phi3.5-moe-42b-a6.6b",
    "llama4-scout-17b-a16e",
    "mellum2-12b-a2.5b",
    "whisper-large-v3",
    "internvl2-2b",
    "mamba2-130m",
]

B, S = 2, 32


def _batch(cfg, rng, with_labels=True):
    batch = {}
    tok_len = S
    if cfg.family == "vlm":
        tok_len = S - cfg.vision_patches
        batch["patch_embeds"] = jnp.asarray(
            rng.randn(B, cfg.vision_patches, cfg.d_model), jnp.float32
        )
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.randn(B, S, cfg.d_model), jnp.float32
        )
    batch["tokens"] = jnp.asarray(
        rng.randint(0, cfg.vocab, (B, tok_len)), jnp.int32
    )
    if with_labels:
        batch["labels"] = jnp.asarray(
            rng.randint(0, cfg.vocab, (B, tok_len)), jnp.int32
        )
    return batch


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).reduced()
            model = build_model(cfg)
            params = unbox(model.init(jax.random.PRNGKey(0)))
            cache[arch] = (cfg, model, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ASSIGNED)
def test_forward_smoke(arch, built):
    cfg, model, params = built(arch)
    rng = np.random.RandomState(0)
    logits, aux = model.apply(params, _batch(cfg, rng, False),
                              preset("w4a8_abfp"))
    tok_len = S - cfg.vision_patches if cfg.family == "vlm" else S
    assert logits.shape == (B, tok_len + (cfg.vision_patches
                                          if cfg.family == "vlm" else 0),
                            cfg.vocab_padded)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_step_smoke(arch, built):
    cfg, model, params = built(arch)
    rng = np.random.RandomState(1)
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(params)
    step = make_train_step(model, opt, preset("w4a8_abfp").with_ste(True),
                           TrainStepConfig())
    batch = _batch(cfg, rng)
    params2, opt_state2, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # at least one parameter moved
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), params, params2
    )
    assert max(jax.tree_util.tree_leaves(moved)) > 0


@pytest.mark.parametrize("arch", ["qwen2-7b", "zamba2-7b", "mamba2-130m",
                                  "whisper-large-v3", "gemma2-9b"])
def test_decode_consistency(arch, built):
    """prefill + decode_step logits == apply() logits at the same position
    (one family representative per state type)."""
    cfg, model, params = built(arch)
    rng = np.random.RandomState(2)
    batch = _batch(cfg, rng, False)
    full_logits, _ = model.apply(params, batch, preset("fp32"))
    pre_logits, state = model.prefill(params, batch, preset("fp32"),
                                      max_len=S + 4)
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(full_logits[:, -1]),
        rtol=5e-3, atol=5e-4,
    )
    nxt = jnp.argmax(pre_logits, axis=-1).astype(jnp.int32)[:, None]
    logits2, state2 = model.decode_step(params, nxt, state, preset("fp32"))
    assert logits2.shape == (B, cfg.vocab_padded)
    assert not bool(jnp.isnan(logits2).any())


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_config_eval_shape(arch):
    """The FULL config must eval_shape-init without allocation errors and
    report a parameter count near the advertised size."""
    cfg = get_config(arch)
    model = build_model(cfg)
    sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(unbox(sds))
    )
    expected = {
        "h2o-danube-1.8b": 1.8e9, "granite-3-8b": 8e9, "gemma2-9b": 9e9,
        "qwen2-7b": 7e9, "zamba2-7b": 7e9,
        "phi3.5-moe-42b-a6.6b": 42e9, "llama4-scout-17b-a16e": 107e9,
        "mellum2-12b-a2.5b": 12e9,
        "whisper-large-v3": 1.5e9, "internvl2-2b": 2e9,
        "mamba2-130m": 0.13e9,
    }[arch]
    assert 0.5 * expected < n < 1.7 * expected, (arch, n, expected)


def test_registry_lists_all():
    for arch in ASSIGNED:
        assert arch in list_configs()


def test_skip_shapes_documented():
    """Pure full-attention archs must skip long_500k; SSM/hybrid run it."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        if arch in ("mamba2-130m", "zamba2-7b", "h2o-danube-1.8b",
                    "gemma2-9b"):
            assert "long_500k" not in cfg.skip_shapes, arch
        if arch in ("granite-3-8b", "qwen2-7b", "whisper-large-v3"):
            assert "long_500k" in cfg.skip_shapes, arch

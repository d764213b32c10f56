"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + finiteness; full-config param counts via eval_shape
(no allocation) checked against the published model sizes."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import registry


def _batch_for(arch, cfg, b=2, s=16):
    kind = registry.input_kind(arch)
    kt, kl = jax.random.split(jax.random.PRNGKey(0))
    if kind == "codebooks":
        shape = (b, cfg.n_codebooks, s)
    else:
        shape = (b, s)
    batch = {
        "tokens": jax.random.randint(kt, shape, 0, cfg.vocab_size),
        "labels": jax.random.randint(kl, shape, 0, cfg.vocab_size),
    }
    if kind == "vlm":
        p = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        batch["positions"] = jnp.stack([p, p, p])
    return batch


ARCHS = [a for a in registry.ARCH_IDS]


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_grad(arch):
    cfg = registry.get_reduced_config(arch)
    fns = registry.model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(arch, cfg)
    logits = fns.forward(params, batch["tokens"], cfg,
                         positions=batch.get("positions"))
    kind = registry.input_kind(arch)
    if kind == "codebooks":
        assert logits.shape == (2, cfg.n_codebooks, 16, cfg.vocab_size)
    else:
        assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    loss, grads = jax.value_and_grad(
        lambda p: fns.loss_fn(p, batch, cfg))(params)
    assert bool(jnp.isfinite(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = registry.get_reduced_config(arch)
    fns = registry.model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    kind = registry.input_kind(arch)
    cache = fns.init_cache(cfg, 2, 32)
    shape = (2, cfg.n_codebooks, 1) if kind == "codebooks" else (2, 1)
    tok = jax.random.randint(jax.random.PRNGKey(1), shape, 0, cfg.vocab_size)
    logits, cache2 = fns.decode_step(params, cache, tok, cfg)
    expect = ((2, cfg.n_codebooks, cfg.vocab_size) if kind == "codebooks"
              else (2, cfg.vocab_size))
    assert logits.shape == expect
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert int(cache2["pos"]) == 1


# Published model sizes (total, active) — full configs, eval_shape only.
PARAM_BOUNDS = {
    "granite-moe-1b-a400m": (1.0e9, 1.7e9, 0.35e9, 0.55e9),
    "qwen3-moe-30b-a3b": (26e9, 34e9, 2.3e9, 3.8e9),
    "minicpm-2b": (2.2e9, 3.0e9, None, None),
    "stablelm-12b": (10e9, 13.5e9, None, None),
    "command-r-35b": (27e9, 37e9, None, None),
    "qwen2.5-32b": (29e9, 36e9, None, None),
    "qwen2-vl-2b": (1.2e9, 1.8e9, None, None),
    "xlstm-350m": (0.28e9, 0.45e9, None, None),
    "recurrentgemma-2b": (2.4e9, 3.2e9, None, None),
    "musicgen-medium": (1.1e9, 1.8e9, None, None),
    "moonlight-16b-a3b": (15.5e9, 16.5e9, 2.7e9, 3.2e9),
}


@pytest.mark.parametrize("arch", list(PARAM_BOUNDS))
def test_full_config_param_count(arch):
    cfg = registry.get_config(arch)
    lo, hi, alo, ahi = PARAM_BOUNDS[arch]
    n = cfg.param_count()
    assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params out of [{lo},{hi}]"
    if alo is not None:
        na = cfg.active_param_count()
        assert alo <= na <= ahi, f"{arch}: active {na/1e9:.2f}B"


def test_registry_cells():
    cells = registry.cells()
    # 10 archs x 4 shapes - 8 long_500k skips = 32 runnable cells
    assert len(cells) == 32
    assert ("xlstm-350m", "long_500k") in cells
    assert ("command-r-35b", "long_500k") not in cells

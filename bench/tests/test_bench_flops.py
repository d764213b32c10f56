"""Operations and bytes from shapes, against hand counts."""
import pytest

from bench import cells, flops
from repro.models import registry


def _cfg(name):
    return cells.resolve(name).config


def test_suncatcher_by_hand():
    c = _cfg("sun100m.chat")
    layer = 768 * 768 + 2 * 768 * 256 + 768 * 768 + 3 * 768 * 2048
    assert flops.layer_matmul_params(c) == layer == 6_291_456
    total = 32768 * 768 + 12 * (layer + 2 * 768) + 768
    assert flops.param_count(c) == total == 100_682_496
    # decode token at context 1000: matmuls + attention 4*L*H*hd*ctx
    assert flops.token_flops(c, 1000) == 2 * (12 * layer + 32768 * 768) \
        + 4 * 12 * 12 * 64 * 1000
    assert flops.kv_bytes_per_token(c) == 2 * 12 * 4 * 64 * 2 == 12_288


def test_minicpm_by_hand():
    c = _cfg("minicpm2b.batch")
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert flops.layer_matmul_params(c) == layer
    assert flops.param_count(c) == 122880 * 2304 + 5 * (layer + 2 * 2304) \
        + 2304
    assert flops.kv_bytes_per_token(c) == 2 * 5 * 36 * 64 * 2 == 46_080
    # prefill of 3 tokens: 3 tokens of matmuls, contexts 1+2+3, head once
    assert flops.prefill_flops(c, 3) == 3 * 2 * 5 * layer \
        + 4 * 5 * 36 * 64 * 6 + 2 * 122880 * 2304


@pytest.mark.parametrize("name", ["sun100m.chat", "minicpm2b.batch"])
def test_param_count_matches_the_program(name):
    c = _cfg(name)
    cfg = registry.get_config(c["registry_id"], n_layers=c["n_layers"])
    assert flops.param_count(c) == cfg.param_count()


def test_decode_substep_bytes_and_roofline():
    c = _cfg("minicpm2b.batch")
    f, b = flops.decode_substep(c, [100, 200])
    kv = flops.kv_bytes_per_token(c)
    assert b == flops.weight_bytes(c) + (101 + 201) * kv
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    assert flops.least_time_s(f, b, peak) == pytest.approx(
        max(f / 1e12, b / 1e9))

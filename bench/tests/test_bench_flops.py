"""Operations and bytes from shapes (bench/counts/), against hand counts."""
import pytest

from bench import cells
from bench.counts import least_time_s
from repro.models import registry

dense = cells.counts("dense_lm")


def _cfg(name):
    return cells.resolve(name).config


def test_suncatcher_by_hand():
    c = _cfg("sun100m.chat")
    layer = 768 * 768 + 2 * 768 * 256 + 768 * 768 + 3 * 768 * 2048
    assert dense.layer_matmul_params(c) == layer == 6_291_456
    total = 32768 * 768 + 12 * (layer + 2 * 768) + 768
    assert dense.param_count(c) == total == 100_682_496
    # decode token at context 1000: matmuls + attention 4*L*H*hd*ctx
    assert dense.token_flops(c, 1000) == 2 * (12 * layer + 32768 * 768) \
        + 4 * 12 * 12 * 64 * 1000
    assert dense.kv_bytes_per_token(c) == 2 * 12 * 4 * 64 * 2 == 12_288


def test_minicpm_by_hand():
    c = _cfg("minicpm2b.batch")
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert dense.layer_matmul_params(c) == layer
    assert dense.param_count(c) == 122880 * 2304 + 5 * (layer + 2 * 2304) \
        + 2304
    assert dense.kv_bytes_per_token(c) == 2 * 5 * 36 * 64 * 2 == 46_080
    # prefill of 3 tokens: 3 tokens of matmuls, contexts 1+2+3, head once
    assert dense.prefill_flops(c, 3) == 3 * 2 * 5 * layer \
        + 4 * 5 * 36 * 64 * 6 + 2 * 122880 * 2304


@pytest.mark.parametrize("name", ["sun100m.chat", "minicpm2b.batch"])
def test_param_count_matches_the_program(name):
    c = _cfg(name)
    cfg = registry.get_config(c["registry_id"], n_layers=c["n_layers"])
    assert dense.param_count(c) == cfg.param_count()


# What the dense counts gave each configuration when they came to be chosen
# by family, kept so that any change to them shows: prefill of 256 and
# 1000, a token at contexts 1 and 2047, a decode sub-step of rows at 1, 300
# and 2047.
BEFORE = {
    "sun100m.chat": (39917715456, 169495707648, 201363456, 276787200,
                     (690536448, 230254080)),
    "minicpm2b.batch": (158361845760, 634097111040, 1176744960, 1271024640,
                        (3638292480, 1285083648)),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_familys_counts_are_bitwise_what_they_were(name):
    c = _cfg(name)
    fam = cells.counts(c["family"])
    got = (fam.prefill_flops(c, 256), fam.prefill_flops(c, 1000),
           fam.token_flops(c, 1), fam.token_flops(c, 2047),
           fam.decode_substep(c, [1, 300, 2047]))
    assert got == BEFORE[name]


def test_decode_substep_bytes_and_roofline():
    c = _cfg("minicpm2b.batch")
    f, b = dense.decode_substep(c, [100, 200])
    kv = dense.kv_bytes_per_token(c)
    assert b == dense.weight_bytes(c) + (101 + 201) * kv
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    assert least_time_s(f, b, peak) == pytest.approx(
        max(f / 1e12, b / 1e9))

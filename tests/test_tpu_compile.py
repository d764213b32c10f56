"""Compile the Pallas kernels of the main path for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks the kernels' arithmetic but
not what the TPU compiler accepts: block shapes against the tiling rules,
VMEM use, 1-D values. These tests lower each kernel at the serving and
training widths of suncatcher-lm-100m (the paged decode kernel also at
MiniCPM-2B's, and the RG-LRU width of recurrentgemma-2b) and compile it
for one chip of a `v5e:2x2` topology, which the installed TPU compiler
can do without a chip attached, and check that the serving engine's paged
decode step, compiled for that chip, calls the kernel. Nothing runs. All
of them live in this one file, behind module fixtures, so that only the
worker that runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.models import registry

LM = registry.get_config("suncatcher-lm-100m")
SLOTS, MAX_LEN, PAGE = 32, 1024, 16          # serving widths
BATCH, SEQ = 8, 1024                          # training widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory on one chip of the described topology,
    with the persistent compilation cache off: entries compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles(sds):
    from repro.kernels.decode_attention.ops import decode_attention
    dt = LM.cdtype
    cache = sds((SLOTS, MAX_LEN, LM.n_kv_heads, LM.hd), dt)
    _assert_kernel(decode_attention, sds((SLOTS, 1, LM.n_heads, LM.hd), dt),
                   cache, cache, sds((SLOTS,), jnp.int32))


def _compile_paged(sds, cfg, slots, pages, pool_pages):
    from repro.kernels.decode_attention.paged import paged_decode_attention
    dt = cfg.cdtype
    pool = sds((pool_pages + 1, PAGE, cfg.n_kv_heads, cfg.hd), dt)
    _assert_kernel(paged_decode_attention,
                   sds((slots, 1, cfg.n_heads, cfg.hd), dt), pool, pool,
                   sds((slots, pages), jnp.int32), sds((slots,), jnp.int32))


def test_paged_decode_attention_compiles(sds):
    pages = MAX_LEN // PAGE
    _compile_paged(sds, LM, SLOTS, pages, SLOTS * pages)


def test_paged_decode_attention_compiles_at_minicpm_widths(sds):
    """The batch cell's decode: 32 slots of 128 pages on a 1024-page pool,
    36 MHA heads of 64, bf16."""
    _compile_paged(sds, registry.get_config("minicpm-2b"), 32, 128, 1024)


def _decode_block_hlo(sds, monkeypatch, cfg, pool_pages=None):
    """The serving engine's paged decode block (4 slots of 128 positions
    in pages of PAGE, 2 sub-steps) compiled for the chip: (engine, HLO
    text). The kernel is chosen by `jax.default_backend()`, which here
    names the CPU, so the lowering names the TPU; the parameters are
    shapes only."""
    from repro.serving import EngineConfig, ServingEngine

    fns = registry.model_fns(cfg)
    params = jax.eval_shape(lambda k: fns.init(k, cfg), jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=4, max_len=128, decode_block=2,
                                     page_size=PAGE, pool_pages=pool_pages))
    on_chip = lambda t: jax.tree.map(lambda a: sds(a.shape, a.dtype), t)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return eng, jax.jit(eng._engine_step_impl).lower(
        on_chip(eng.params), on_chip(eng.cache), on_chip(eng.state)
    ).compile().as_text()


def test_paged_decode_step_runs_the_kernel(sds, monkeypatch):
    """The serving engine's paged decode step, compiled for the chip, calls
    the paged kernel and never gathers the rows' page tables into the
    dense (B, max_pages, page_size, Hkv, dh) layout."""
    eng, hlo = _decode_block_hlo(sds, monkeypatch, registry.get_config(
        "suncatcher-lm-100m", n_layers=2, vocab_size=1024))
    (b, mp), kp = eng.cache["ptab"].shape, eng.cache["kp"].shape
    assert "tpu_custom_call" in hlo
    assert f"[{b},{mp},{kp[2]},{kp[3]},{kp[4]}]" not in hlo


def test_paged_latent_attention_compiles_at_moonlight_widths(sds):
    """The moonlight16b.batch64 cell's latent decode: 32 slots of 128 pages
    on a 4096-page pool of [c_kv | k_pe] rows (576), 16 query heads."""
    from repro.kernels.decode_attention.paged import paged_latent_attention
    dt = jnp.bfloat16
    _assert_kernel(
        lambda q, p, t, n: paged_latent_attention(q, p, t, n,
                                                  sm_scale=192 ** -0.5,
                                                  v_dim=512),
        sds((32, 16, 576), dt), sds((4097, PAGE, 576), dt),
        sds((32, 128), jnp.int32), sds((32,), jnp.int32))


def test_paged_latent_decode_step_runs_the_kernel(sds, monkeypatch):
    """The engine's paged decode step of the latent-attention model,
    compiled for the chip, calls the latent kernel and never gathers the
    rows' pages into the dense (B, max_pages * page_size, 576) layout."""
    _, hlo = _decode_block_hlo(sds, monkeypatch, registry.get_config(
        "moonlight-16b-a3b", n_layers=2, vocab_size=1024, experts_held=8))
    assert "paged_latent_attention" in hlo and "tpu_custom_call" in hlo
    assert f"[4,{128 // PAGE},{PAGE},576]" not in hlo
    assert "[4,128,576]" not in hlo


def _computations(hlo):
    """{name: text} of every computation of an HLO module's text."""
    out = {}
    for block in re.split(r"\n(?=\S)", hlo):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+)", block)
        if head:
            out[head.group(1)] = block
    return out


def _in_loops(comps):
    """Names of the computations that run inside a `while` body: the
    bodies and all they call, fused computations included."""
    todo = [b for text in comps.values()
            for b in re.findall(r"body=%([\w.\-]+)", text)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)",
                           comps[name])
    return seen


def _dims(shape):
    """The dims of an HLO shape string or a shape tuple, leading 1s
    dropped."""
    dims = tuple(int(d) for d in (shape.split(",") if isinstance(shape, str)
                                  else shape) if d)
    while dims[:1] == (1,):
        dims = dims[1:]
    return dims


@pytest.mark.parametrize("arch,extra,pool_pages", [
    ("minicpm-2b", {}, 512), ("moonlight-16b-a3b", {"experts_held": 8}, 4096)])
def test_paged_decode_updates_the_stacked_pools_in_place(
        sds, monkeypatch, arch, extra, pool_pages):
    """The engine's paged decode block at three layers, compiled for the
    chip, keeps each stacked pool (L, P+1, ps, ...) in one buffer: no copy,
    dynamic-slice or dynamic-update-slice (nor their asynchronous forms)
    inside a `while` body (the sub-step scan, the layer scan, the fusions
    they call) has the shape of a stacked pool or of one layer's pool
    (P+1, ps, ...), and every paged kernel call reads a stacked pool.
    Copies at the program's entry and exit, of the pool argument that is
    not donated and into the layout the caller holds, are outside every
    loop and allowed. The pools are too large (75-150 MB) for XLA to stage
    them through the chip's VMEM, as the cells' pools are."""
    from repro.models.transformer import cache_names

    cfg = registry.get_config(arch, n_layers=3, vocab_size=1024, **extra)
    eng, hlo = _decode_block_hlo(sds, monkeypatch, cfg, pool_pages)
    pools = [eng.cache[c + "p"].shape for c in cache_names(cfg)]
    stacked = {_dims(p) for p in pools}
    banned = stacked | {_dims(p[1:]) for p in pools}
    comps = _computations(hlo)
    loops = _in_loops(comps)
    assert loops, "no while body"
    op = re.compile(r"%([\w.\-]+) = \(*\w+\[([\d,]*)\]\S* "
                    r"(copy|copy-start|dynamic-slice|slice-start|"
                    r"dynamic-update-slice)\(")
    found = [m.groups() for name in loops for m in op.finditer(comps[name])
             if _dims(m.group(2)) in banned]
    assert not found, found
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo))
    calls = re.findall(r"custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", hlo)
    assert calls
    for operands in calls:
        assert {_dims(shapes[n]) for n in re.findall(r"%([\w.\-]+)",
                                                     operands)
                } & stacked, operands


def test_flash_attention_compiles(sds):
    from repro.kernels.flash_attention.ops import flash_attention
    dt = LM.cdtype
    kv = sds((BATCH, SEQ, LM.n_kv_heads, LM.hd), dt)
    _assert_kernel(lambda q, k, v: flash_attention(q, k, v, causal=True),
                   sds((BATCH, SEQ, LM.n_heads, LM.hd), dt), kv, kv)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_compiles(sds, dtype):
    from repro.kernels.rglru_scan.ops import rglru_scan
    d = registry.get_config("recurrentgemma-2b").d_model
    x = sds((4, SEQ, d), dtype)
    _assert_kernel(rglru_scan, x, x)

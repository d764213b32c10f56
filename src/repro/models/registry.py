"""Architecture registry: --arch <id> -> (config, model functions, shapes)."""
from __future__ import annotations

import importlib
from dataclasses import replace
from types import SimpleNamespace

from .rglru import RGLRUConfig
from .transformer import TransformerConfig
from .xlstm import XLSTMConfig

ARCH_IDS = [
    "granite-moe-1b-a400m",
    "qwen3-moe-30b-a3b",
    "moonlight-16b-a3b",
    "minicpm-2b",
    "stablelm-12b",
    "command-r-35b",
    "qwen2.5-32b",
    "qwen2-vl-2b",
    "xlstm-350m",
    "recurrentgemma-2b",
    "musicgen-medium",
    # the paper's own end-to-end demo model (examples/train_100m.py)
    "suncatcher-lm-100m",
]

# The LM shape suite (assignment): name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}

# Sub-quadratic archs run long_500k; pure full-attention archs skip it
# (docs/ARCHITECTURE.md, "Model trunk").
SUBQUADRATIC = {"xlstm-350m", "recurrentgemma-2b"}


# config dataclass -> model module; dispatch is isinstance-based so MoE /
# VLM / audio configs (all TransformerConfig) share the transformer module
_FAMILIES = {
    XLSTMConfig: "repro.models.xlstm",
    RGLRUConfig: "repro.models.rglru",
    TransformerConfig: "repro.models.transformer",
}


def model_fns(cfg) -> SimpleNamespace:
    """Dispatch config dataclass -> its model module's uniform interface.

    Every family exposes: init / forward / loss_fn, the serving pair
    init_cache(cfg, batch, max_len, dtype=None) / decode_step, and
    decode_spec (models/decode_state.py) — the per-slot DecodeState spec
    the serving engine and migration plane are written against."""
    for klass, modname in _FAMILIES.items():
        if isinstance(cfg, klass):
            mod = importlib.import_module(modname)
            break
    else:
        raise KeyError(
            f"no model family registered for config type "
            f"{type(cfg).__name__}; registered families: "
            f"{sorted(k.__name__ for k in _FAMILIES)}")
    from repro.models.decode_state import decode_spec
    return SimpleNamespace(init=mod.init_params, forward=mod.forward,
                           loss_fn=mod.loss_fn, init_cache=mod.init_cache,
                           decode_step=mod.decode_step,
                           decode_spec=decode_spec)


def get_config(arch: str, **overrides):
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_").replace(".", "_"))
    cfg = mod.config()
    if overrides:
        cfg = replace(cfg, **overrides)
    return cfg


def get_reduced_config(arch: str, **overrides):
    """Tiny same-family config for CPU smoke tests."""
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_").replace(".", "_"))
    cfg = mod.reduced()
    if overrides:
        cfg = replace(cfg, **overrides)
    return cfg


def input_kind(arch: str) -> str:
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_").replace(".", "_"))
    return getattr(mod, "INPUT_KIND", "tokens")


def shape_applicable(arch: str, shape: str) -> bool:
    if shape == "long_500k" and arch not in SUBQUADRATIC:
        return False  # quadratic KV at 524k tokens: skipped per assignment
    return True


# Archs outside the dry-run shape suite: the paper's demo model, and
# Moonlight, whose latent attention and held experts serve one chip's share
# and carry no mesh sharding hints.
NOT_IN_SHAPE_SUITE = {"suncatcher-lm-100m", "moonlight-16b-a3b"}


def cells(archs=None):
    """All runnable (arch, shape) dry-run cells."""
    archs = archs or [a for a in ARCH_IDS if a not in NOT_IN_SHAPE_SUITE]
    return [(a, s) for a in archs for s in SHAPES if shape_applicable(a, s)]

"""Finds the architecture a configuration runs, by name.

A configuration file (``bench/configs/<config>.json``) may name
``"arch": "<name>"``; without the key it runs ``gqa_moe``.  The module
``bench/archs/<name>.py`` gives what the benchmark needs of that
architecture, and nothing here names one:

- ``sizes(conf)``: a hashable object of the sizes the benchmark reads,
  with at least ``vocab``, ``d_model``, ``n_moe``, ``n_experts``,
  ``top_k``, ``zipf_a``, ``embed_rms`` and ``id_noise_share`` (the
  structured router draw, ``bench/harness/weights.py``);
- ``model_config(name, sizes)``: the program's ``ModelConfig``;
- ``layout(sizes)``: path -> (shape, dtype, std) of every parameter;
- ``forward(params, sizes, tokens, fp4, rows, pad_to, mode)``: the plain
  float32 reference (``fp4 [T, n_moe, ep]``: the EP ranks that ran a
  position's MoE layer in FP4);
- ``prefill_flops``, ``fp4_ffn_work``, ``quantize_work``: the counts the
  per-layer readers use.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, Tuple

DEFAULT = "gqa_moe"
ROOT = Path(__file__).resolve().parents[2]      # the checkout

_LOADED: Dict[Path, ModuleType] = {}


def module(conf: dict, root: Path = ROOT) -> ModuleType:
    """The architecture module that ``conf`` names."""
    name = conf.get("arch", DEFAULT)
    path = (root / "bench" / "archs" / f"{name}.py").resolve()
    if path not in _LOADED:
        key = f"bench_arch_{len(_LOADED)}_" + name.replace("-", "_")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod          # dataclasses look their module up
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of(conf: dict, root: Path = ROOT) -> Tuple[ModuleType, object]:
    """(architecture module, sizes) of a configuration file."""
    mod = module(conf, root)
    return mod, mod.sizes(conf)

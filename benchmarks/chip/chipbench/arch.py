"""Each architecture's module, ``archs/<model_type>.py``, chosen by the
``model_type`` key of a configuration file.

A module defines ``program_config(conf)`` (the program's ModelConfig for
the file; it raises on what the program cannot express), ``Reference(params,
conf, quant=None)`` (the plain reference: ``prefix_kv`` and ``logits``, see
``archs/qwen3.py``), ``prefill_flops(conf, suffix, prefix)`` and
``decode_flops(conf, context)``.  A new architecture is a new module and a
configuration file, never an edit.

``Layout.cell`` loads its cell's module from the directories that
``BENCHMARK.json``'s ``paths`` lists and keeps it as ``Cell.arch``, so that
set-up fails on a ``model_type`` with none.
"""
from __future__ import annotations

import importlib.util
import pathlib
from types import ModuleType

SUB = "archs"


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(model_type: str, dirs) -> ModuleType:
    """The module ``archs/<model_type>.py`` in the first of ``dirs`` that
    holds one."""
    tried = [pathlib.Path(d) / SUB / f"{model_type}.py" for d in dirs]
    path = next((p for p in tried if p.is_file()), None)
    if path is None:
        raise FileNotFoundError(
            f"no module for model_type {model_type!r}: looked for "
            f"{', '.join(map(str, tried))}")
    return load_module(path, f"chipbench_arch_{model_type}")

"""Architecture registry: one module per assigned architecture.

Each module exposes ``config()`` (the exact published configuration) and
``smoke_config()`` (a reduced same-family configuration for CPU smoke
tests).  ``get_config(name)`` / ``get_smoke_config(name)`` dispatch by id.
"""
from __future__ import annotations

import importlib
from typing import List

ARCHS: List[str] = [
    "gemma2-2b",
    "yi-34b",
    "smollm-135m",
    "stablelm-12b",
    "musicgen-medium",
    "llama-3.2-vision-11b",
    "xlstm-125m",
    "arctic-480b",
    "deepseek-v2-236b",
    "deepseek-v2-lite",
    "zamba2-7b",
]


def _module(name: str):
    mod_name = name.replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod_name}")


def get_config(name: str):
    return _module(name).config()


def get_smoke_config(name: str):
    return _module(name).smoke_config()

"""Shared set-up of the chip benchmark: paths, the compile cache, and the
configuration files read from ``chipbench/configs/<name>.json`` (each
served by the architecture module its ``arch`` key names, ``archs.py``).

Importing this module puts the program's ``src`` directory on ``sys.path``
and points JAX's persistent compilation cache at a fixed directory inside
the checkout. Import it before JAX.
"""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")

os.makedirs(CACHE_DIR, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
# no eviction: the cache is the checkout's own
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def read_json(rel: str):
    with open(os.path.join(ROOT, rel) if not os.path.isabs(rel) else rel) as f:
        return json.load(f)


def config_file(name: str) -> dict:
    return read_json(os.path.join("chipbench", "configs", f"{name}.json"))


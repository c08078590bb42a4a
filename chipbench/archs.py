"""The architecture modules, ``chipbench/arch/<arch>.py``, each chosen by
the ``arch`` key of a configuration file (``dense`` where it has none).

An architecture joins the benchmark as one new file that provides:

- ``model_config(raw)``: the program's ``ModelConfig``;
- ``model_params(seed, raw)``: seeded weights in the program's layout,
  drawn on the device in one jitted call (``weights.lm_params``);
- ``score(seed, raw, policy, seqs, cap, n_out, controls=())``: the plain
  reference (``reference.teacher_forced``);
- ``KERNELS``: each Pallas kernel name its programs use -> kernel class;
- ``decode_need_s(raw, policy, lens, peaks)``: {kernel class: least
  seconds} of one decode launch whose live slots attend ``lens`` rows;
- ``decode_flops(raw, lens)``: the model FLOPs of that launch.
"""
from __future__ import annotations

import importlib.util
import os
import sys

DIRS = [os.path.join(os.path.dirname(os.path.abspath(__file__)), "arch")]


def load(raw: dict):
    """The architecture module of a configuration."""
    return by_name(raw.get("arch", "dense"))


def by_name(name: str):
    key = "chipbench_arch_" + name
    if key in sys.modules:
        return sys.modules[key]
    for d in DIRS:
        path = os.path.join(d, f"{name}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[key] = mod
            try:
                spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[key]
                raise
            return mod
    raise ValueError(f"no architecture module {name}.py in {DIRS}")

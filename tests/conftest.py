"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see the real device
count (only launch/dryrun.py forces 512 host devices)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def bench_data():
    """``(cfg, data)`` of a benchmark configuration at ``n_records``, made by
    its generator in ``perfbench/configs`` from ``seed``."""
    import importlib.util
    import json

    configs = os.path.join(os.path.dirname(__file__), "..", "perfbench",
                           "configs")

    def make(name: str, n_records: int, seed: int):
        spec = importlib.util.spec_from_file_location(
            f"_bench_config_{name}", os.path.join(configs, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        with open(os.path.join(configs, f"{name}.json")) as f:
            cfg = json.load(f)
        cfg["n_records"] = n_records
        return cfg, mod.generate(cfg, seed)

    return make

"""Traffic mixes: the data file of each mix and the general generator code
shared by the loop kinds.

A mix (``bench/traffic/<name>.json``) names its loop kind under
``generator``; ``bench/generators/<kind>.py`` drives the engine with it.
Every seed gets the same multiset of request sizes: evenly spaced
quantiles of the mix's clipped lognormal lengths.  The seed only orders
them and draws the prompt token ids, so two seeds offer the same work.
"""
from __future__ import annotations

import importlib
import json
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def generator(mix: dict):
    return importlib.import_module(f"bench.generators.{mix['generator']}")


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a lognormal with the
    spec's ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def max_context(mix: dict) -> int:
    """Longest prompt plus longest output: the engine's ``max_len``."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])

"""The one general generator: a cell's requests and arrivals from its seed.

What a request holds comes from the configuration (``graphs``: a family,
found by name in ``graphs/<kind>.py``, and its parameters); when and how
requests are sent comes from the traffic mix (``traffic/<mix>.json``):

* ``{"arrivals": "poisson", "rate_per_s": r}`` — an open loop. The run
  sends ``round(r · seconds)`` requests at fixed gaps: the quantiles
  ``-ln(1 - (i + 0.5) / N) / r`` of the exponential law, in a seeded order,
  so every seed offers the same gaps and only their order differs.
* ``{"arrivals": "closed", "outstanding": c}`` — a closed loop that keeps
  ``c`` requests in the engine.
* ``{"arrivals": "saturate"}`` — admits as fast as the engine takes
  requests, retrying a refused admission.

Every request draws its graph from the configuration's pool in a seeded
cycle (each pool graph in turn, reshuffled every cycle) and gets a key of
its own, so no two requests are the same and the result cache never hits.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def graph_family(kind: str):
    path = HERE / "graphs" / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no graph family {kind!r} under {path.parent}")
    spec = importlib.util.spec_from_file_location(f"graphs_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded(seed: int, stream: int):
    return np.random.default_rng([stream, seed])


def make_pool(config: dict, seed: int) -> list:
    """The configuration's pool of distinct graphs, as ``(n, edges)``."""
    graphs = config["graphs"]
    return graph_family(graphs["kind"]).make_pool(graphs, seeded(seed, 0))


def graph_order(pool_size: int, count: int, seed: int) -> np.ndarray:
    rng = seeded(seed, 1)
    cycles = -(-count // pool_size)
    return np.concatenate([rng.permutation(pool_size)
                           for _ in range(cycles)])[:count]


def request_keys(count: int, seed: int, stream: int = 2) -> np.ndarray:
    """``count`` raw ``uint32[2]`` PRNG keys."""
    return seeded(seed, stream).integers(
        0, 2**32, size=(count, 2), dtype=np.uint32)


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times (from the window's start) of an open loop."""
    count = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(count) + 0.5) / count) / rate
    return np.cumsum(seeded(seed, 3).permutation(gaps))


def request_budget(traffic: dict, seconds: float) -> int:
    """How many requests a run may send: every due request of an open loop,
    and for the other loops more than the engine can take in the window."""
    if traffic["arrivals"] == "poisson":
        return max(1, int(round(traffic["rate_per_s"] * seconds)))
    return int(math.ceil(traffic["max_rate_per_s"] * seconds))

"""The one traffic generator: closed-loop clients whose requests draw
their parameters from a traffic mix file (``bench/traffic/<mix>.json``).

A mix names the number of clients, the query parts of a request (which
the configuration turns into queries) and, per parameter, the list of its
values. Every parameter is drawn uniformly and independently for every
request. Client c's k-th request
depends only on the seed, c and k, never on timing. ``device_key`` gives
the configurations' data the same seed.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class Param:
    name: str
    values: tuple


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    clients: int
    parts: tuple
    params: tuple

    @staticmethod
    def load(path: Path) -> "Mix":
        raw = json.loads(Path(path).read_text())
        if raw["loop"] != "closed":
            raise ValueError(f"{path}: only closed-loop mixes are driven")
        params = [Param(name, tuple(values))
                  for name, values in raw["params"].items()]
        return Mix(Path(path).stem, raw["loop"], int(raw["clients"]),
                   tuple(raw["parts"]), tuple(params))


class ClientStream:
    """The request parameters of one client, in order."""

    def __init__(self, mix: Mix, seed: int, client: int):
        self.mix = mix
        self.rng = np.random.default_rng([int(seed), int(client)])

    def next(self) -> Dict[str, object]:
        return {p.name: p.values[int(self.rng.integers(len(p.values)))]
                for p in self.mix.params}


def device_key(seed: int):
    """A JAX key from any non-negative seed, also one past 32 bits."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)

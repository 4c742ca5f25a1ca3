"""Helpers for the CPU rehearsals: a cell at a tiny size, run through the
harness without the look for a chip (Pallas kernels in interpret mode)."""

import time

from bench import harness

TINY = {"wau_16m": {"n_users": 2 ** 14}}
SEED = 2 ** 33 + 5


def tiny_cell(name: str) -> harness.Cell:
    return harness.resolve(name, cfg_overrides=TINY[name.split(".")[0]])


def run(name: str, seconds: float = 1.0, seed: int = SEED) -> dict:
    return harness.run_cell(tiny_cell(name), seed, seconds, False,
                            time.perf_counter())

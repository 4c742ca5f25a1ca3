"""q6_window_compiles: window_compiles in the Q6 cell, whose 80
parameter sets are 80 programs; all of them are warmed before the
window opens, so this should read 0."""

from bench.metrics.window_compiles import read  # noqa: F401

"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached. These compiles
catch what interpret mode cannot - tiling the chip refuses, programs
that do not fit its memory - at the widths the chip path uses. The
topology is described inside a fixture, never at import, because only
one process at a time may load the TPU library; keep every such compile
in this one file.
"""

import inspect
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Expr, engine
from repro.kernels import (binary_matmul, bitweaving, bitwise, ops,
                           popcount)

GiB = 2 ** 30
X, Y = Expr.var("x"), Expr.var("y")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shapes on one described chip, with the persistent compile cache
    off: its entries for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                              sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_kernel(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel
    return compiled


def test_fused_bitwise_compiles(one_chip):
    a = one_chip(8, 2 ** 23)
    _compiled_kernel(bitwise.fused_bitwise, X & Y, ("x", "y"), a, a,
                     interpret=False)


def test_fused_bitwise_stacked_compiles(one_chip):
    a = one_chip(8, 8, 2 ** 20)
    _compiled_kernel(bitwise.fused_bitwise_stacked, X & Y, ("x", "y"), a,
                     a, interpret=False)


def test_popcount_rows_compiles(one_chip):
    _compiled_kernel(popcount.popcount_rows, one_chip(8, 2 ** 23),
                     interpret=False)


def test_bitweaving_scan_compiles(one_chip):
    _compiled_kernel(bitweaving.bitweaving_scan, one_chip(12, 2 ** 20),
                     100, 3000, interpret=False)


def test_stacked_epoch_fits_at_2_28_bits(one_chip, monkeypatch):
    """One serving epoch of 8 ``x & y`` queries over 2^28-bit bitmaps,
    as the DevicePlanner dispatches it, stays well inside the chip's
    16 GB (the 1-D operands are padded to 8 rows, so it needs ~6.75 GiB
    where the data is 0.75 GiB)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    # a fresh jit, so no trace cached with the interpreter is reused
    fn = engine._device_compiled_stacked.__wrapped__(
        X & Y, ("x", "y"), "pallas", 2 ** 28)
    a = one_chip(8, 1, 2 ** 23)
    mem = _compiled_kernel(fn, a, a).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 12 * GiB


def test_interpret_has_no_default():
    """Every kernel takes ``interpret`` explicitly: none falls back to
    the interpreter because a caller left the flag out."""
    for fn in (bitwise.fused_bitwise, bitwise.fused_bitwise_stacked,
               popcount.popcount_rows, bitweaving.bitweaving_scan,
               binary_matmul.binary_matmul):
        param = inspect.signature(fn).parameters["interpret"]
        assert param.default is inspect.Parameter.empty, fn.__name__

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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Expr, engine
from repro.kernels import bitweaving, bitwise, ops, popcount

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


def test_popcount_compiles_past_whole_blocks(one_chip, monkeypatch):
    """The runtime popcount of one TPC-H Q6 selection at SF30: 5,625,000
    words, which the wrapper pads to 5,625,088, half a 512-word block
    past the last whole one, so the kernel masks its last block."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    _compiled_kernel(jax.jit(ops.popcount), one_chip(5_625_000))


def test_bitweaving_scan_compiles(one_chip):
    _compiled_kernel(bitweaving.bitweaving_scan, one_chip(12, 2 ** 20),
                     100, 3000, interpret=False)


def test_stacked_epoch_fits_at_2_28_bits(one_chip, monkeypatch):
    """One serving epoch of 8 ``x & y`` queries over 2^28-bit bitmaps,
    as the DevicePlanner dispatches it (16 operands in their stored
    shape, job-major), stays well inside the chip's 16 GB (the 1-D
    operands are stacked lane-dense inside the program, so it needs ~2
    GiB where the data is 0.75 GiB; padded to 8 rows it needed ~6.75)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    # a fresh jit, so no trace cached with the interpreter is reused
    fn = engine._device_compiled_stacked.__wrapped__(
        X & Y, ("x", "y"), "pallas", 2 ** 28)
    operands = [one_chip(2 ** 23)] * (8 * 2)
    mem = _compiled_kernel(fn, *operands).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 12 * GiB


def _q6_predicate():
    """TPC-H Q6's predicate over its 22 resident planes (SF30's widths)."""
    from repro.apps.bitweaving_db import conjunction_plan
    planes = {"l_shipdate": range(12), "l_discount": range(4),
              "l_quantity": range(6)}
    expr, env = conjunction_plan(planes, (("l_shipdate", 366, 730),
                                          ("l_discount", 5, 7),
                                          ("l_quantity", 0, 23)))
    return expr, tuple(sorted(env))


def _and_of_weeks(weeks):
    """Ambit's Section 8.1 query: the AND of ``weeks`` 7-day ORs."""
    names = tuple(f"d{i}" for i in range(7 * weeks))
    v = [Expr.var(nm) for nm in names]
    expr = None
    for w in range(weeks):
        week = v[7 * w]
        for day in v[7 * w + 1:7 * w + 7]:
            week = week | day
        expr = week if expr is None else expr & week
    return expr, names


@pytest.mark.parametrize("query,words", [
    (_q6_predicate, 5_625_000),             # TPC-H Q6 over SF30 lineitem
    (lambda: _and_of_weeks(4), 2 ** 19),    # wau_16m, 4 weeks
], ids=["q6_22_operands", "wau_28_operands"])
def test_one_row_query_reads_its_operands_in_place(one_chip, monkeypatch,
                                                   query, words):
    """A served query over one-row operands compiles for the chip with
    its blocks inside the kernel's VMEM budget, and reads its operands in
    place: the program needs no temporaries to speak of (the 8-row pad of
    Q6's 22 operands needed 3.86 GiB)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    expr, names = query()
    block, = bitwise.block_shape(len(names), (words,))
    assert 2 * (len(names) + 1) * block * 4 <= bitwise.VMEM_BUDGET
    fn = engine._device_compiled.__wrapped__(expr, names, "pallas",
                                             32 * words, None)
    mem = _compiled_kernel(fn, *[one_chip(words)] * len(names)
                           ).memory_analysis()
    assert mem.temp_size_in_bytes < GiB


@pytest.mark.parametrize("program", ["ambit_query", "ambit_epoch"])
@pytest.mark.parametrize("words", [1, 127, 512, 513, 1025, 70_001])
def test_one_row_programs_compile_at_any_word_count(one_chip, monkeypatch,
                                                    program, words):
    """XLA tiles a 1-D uint32 array of up to 512 words in 128 to 512
    words, and a longer one in 1024: both sides of that line, and word
    counts that fill no whole tile, compile for the chip."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    expr, names = ~(X & Y) ^ ~X, ("x", "y")
    if program == "ambit_query":
        fn = engine._device_compiled.__wrapped__(expr, names, "pallas",
                                                 32 * words - 5, None)
        operands = [one_chip(words)] * 2
    else:
        fn = engine._device_compiled_stacked.__wrapped__(
            expr, names, "pallas", 32 * words - 5)
        operands = [one_chip(words)] * (3 * 2)
    _compiled_kernel(fn, *operands)


def test_interpret_has_no_default():
    """Every kernel takes ``interpret`` explicitly: none falls back to
    the interpreter because a caller left the flag out."""
    for fn in (bitwise.fused_bitwise, bitwise.fused_bitwise_stacked,
               popcount.popcount_rows, bitweaving.bitweaving_scan):
        param = inspect.signature(fn).parameters["interpret"]
        assert param.default is inspect.Parameter.empty, fn.__name__


def _program_names(fn, *args, **static):
    """The compiled module's name and its Mosaic kernels' names."""
    text = fn.lower(*args, **static).compile().as_text()
    module = re.search(r"^HloModule (\S+?),", text, re.M).group(1)
    kernels = re.findall(r"%(\w+?)(?:\.\d+)? = \S+ custom-call\(.*"
                         r"custom_call_target=\"tpu_custom_call\"", text)
    return module, set(kernels)


@pytest.mark.parametrize("program,kernel", [
    ("fused_bitwise", "fused_bitwise"),
    ("fused_bitwise_stacked", "fused_bitwise_stacked"),
    ("popcount_rows", "popcount_rows"),
    ("bitweaving_scan", "bitweaving_scan"),
    ("ambit_query", "fused_bitwise"),
    ("ambit_epoch", "fused_bitwise_stacked"),
])
def test_trace_names_are_stable(one_chip, monkeypatch, program, kernel):
    """A profiler trace labels device work ``jit_<program>:<kernel>``:
    every pallas_call and both of the planner's jitted programs carry a
    name of their own, so the labels survive a refactor."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    xy = (X & Y, ("x", "y"))
    calls = {
        "fused_bitwise": lambda: (bitwise.fused_bitwise, *xy,
                                  one_chip(8, 2 ** 14), one_chip(8, 2 ** 14)),
        "fused_bitwise_stacked": lambda: (
            bitwise.fused_bitwise_stacked, *xy, one_chip(2, 8, 2 ** 12),
            one_chip(2, 8, 2 ** 12)),
        "popcount_rows": lambda: (popcount.popcount_rows,
                                  one_chip(8, 2 ** 14)),
        "bitweaving_scan": lambda: (bitweaving.bitweaving_scan,
                                    one_chip(12, 2 ** 14), 100, 3000),
        "ambit_query": lambda: (engine._device_compiled.__wrapped__(
            *xy, "pallas", 2 ** 19, None), one_chip(2 ** 14),
            one_chip(2 ** 14)),
        "ambit_epoch": lambda: (engine._device_compiled_stacked.__wrapped__(
            *xy, "pallas", 2 ** 19), *[one_chip(2 ** 14)] * (2 * 2)),
    }
    fn, *args = calls[program]()
    static = {} if program.startswith("ambit_") else {"interpret": False}
    module, kernels = _program_names(fn, *args, **static)
    assert module == f"jit_{program}"
    assert kernels == {kernel}

"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret mode on CPU) + randomized engine-invariant tests.

Engine-invariant property tests run under hypothesis when installed
(requirements-dev.txt pins it); otherwise they fall back to deterministic
seeded sweeps so collection never fails and coverage is preserved.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic fallbacks below keep coverage
    HAVE_HYPOTHESIS = False

from repro.core import BitVector, BulkBitwiseEngine
from repro.core import expr as E
from repro.core.bitvector import pack_bits, unpack_bits
from repro.kernels import bitwise, ops, ref

RNG = np.random.default_rng(3)


def rand_u32(shape):
    return jnp.asarray(RNG.integers(0, 2**32, shape, dtype=np.uint32))


X, Y, Z = E.Expr.var("x"), E.Expr.var("y"), E.Expr.var("z")
EXPRS = [X & Y, X ^ Y, ~X, ((X & Y) | ~Z) ^ (X | Y), E.maj(X, Y, Z)]


@pytest.mark.parametrize("shape", [(1, 7), (3, 130), (16, 512), (129,),
                                   (2, 3, 40)])
@pytest.mark.parametrize("expr", EXPRS, ids=[repr(e)[:30] for e in EXPRS])
def test_fused_bitwise_kernel(shape, expr):
    env = {k: rand_u32(shape) for k in "xyz"}
    got = ops.bitwise_eval(expr, env)
    assert got.dtype == jnp.uint32
    assert np.array_equal(np.asarray(got), np.asarray(ref.bitwise_eval(
        expr, env)))


def _chain(n_ops: int) -> tuple:
    """A ``~``-rooted expression over ``n_ops`` variables, so that any
    pad word the kernel computes turns to ones."""
    names = tuple(f"v{i:02d}" for i in range(n_ops))
    v = [E.Expr.var(nm) for nm in names]
    e = v[0]
    for i, x in enumerate(v[1:]):
        e = (e & x, e | x, e ^ x)[i % 3]
    return ~e, names


def _masked(words: np.ndarray, n_bits: int) -> np.ndarray:
    """``words`` with every bit past ``n_bits`` cleared."""
    out = words.copy()
    full, rem = divmod(n_bits, 32)
    out[..., full + (rem > 0):] = 0
    if rem:
        out[..., full] &= np.uint32((1 << rem) - 1)
    return out


def _run_path(path, expr, names, jobs, n_bits):
    """One result per job in ``jobs`` (lists of per-name arrays), through
    one of the pallas backend's entry points."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import engine
    from repro.kernels import bitwise
    if path == "bitwise_eval":
        return [ops.bitwise_eval(expr, dict(zip(names, job)))
                for job in jobs]
    if path == "kernel_tpu_interpret":   # past-the-end reads give garbage
        return [bitwise.fused_bitwise(expr, names, *job,
                                      interpret=pltpu.InterpretParams())
                for job in jobs]
    if path.startswith("query"):
        donate = 0 if path == "query_donated" else None
        fn = engine._device_compiled(expr, names, "pallas", n_bits, donate)
        # a donated buffer is gone after the call: give it a copy
        return [fn(*[jnp.array(a, copy=True) for a in job]) for job in jobs]
    fn = engine._device_compiled_stacked(expr, names, "pallas", n_bits)
    return list(fn(*[a for job in jobs for a in job]))


# (path, words, operands): every word count with two operands, and 1 and
# 22 operands (the TPC-H Q6 predicate's plane count) at two counts.
LANE_WORDS = (1, 127, 128, 1000, 1024, 1025, 11_111, 2 ** 14)
LANE_PATHS = ("bitwise_eval", "kernel_tpu_interpret", "query",
              "query_donated", "epoch2", "epoch3")
LANE_CASES = [(p, w, 2) for p in LANE_PATHS for w in LANE_WORDS] + \
    [(p, w, n) for p in LANE_PATHS for n in (1, 22) for w in (1025, 11_111)]


@pytest.mark.parametrize("path,words,n_ops", LANE_CASES)
def test_one_row_paths_match_reference(path, words, n_ops):
    """One-row operands through every pallas entry point (read in place
    by one launch, or stacked lane-dense by an epoch) give the numpy
    reference's words, tail-masked to an ``n_bits`` that is not a whole
    number of words wherever the path masks."""
    expr, names = _chain(n_ops)
    n_jobs = int(path[-1]) if path.startswith("epoch") else 1
    rng = np.random.default_rng(words * 31 + n_ops)
    jobs = [[rng.integers(0, 2**32, words, dtype=np.uint32)
             for _ in names] for _ in range(n_jobs)]
    n_bits = 32 * words - 5
    got = _run_path(path, expr, names,
                    [[jnp.asarray(a) for a in job] for job in jobs], n_bits)
    assert len(got) == n_jobs
    for g, job in zip(got, jobs):
        want = E.eval_expr(expr, dict(zip(names, job)))
        if path not in ("bitwise_eval", "kernel_tpu_interpret"):
            want = _masked(want, n_bits)
        assert g.shape == (words,) and g.dtype == jnp.uint32
        assert np.array_equal(np.asarray(g), want)


@pytest.mark.parametrize("rows,words,nbytes", [
    (1, 5_625_000, 5_625_856 * 4),    # TPC-H Q6 at SF30: 1.00015x
    (1, 2 ** 19, 2 ** 21),            # a 2^24-bit bitmap: no pad
    (1, 10, 1024 * 4),
    (8, 2 ** 19, 2 ** 24),
    (3, 130, 8 * 256 * 4),
])
def test_fused_operand_bytes_by_shape(rows, words, nbytes):
    """An operand's bytes as the fused kernel receives it, from its shape
    alone: one row in whole 1024-word tiles, more rows in whole (8, 128)
    tiles."""
    assert ops.fused_operand_bytes(rows, words) == nbytes


@pytest.mark.parametrize("shape", [(1, 1), (4, 100), (33, 257), (257, 8)])
def test_popcount_kernel(shape):
    a = rand_u32(shape)
    got = ops.popcount(a)
    assert np.array_equal(np.asarray(got), np.asarray(ref.popcount(a)))


@pytest.mark.parametrize("words", [640, 1000, 5 * 512 + 256, 1024, 2048])
@pytest.mark.parametrize("fill", ["ones", "random"])
def test_popcount_rows_counts_no_word_past_the_end(words, fill):
    """In TPU interpret mode a block that reaches past the array reads
    uninitialised words (all ones), as the chip reads whatever lies past
    the buffer: only words within the array may count, also where the
    word count is not a multiple of the 512-word tile."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import popcount
    x = jnp.full((8, words), 0xFFFFFFFF, jnp.uint32) if fill == "ones" \
        else rand_u32((8, words))
    got = popcount.popcount_rows(x, interpret=pltpu.InterpretParams())
    want = jax.lax.population_count(x).astype(jnp.int32).sum(1)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# the flat popcount's block: 786,432 words, for any array of more
BLOCK = bitwise.block_shape(1, (2 ** 24,))[0]


@pytest.mark.parametrize("words", [513, 1024, 1025, BLOCK - 1, BLOCK + 1,
                                   3 * BLOCK + 7])
@pytest.mark.parametrize("fill", ["ones", "random"])
def test_popcount_flat_counts_no_word_past_the_end(words, fill):
    """A one-row operand is read flat, in place: a block, and its last
    strip of (8, 128) words, may reach past the array, where TPU
    interpret mode reads uninitialised words (all ones) as the chip reads
    whatever lies there. Only the array's words may count, in one block
    or several, whole or not."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import popcount
    x = jnp.full((words,), 0xFFFFFFFF, jnp.uint32) if fill == "ones" \
        else rand_u32((words,))
    got = popcount.popcount_rows(x, interpret=pltpu.InterpretParams())
    assert got.shape == ()
    assert int(got) == int(ref.popcount(x))


@pytest.mark.parametrize("b,n", [(1, 32), (4, 64), (8, 320), (12, 1024),
                                 (16, 4096), (32, 96)])
def test_bitweaving_kernel(b, n):
    vals = RNG.integers(0, 2**b, n).astype(np.uint32)
    planes = ref.bitslice(jnp.asarray(vals), b)
    lo, hi = sorted(RNG.integers(0, 2**b, 2).tolist())
    got = ops.bitweaving_scan(planes, lo, hi)
    expect = ref.bitweaving_scan(planes, lo, hi)
    assert np.array_equal(np.asarray(got), np.asarray(expect))
    mask = np.asarray(unpack_bits(got, n))
    assert np.array_equal(mask, (vals >= lo) & (vals <= hi))


# -- engine invariants (randomized) -------------------------------------------
# Shared check bodies; hypothesis drives them when installed, deterministic
# seeded sweeps otherwise.


def check_engine_demorgan(a_bits, b_bits, backend):
    n = min(len(a_bits), len(b_bits))
    a = BitVector.from_bits(np.array(a_bits[:n], bool))
    b = BitVector.from_bits(np.array(b_bits[:n], bool))
    eng = BulkBitwiseEngine(backend)
    lhs = eng.nand(a, b).bits()
    rhs = eng.or_(~a, ~b).bits()
    assert np.array_equal(np.asarray(lhs), np.asarray(rhs))


def check_engine_xor_involution(a_bits):
    a = BitVector.from_bits(np.array(a_bits, bool))
    eng = BulkBitwiseEngine("jnp")
    twice = eng.xor(eng.xor(a, a), a).bits()
    assert np.array_equal(np.asarray(twice), np.array(a_bits, bool))


def check_engine_popcount_inclusion_exclusion(a_bits, b_bits):
    n = min(len(a_bits), len(b_bits))
    a = BitVector.from_bits(np.array(a_bits[:n], bool))
    b = BitVector.from_bits(np.array(b_bits[:n], bool))
    eng = BulkBitwiseEngine("jnp")
    pc = lambda v: int(eng.popcount(v))
    assert pc(eng.or_(a, b)) == pc(a) + pc(b) - pc(eng.and_(a, b))


def check_pack_unpack_roundtrip(bits):
    arr = np.array(bits, bool)
    bv = BitVector.from_bits(arr)
    assert np.array_equal(np.asarray(bv.bits()), arr)
    assert int(bv.popcount()) == int(arr.sum())


def _seeded_bits(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 201))
    return rng.integers(0, 2, n).astype(bool).tolist()


if HAVE_HYPOTHESIS:

    bit_arrays = st.integers(1, 200).flatmap(
        lambda n: st.lists(st.booleans(), min_size=n, max_size=n))

    @settings(max_examples=30, deadline=None)
    @given(bit_arrays, bit_arrays, st.sampled_from(["jnp", "pallas"]))
    def test_engine_demorgan(a_bits, b_bits, backend):
        check_engine_demorgan(a_bits, b_bits, backend)

    @settings(max_examples=30, deadline=None)
    @given(bit_arrays)
    def test_engine_xor_involution(a_bits):
        check_engine_xor_involution(a_bits)

    @settings(max_examples=30, deadline=None)
    @given(bit_arrays, bit_arrays)
    def test_engine_popcount_inclusion_exclusion(a_bits, b_bits):
        check_engine_popcount_inclusion_exclusion(a_bits, b_bits)

    @settings(max_examples=20, deadline=None)
    @given(bit_arrays)
    def test_pack_unpack_roundtrip(bits):
        check_pack_unpack_roundtrip(bits)

else:

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("seed", range(8))
    def test_engine_demorgan(seed, backend):
        check_engine_demorgan(_seeded_bits(3 * seed),
                              _seeded_bits(3 * seed + 1), backend)

    @pytest.mark.parametrize("seed", range(15))
    def test_engine_xor_involution(seed):
        check_engine_xor_involution(_seeded_bits(100 + seed))

    @pytest.mark.parametrize("seed", range(15))
    def test_engine_popcount_inclusion_exclusion(seed):
        check_engine_popcount_inclusion_exclusion(
            _seeded_bits(200 + 2 * seed), _seeded_bits(201 + 2 * seed))

    @pytest.mark.parametrize("seed", range(10))
    def test_pack_unpack_roundtrip(seed):
        check_pack_unpack_roundtrip(_seeded_bits(300 + seed))


def test_engine_backends_agree_on_majority():
    a, b, c = (BitVector.from_bits(RNG.integers(0, 2, 500).astype(bool))
               for _ in range(3))
    outs = []
    for backend in ("jnp", "pallas", "ambit_sim"):
        eng = BulkBitwiseEngine(backend)
        outs.append(np.asarray(eng.maj(a, b, c).bits()))
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


@pytest.mark.parametrize("amount", [-70, -64, -33, -32, -31, -1, 0, 1, 31,
                                    32, 33, 64, 70])
@pytest.mark.parametrize("n_bits", [1, 63, 64, 200])
def test_engine_shift_matches_numpy(n_bits, amount):
    """Section 9.1 future-op: logical shift over packed words."""
    rng = np.random.default_rng(n_bits * 1000 + amount)
    arr = rng.integers(0, 2, n_bits).astype(bool)
    eng = BulkBitwiseEngine("jnp")
    got = np.asarray(eng.shift(BitVector.from_bits(arr), amount).bits())
    want = np.zeros_like(arr)
    n = len(arr)
    if amount >= 0:
        if amount < n:
            want[amount:] = arr[:n - amount]
    else:
        if -amount < n:
            want[:n + amount] = arr[-amount:]
    assert np.array_equal(got, want), (amount, n)


def test_tmr_ecc_homomorphism_and_scrub():
    """Section 5.5: TMR is homomorphic over bitwise ops; majority decode
    corrects single-replica flips (and is itself one TRA)."""
    from repro.core.ecc import TMRCodec
    rng = np.random.default_rng(0)
    a = BitVector.from_bits(rng.integers(0, 2, 300).astype(bool))
    b = BitVector.from_bits(rng.integers(0, 2, 300).astype(bool))
    eng = BulkBitwiseEngine("jnp")
    codec = TMRCodec(eng)
    ea, eb = codec.encode(a), codec.encode(b)
    # op on encoded replicas == encode(op on plaintext)
    enc_res = codec.apply("xor", ea, eb)
    plain = eng.xor(a, b)
    assert np.array_equal(np.asarray(codec.decode(enc_res).bits()),
                          np.asarray(plain.bits()))
    # flip bits in ONE replica; scrub recovers
    corrupted = enc_res[0].data.at[0].set(enc_res[0].data[0] ^ 0xFF)
    enc_res[0] = BitVector(corrupted, enc_res[0].n_bits)
    clean, n_fixed = codec.scrub(enc_res)
    assert n_fixed == 8
    assert np.array_equal(np.asarray(codec.decode(clean).bits()),
                          np.asarray(plain.bits()))


@pytest.mark.parametrize("backend,interpret",
                         [("cpu", True), ("tpu", False), ("gpu", False)])
def test_interpreter_only_on_cpu_backend(monkeypatch, backend, interpret):
    """A backend other than the CPU compiles the kernels: nothing falls
    back to the Pallas interpreter because the backend is not a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret() is interpret

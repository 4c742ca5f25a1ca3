"""Accelerator-resident DeviceStore: differential + lifecycle suite.

The DeviceStore keeps operands on the accelerator across calls the way
PimStore keeps rows in simulated DRAM. The harness proves three things:

  * residency never changes WHAT is computed - random expression trees
    and chains over the resident path are bit-identical to the
    non-resident engine and to the ambit_sim device model, on both
    performance backends;
  * the ledger is honest - resident operands touch zero host bytes, only
    uploads/read-backs/spills/fault-ins are charged, and a drain's bytes
    accounting is identical to serial eval of the same queries;
  * multi-query drains fuse - an epoch of shape-compatible queries is
    ONE stacked kernel launch (the ``fused_dispatches`` counter), with
    results identical to serial evaluation.

Property tests run under hypothesis when installed; without it they fall
back to deterministic seeded sweeps over the same generators.
"""

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import AmbitError, BitVector, BulkBitwiseEngine, Expr, maj
from repro.core.engine import OpStats, device_compile_cache_info
from repro.kernels import ops as kops
from repro.pim import AmbitRuntime, DeviceStore

BACKENDS = ("jnp", "pallas")
RNG = np.random.default_rng(47)

X, Y, Z = Expr.var("x"), Expr.var("y"), Expr.var("z")


def rand_expr(rng, depth=0):
    if depth > 2 or rng.integers(2):
        return (X, Y, Z)[rng.integers(3)]
    op = ("and", "or", "xor", "not", "maj")[rng.integers(5)]
    if op == "not":
        return ~rand_expr(rng, depth + 1)
    if op == "maj":
        return maj(rand_expr(rng, depth + 1), rand_expr(rng, depth + 1),
                   rand_expr(rng, depth + 1))
    a, b = rand_expr(rng, depth + 1), rand_expr(rng, depth + 1)
    return {"and": a & b, "or": a | b, "xor": a ^ b}[op]


# -- differential: resident == non-resident == ambit_sim ----------------------


def check_resident_matches_engines(seed, backend):
    """Random exprs + a dependent chain: the DeviceStore path must be
    bit-identical to the non-resident engine (same backend) and to the
    ambit_sim device model, with ZERO host bytes for resident operands."""
    rng = np.random.default_rng(seed)
    n_bits = int(rng.integers(1, 700))
    rows = () if rng.integers(2) else (int(rng.integers(1, 4)),)
    bits = rng.integers(0, 2, (3,) + rows + (n_bits,)).astype(bool)
    vecs = {k: BitVector.from_bits(bits[i]) for i, k in enumerate("xyz")}

    rt = AmbitRuntime(backend=backend)
    hs = {k: rt.put(v) for k, v in vecs.items()}
    host_eng = BulkBitwiseEngine(backend)
    sim_eng = BulkBitwiseEngine("ambit_sim")

    for _ in range(3):
        expr = rand_expr(rng)
        if expr.op in ("var", "lit"):
            expr = expr ^ Y
        out = rt.eval(expr, hs)
        assert rt.last_stats.bytes_touched == 0     # fully resident
        got = np.asarray(rt.get(out).bits())
        want_host = np.asarray(host_eng.eval(expr, vecs).bits())
        want_sim = np.asarray(sim_eng.eval(expr, vecs).bits())
        assert np.array_equal(got, want_host), (backend, expr)
        assert np.array_equal(want_host, want_sim), expr
        rt.free(out)

    # dependent chain: intermediates never cross the channel
    reads0 = rt.store.host_reads
    acc = rt.eval(X ^ Y, {"x": hs["x"], "y": hs["y"]})
    for _ in range(3):
        acc = rt.eval(X & Y, {"x": acc, "y": hs["z"]})
    assert rt.store.host_reads == reads0
    want = np.asarray(vecs["x"].bits()) ^ np.asarray(vecs["y"].bits())
    for _ in range(3):
        want = want & np.asarray(vecs["z"].bits())
    assert np.array_equal(np.asarray(rt.get(acc).bits()), want)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.sampled_from(BACKENDS))
    def test_resident_matches_engines_random(seed, backend):
        check_resident_matches_engines(seed, backend)

else:

    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_resident_matches_engines_random(seed, backend):
        check_resident_matches_engines(seed, backend)


# -- multi-query drain: fused epochs ------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_drain_bit_identical_with_identical_bytes(backend):
    """submit+drain of a query mix == serial eval: same bits, same bytes
    accounting (both charge only fault-ins; here: none)."""
    rng = np.random.default_rng(5)
    bits = rng.integers(0, 2, (4, 500)).astype(bool)
    queries = [(X & Y, (0, 1)), (X ^ Y, (2, 3)),
               (~X, (1, 1)), (maj(X, Y, Z), (0, 2))]

    rt_s = AmbitRuntime(backend=backend)
    rt_a = AmbitRuntime(backend=backend)
    vs_s = [rt_s.put(BitVector.from_bits(b)) for b in bits]
    vs_a = [rt_a.put(BitVector.from_bits(b)) for b in bits]

    def env_for(expr, picks, vs):
        full = {k: vs[picks[i % len(picks)]] for i, k in enumerate("xyz")}
        return {nm: full[nm] for nm in sorted(full)
                if Expr.var(nm) in _vars(expr)}

    serial, serial_bytes = [], 0
    for expr, picks in queries:
        out = rt_s.eval(expr, env_for(expr, picks, vs_s))
        serial_bytes += rt_s.last_stats.bytes_touched
        serial.append(np.asarray(rt_s.get(out).bits()))

    tickets = [rt_a.submit(expr, env_for(expr, picks, vs_a))
               for expr, picks in queries]
    rt_a.drain()
    drain_bytes = rt_a.last_drain.stats.bytes_touched
    assert drain_bytes == serial_bytes == 0
    for t, want in zip(tickets, serial):
        assert t.state == "done"
        assert np.array_equal(np.asarray(rt_a.get(t.result).bits()), want)


def _vars(expr):
    seen = set()

    def walk(e):
        if e.op == "var":
            seen.add(e)
        for a in e.args:
            walk(a)
    walk(expr)
    return seen


def test_pallas_drain_launches_one_kernel_per_epoch():
    """The acceptance probe: shape-compatible same-expression queries
    drain as ONE epoch = ONE stacked pallas dispatch; a different
    expression forces a second epoch = a second dispatch."""
    rng = np.random.default_rng(9)
    rt = AmbitRuntime(backend="pallas")
    bits = rng.integers(0, 2, (4, 2, 300)).astype(bool)
    envs = []
    for q in range(4):
        a = rt.put(BitVector.from_bits(bits[q, 0]))
        b = rt.put(BitVector.from_bits(bits[q, 1]))
        envs.append({"x": a, "y": b})
    dispatches = rt.metrics.counter("fused_dispatches")
    launches0 = dispatches.total()
    tickets = [rt.submit(X & Y, env) for env in envs]
    odd = rt.submit(X | Y, envs[0])          # different expr: new epoch
    rt.drain()
    assert len(rt.last_drain.epochs) == 2
    assert [t.epoch for t in tickets] == [0, 0, 0, 0] and odd.epoch == 1
    assert dispatches.total() - launches0 == 2  # one pallas_call per epoch
    for t, b in zip(tickets, bits):
        assert np.array_equal(np.asarray(rt.get(t.result).bits()),
                              b[0] & b[1])
    assert np.array_equal(np.asarray(rt.get(odd.result).bits()),
                          bits[0, 0] | bits[0, 1])


def test_stacked_kernel_matches_per_query():
    """ops.bitwise_eval_stacked == one bitwise_eval per environment."""
    rng = np.random.default_rng(3)
    expr = (X & Y) | ~X
    envs = [{nm: rng.integers(0, 2**32, (5, 40), dtype=np.uint32)
             for nm in ("x", "y")} for _ in range(3)]
    got = kops.bitwise_eval_stacked(expr, ("x", "y"), envs)
    for g, env in zip(got, envs):
        want = kops.bitwise_eval(expr, env)
        assert np.array_equal(np.asarray(g), np.asarray(want))


def _shared_operand_epoch(rt, size, seed):
    """``size`` jobs of ``(x | ~y) & z`` over (2, 300)-bit operands: each
    job has its own x and y, and every job reads one shared z (as every
    week-and-gender query reads the one gender bitmap)."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (size, 2, 2, 300)).astype(bool)
    z = rt.put(BitVector.from_bits(rng.integers(0, 2, (2, 300)) > 0))
    envs = [{"x": rt.put(BitVector.from_bits(b[0])),
             "y": rt.put(BitVector.from_bits(b[1])), "z": z} for b in bits]
    return (X | ~Y) & Z, envs


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("size", (2, 3, 8))
def test_stacked_epoch_matches_execute_per_job(backend, size):
    """A stacked epoch whose jobs share an operand handle gives, job by
    job and in order, what one unstacked ``execute`` per job gives: the
    same words, shape and flags."""
    rt = AmbitRuntime(backend=backend)
    expr, envs = _shared_operand_epoch(rt, size, seed=size)
    jobs = [(expr, env, f"q{k}", None) for k, env in enumerate(envs)]
    got = rt.planner.execute_epoch(jobs)
    assert rt.planner.last_report.queries == size
    assert len(got) == size
    for k, (res, env) in enumerate(zip(got, envs)):
        want = rt.planner.execute(expr, env, out_name=f"q{k}")
        assert res._dev.shape == want._dev.shape == (2, env["x"].words32)
        assert (res.n_bits, res.shape, res.words32) == (
            want.n_bits, want.shape, want.words32)
        assert (res.dirty, res._private, res.name) == (True, True, f"q{k}")
        assert res.store is rt.store and not res.freed
        assert np.array_equal(np.asarray(res._dev), np.asarray(want._dev))


@pytest.mark.parametrize("backend", BACKENDS)
def test_stacked_epoch_stacks_and_slices_inside_its_program(
        backend, monkeypatch):
    """A stacked epoch calls its one jitted program once, and no
    ``jnp.stack``, slice or reshape runs on concrete arrays outside that
    program's trace."""
    from repro.pim import device_store
    rt = AmbitRuntime(backend=backend)
    expr, envs = _shared_operand_epoch(rt, 3, seed=5)
    jobs = [(expr, env, None, None) for env in envs]
    want = [rt.planner.execute(expr, env) for env in envs]
    calls = []
    real_compiled = device_store._device_compiled_stacked

    def counted(*key):
        fn = real_compiled(*key)

        def call(*arrays):
            calls.append(len(arrays))
            return fn(*arrays)
        return call

    def refuse_concrete(real):
        def guarded(*args, **kwargs):
            leaves = jax.tree_util.tree_leaves((args, kwargs))
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                raise AssertionError(f"eager {real.__name__} in an epoch")
            return real(*args, **kwargs)
        return guarded

    array_type = type(envs[0]["x"]._dev)
    monkeypatch.setattr(device_store, "_device_compiled_stacked", counted)
    monkeypatch.setattr(device_store.jnp, "stack",
                        refuse_concrete(device_store.jnp.stack))
    for method in ("__getitem__", "reshape"):
        monkeypatch.setattr(array_type, method,
                            refuse_concrete(getattr(array_type, method)))
    got = rt.planner.execute_epoch(jobs)
    got += rt.planner.execute_epoch(jobs[:2])
    monkeypatch.undo()
    assert calls == [3 * 3, 2 * 3]      # one call per epoch, job-major
    for res, w in zip(got, want + want[:2]):
        assert np.array_equal(np.asarray(res._dev), np.asarray(w._dev))


def _refuse_concrete(real):
    """``real``, raising where it is called on concrete arrays alone,
    i.e. eagerly, outside a jitted program's trace."""
    def guarded(*args, **kwargs):
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            raise AssertionError(f"eager {real.__name__} in a popcount")
        return real(*args, **kwargs)
    return guarded


def test_store_popcount_is_one_dispatch_that_reads_flat(monkeypatch):
    """On the pallas backend a count is one call of the jitted
    ``popcount_rows`` on the handle's buffer as it lies, with no eager
    reshape, pad or slice around it. A one-row handle of a length that
    is no multiple of a block or a strip counts exactly and bumps
    ``store_popcount_flat``; a multi-row handle does not."""
    from repro.kernels import popcount as kpc
    from repro.obs import STORE_POPCOUNT_FLAT
    rng = np.random.default_rng(18)
    store = DeviceStore("pallas")
    one = rng.integers(0, 2, 32 * 5001 + 13).astype(bool)
    two = rng.integers(0, 2, (2, 32 * 700 + 5)).astype(bool)
    h1, h2 = (store.put(BitVector.from_bits(b)) for b in (one, two))
    flat = store.metrics.counter(STORE_POPCOUNT_FLAT)
    calls = []
    real = kpc.popcount_rows

    def counted(x, **static):
        calls.append(x)
        return real(x, **static)

    array_type = type(h1._dev)
    monkeypatch.setattr(kpc, "popcount_rows", counted)
    monkeypatch.setattr(jax.numpy, "pad", _refuse_concrete(jax.numpy.pad))
    for method in ("__getitem__", "reshape"):
        monkeypatch.setattr(array_type, method,
                            _refuse_concrete(getattr(array_type, method)))
    got1 = store.popcount(h1)
    calls1, flat1 = list(calls), flat.value()
    got2 = store.popcount(h2)
    monkeypatch.undo()
    assert got1 == int(one.sum()) and got2 == int(two.sum())
    assert len(calls1) == 1 and calls1[0] is h1._dev and flat1 == 1
    assert len(calls) == 2 and calls[1] is h2._dev and flat.value() == 1


def _primitives(jaxpr) -> list:
    """Every primitive of ``jaxpr`` and of the jaxprs it holds, a
    pallas_call's kernel included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                names += _primitives(inner)
    return names


def test_store_popcount_program_has_one_kernel_and_no_pad():
    """The program a one-row count dispatches, for a (words,) buffer:
    the jitted ``popcount_rows`` alone at the top, and within it one
    pallas_call and no pad."""
    jaxpr = jax.make_jaxpr(kops.popcount)(
        jax.numpy.zeros(5001, jax.numpy.uint32)).jaxpr
    assert [(e.primitive.name, e.params.get("name")) for e in jaxpr.eqns] \
        == [("jit", "popcount_rows")]
    prims = _primitives(jaxpr)
    assert prims.count("pallas_call") == 1 and "pad" not in prims


def test_drain_dependency_and_out_rebind():
    """Ticket deps execute in earlier epochs; out= rebinds preserve the
    destination handle's identity (device-buffer move, no copy)."""
    rng = np.random.default_rng(11)
    rt = AmbitRuntime(backend="pallas")
    bits = rng.integers(0, 2, (3, 260)).astype(bool)
    a, b, o = (rt.put(BitVector.from_bits(x)) for x in bits)
    t1 = rt.submit(X & Y, {"x": a, "y": b})
    t2 = rt.submit(X ^ Y, {"x": t1, "y": a}, out=o)
    rt.drain()
    assert t1.epoch < t2.epoch
    assert t2.result is o and o.dirty
    want = (bits[0] & bits[1]) ^ bits[0]
    assert np.array_equal(np.asarray(rt.get(o).bits()), want)


# -- lifecycle: capacity budget, spill, pin -----------------------------------


def _nb_bytes(n_bits):
    return BitVector.from_bits(np.zeros(n_bits, bool)).nbytes


@pytest.mark.parametrize("backend", BACKENDS)
def test_capacity_budget_spills_lru_clean_for_free(backend):
    nb = 1024                                # 512 B packed
    rt = AmbitRuntime(backend=backend, capacity_bytes=2 * _nb_bytes(nb))
    bits = RNG.integers(0, 2, (3, nb)).astype(bool)
    a = rt.put(BitVector.from_bits(bits[0]))
    b = rt.put(BitVector.from_bits(bits[1]))
    c = rt.put(BitVector.from_bits(bits[2]))
    assert a.spilled and not b.spilled and not c.spilled
    assert rt.store.evicted_clean == 1 and rt.store.bytes_from_device == 0
    assert np.array_equal(np.asarray(rt.get(a).bits()), bits[0])  # free
    # eval over the spilled operand faults it back in, charged to the call
    out = rt.eval(X ^ Y, {"x": a, "y": c})
    assert rt.last_stats.bytes_touched >= a.device_bytes
    assert np.array_equal(np.asarray(rt.get(out).bits()),
                          bits[0] ^ bits[2])


def test_dirty_spill_reads_back_through_ledger():
    nb = 1024
    rt = AmbitRuntime(backend="jnp", capacity_bytes=3 * _nb_bytes(nb))
    bits = RNG.integers(0, 2, (2, nb)).astype(bool)
    a = rt.put(BitVector.from_bits(bits[0]))
    b = rt.put(BitVector.from_bits(bits[1]))
    out = rt.and_(a, b)                      # dirty result, store full
    rt.get(a), rt.get(b)                     # free touches: out is LRU
    down0 = rt.store.bytes_from_device
    rt.put(BitVector.from_bits(bits[0]))     # evicts out: dirty read-back
    assert out.spilled
    assert rt.store.evicted_dirty == 1
    assert rt.store.bytes_from_device - down0 == out.device_bytes
    assert np.array_equal(np.asarray(rt.get(out).bits()),
                          bits[0] & bits[1])


def test_pinned_never_evicted_and_held_faults_back():
    """Pinned handles are never victims (a full device raises instead);
    a held (queued) operand spills only as a capacity-pressure last
    resort and faults back in at drain, charged to its ticket."""
    nb = 1024
    rt = AmbitRuntime(backend="jnp", capacity_bytes=2 * _nb_bytes(nb))
    bits = RNG.integers(0, 2, (3, nb)).astype(bool)
    a = rt.put(BitVector.from_bits(bits[0]), pin=True)
    b = rt.put(BitVector.from_bits(bits[1]))
    t = rt.submit(~X, {"x": b})              # b held by the queue
    with pytest.raises(AmbitError, match="queued"):
        rt.free(b)
    rt.put(BitVector.from_bits(bits[2]))     # forces the held spill of b
    assert b.spilled and not a.spilled       # pinned a survived
    rt.drain()
    assert t.stats.bytes_touched >= b.device_bytes  # fault-in charged
    assert np.array_equal(np.asarray(rt.get(t.result).bits()), ~bits[1])
    # with everything pinned, capacity pressure must raise, not evict
    rt2 = AmbitRuntime(backend="jnp", capacity_bytes=_nb_bytes(nb))
    rt2.put(BitVector.from_bits(bits[0]), pin=True)
    with pytest.raises(AmbitError, match="pinned or in use"):
        rt2.put(BitVector.from_bits(bits[1]))


def test_freed_handle_raises():
    rt = AmbitRuntime(backend="jnp")
    a = rt.put(BitVector.from_bits(RNG.integers(0, 2, 64).astype(bool)))
    rt.free(a)
    assert a.freed
    with pytest.raises(AmbitError, match="freed"):
        rt.get(a)
    with pytest.raises(AmbitError, match="freed"):
        rt.eval(~X, {"x": a})


def test_store_rejects_foreign_and_sim_backends():
    with pytest.raises(ValueError, match="PimStore"):
        DeviceStore(backend="ambit_sim")
    rt1 = AmbitRuntime(backend="jnp")
    rt2 = AmbitRuntime(backend="jnp")
    a = rt1.put(BitVector.from_bits(RNG.integers(0, 2, 64).astype(bool)))
    with pytest.raises(AmbitError, match="another store"):
        rt2.get(a)


def test_eval_out_rebind_in_place():
    """eval(out=) rebinds the result into an existing handle: identity
    preserved, zero host traffic, correct bits (the donation path when
    the destination is an operand of the expression)."""
    rng = np.random.default_rng(21)
    for backend in BACKENDS:
        rt = AmbitRuntime(backend=backend)
        bits = rng.integers(0, 2, (2, 300)).astype(bool)
        acc = rt.put(BitVector.from_bits(bits[0]))
        w = rt.put(BitVector.from_bits(bits[1]))
        got = rt.eval(X & Y, {"x": acc, "y": w}, out=acc)
        assert got is acc and acc.dirty
        assert rt.last_stats.bytes_touched == 0
        assert np.array_equal(np.asarray(rt.get(acc).bits()),
                              bits[0] & bits[1])


def test_spilled_handles_hold_no_device_references():
    """Spill must genuinely release the accelerator: the surviving host
    copy is materialized as a numpy array (not a wrapper around the
    device buffer), for clean and dirty victims alike - otherwise the
    capacity budget would not bound device memory."""
    nb = 1024
    rt = AmbitRuntime(backend="jnp", capacity_bytes=2 * _nb_bytes(nb))
    bits = RNG.integers(0, 2, (2, nb)).astype(bool)
    a = rt.put(BitVector.from_bits(bits[0]))
    b = rt.put(BitVector.from_bits(bits[1]))
    rt.store.spill(a)                        # clean victim
    assert a._dev is None and isinstance(a._host.data, np.ndarray)
    assert np.array_equal(np.asarray(rt.get(a).bits()), bits[0])
    out = rt.and_(rt.store.ensure_resident(a), b)   # dirty result
    rt.store.spill(out)
    assert out._dev is None and isinstance(out._host.data, np.ndarray)
    assert np.array_equal(np.asarray(rt.get(out).bits()),
                          bits[0] & bits[1])


def test_donation_restricted_to_store_private_buffers():
    """put() shares the caller's buffer, so it must never be donated to
    XLA (the caller's BitVector would be invalidated); planner results
    are store-created and donation-eligible."""
    rt = AmbitRuntime(backend="jnp")
    bits = RNG.integers(0, 2, (2, 300)).astype(bool)
    a = rt.put(BitVector.from_bits(bits[0]))
    w = rt.put(BitVector.from_bits(bits[1]))
    assert not a._private
    rt.eval(X & Y, {"x": a, "y": w}, out=a)  # must not donate a's buffer
    assert rt.planner.last_report.donated == 0
    assert a._private                        # now holds a result buffer
    rt.eval(X ^ Y, {"x": a, "y": w}, out=a)  # eligible (CPU skips the
    assert np.array_equal(                   # actual donation, but the
        np.asarray(rt.get(a).bits()),        # plumbing selects the slot)
        (bits[0] & bits[1]) ^ bits[1])


def test_compile_cache_reuses_jitted_callables():
    """Repeated evals of one expression shape hit the jitted-callable
    LRU (the _compile_cached mirror), not a fresh trace per call."""
    rt = AmbitRuntime(backend="jnp")
    bits = RNG.integers(0, 2, (2, 200)).astype(bool)
    a = rt.put(BitVector.from_bits(bits[0]))
    b = rt.put(BitVector.from_bits(bits[1]))
    rt.eval(X & Y, {"x": a, "y": b})
    single0, _ = device_compile_cache_info()
    rt.eval(X & Y, {"x": a, "y": b})
    single1, _ = device_compile_cache_info()
    assert single1.hits == single0.hits + 1
    assert single1.misses == single0.misses


# -- engine ledger regression (stale last_stats) ------------------------------


@pytest.mark.parametrize("backend", ("jnp", "pallas", "ambit_sim"))
def test_engine_entry_points_set_fresh_stats(backend):
    """shift/popcount used to leave the PREVIOUS call's ledger in
    last_stats, so app accumulators silently double-merged the prior op's
    DRAM cost. Every public entry point must now report its own ledger."""
    eng = BulkBitwiseEngine(backend)
    bits = RNG.integers(0, 2, (2, 300)).astype(bool)
    a = BitVector.from_bits(bits[0])
    b = BitVector.from_bits(bits[1])
    eng.and_(a, b)
    and_stats = eng.last_stats
    assert and_stats.bytes_touched > 0
    eng.popcount(a)
    assert eng.last_stats is not and_stats
    assert eng.last_stats.ns == 0 and eng.last_stats.aap_count == 0
    eng.and_(a, b)
    mid = eng.last_stats
    eng.shift(a, 7)
    assert eng.last_stats is not mid
    assert eng.last_stats.aap_count == 0
    eng.shift(a, 0)                          # amount-0 fast path too
    assert eng.last_stats.bytes_touched == 2 * a.nbytes


# -- apps run unmodified on accelerator backends ------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_bitmap_index_weekly_on_device_backend(backend):
    from repro.apps.bitmap_index import BitmapIndex

    rng = np.random.default_rng(31)
    n_users = 1200
    weeks = [f"w{i}" for i in range(4)]
    host = BitmapIndex(n_users, BulkBitwiseEngine("jnp"))
    rt = AmbitRuntime(backend=backend)
    res = BitmapIndex(n_users, runtime=rt)
    for w in weeks + ["male"]:
        members = rng.choice(n_users, n_users // 3, replace=False)
        host.add(w, members)
        res.add(w, members)
    want_u, want_pw, _ = host.weekly_active_query(weeks, "male")
    got_u, got_pw, stats = res.weekly_active_query(weeks, "male")
    assert (got_u, got_pw) == (want_u, want_pw)
    assert rt.scheduler.drains == 1          # one batched drain
    assert rt.last_drain.n_queries == len(weeks) + 1
    assert stats.bytes_touched > 0           # the popcount read-backs


@pytest.mark.parametrize("backend", BACKENDS)
def test_bitfunnel_on_device_backend(backend):
    from repro.apps.bitfunnel import BitFunnelIndex

    docs = {0: ["apple", "banana"], 1: ["banana", "cherry"],
            2: ["apple", "cherry", "date"], 3: ["elderberry"]}
    rt = AmbitRuntime(backend=backend)
    idx = BitFunnelIndex(n_docs=4, filter_bits=256, runtime=rt)
    for d, terms in docs.items():
        idx.add_document(d, terms)
    idx.freeze(pin=True)
    for query, must in ((["apple"], {0, 2}), (["banana"], {0, 1}),
                        (["apple", "cherry"], {2})):
        got = set(idx.query(query).tolist())
        assert must <= got


@pytest.mark.parametrize("backend", BACKENDS)
def test_bitweaving_resident_scan_on_device_backend(backend):
    from repro.apps.bitweaving_db import (BitWeavingColumn,
                                          ambit_scan_resident)

    rng = np.random.default_rng(17)
    vals = rng.integers(0, 2**10, 4000).astype(np.uint32)
    col = BitWeavingColumn.from_values(vals, 10)
    rt = AmbitRuntime(backend=backend)
    for (c1, c2) in ((0, 1023), (100, 100), (256, 700)):
        count, stats, _ = ambit_scan_resident(col, c1, c2, rt)
        assert count == col.oracle_count(vals, c1, c2)
    # planes stayed resident: the second/third scans paid no re-upload
    assert rt.store.host_writes == 10

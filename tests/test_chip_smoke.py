"""CPU rehearsal of ``chip_smoke.py``: each phase at 2^12-2^14 bits,
checked against its numpy reference, plus the script's refusal to run
without a TPU and the compile cache's placement."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()

SMALL = {"serving": {"n_bits": 2 ** 14}, "multi_root": {"n_bits": 2 ** 13},
         "donation": {"n_bits": 2 ** 12}, "bitweaving": {"n_rows": 2 ** 14}}
EXPECT = {"serving": {"queries": 64, "drains": 8, "epochs": 8,
                      "fused_dispatches": 8},
          "multi_root": {"queries": 5, "epochs": 2},
          "donation": {"rebinds": 4, "donated": 4},
          "bitweaving": {"predicates": 4, "rows": 2 ** 14, "bits": 12}}


@pytest.mark.parametrize("name", [n for n, _ in smoke.PHASES])
def test_phase_matches_numpy_at_small_size(name):
    phase = dict(smoke.PHASES)[name]
    assert phase(seed=7, **SMALL[name]) == EXPECT[name]


@pytest.mark.parametrize("name", ["serving", "bitweaving"])
def test_phase_fails_on_a_wrong_device_answer(name, monkeypatch):
    """A wrong count from the device path is a failure of the phase,
    never a result the script reports as ok."""
    from repro.pim.device_store import DeviceStore
    real = DeviceStore.popcount
    monkeypatch.setattr(DeviceStore, "popcount",
                        lambda self, rbv: real(self, rbv) + 1)
    with pytest.raises(smoke.SmokeMismatch):
        dict(smoke.PHASES)[name](seed=0, **SMALL[name])


def test_main_refuses_cpu_before_any_phase(monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(smoke, "PHASES",
                        (("probe", lambda seed: ran.append(seed)),))
    assert smoke.main([]) != 0
    assert ran == []
    assert capsys.readouterr().out == ""


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_bitweaving_host_slicing_matches_reference():
    """``from_values`` bit-slices on the host; ``ref.bitslice`` is the
    device reference."""
    import jax.numpy as jnp

    from repro.apps.bitweaving_db import BitWeavingColumn
    from repro.kernels import ref
    values = np.random.default_rng(2).integers(0, 2 ** 12, 1000,
                                               dtype=np.uint32)
    col = BitWeavingColumn.from_values(values, 12)
    padded = np.pad(values, (0, (-1000) % 32))
    assert np.array_equal(col.planes,
                          np.asarray(ref.bitslice(jnp.asarray(padded), 12)))


_PROBE = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    sys.path.insert(0, {src!r})
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * {salt} + 1)(jnp.arange(8)).block_until_ready()
    print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
""")


def _probe_cache(env_dir):
    salt = int.from_bytes(os.urandom(3), "little")   # a fresh cache key
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = _PROBE.format(src=str(ROOT / "src"), salt=salt)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.splitlines()[-1])


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    checkout_cache = ROOT / ".jax_cache"
    before = _entries(checkout_cache)
    path, configured = _probe_cache(tmp_path)
    assert path == configured == str(tmp_path)
    assert _entries(tmp_path)
    assert _entries(checkout_cache) == before


def test_compile_cache_defaults_to_the_checkout():
    checkout_cache = ROOT / ".jax_cache"
    before = _entries(checkout_cache)
    path, configured = _probe_cache(None)
    assert path == configured == str(checkout_cache)
    assert _entries(checkout_cache) - before

"""BENCHMARK.json and the files it names: every cell resolves its
configuration, mix and metric readers; run.py refuses to run without a
TPU; work.py's byte counts; the generator's determinism."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, loadgen, work

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.mix.clients >= 1
        assert cell.cfg["frontend"]["max_batch"] >= 1
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all("\n" not in x for x in layers)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "wau_16m.console1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=harness.ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_query_bytes():
    n = 2 ** 24
    assert work.bitmap_bytes(n) == 2 ** 21
    assert work.bitmap_bytes(4097) == 513
    assert work.count_bytes(n, 29) == 29 * 2 ** 21


@pytest.mark.parametrize("weeks,parts,bitmaps", [
    (4, ("all_weeks", "week_and_attribute"), 29),
    (2, ("all_weeks", "week_and_attribute"), 15),
    (3, ("all_weeks",), 21),
])
def test_request_bytes(weeks, parts, bitmaps):
    """A request reads each of its distinct bitmaps once: the days of its
    weeks, and the attribute where it asks for it."""
    cell = harness.resolve("wau_16m.dash16")
    qs = cell.data.queries(parts, {"weeks": weeks}, cell.cfg)
    assert cell.data.least_bytes(qs, cell.cfg) == bitmaps * 2 ** 21


def test_streams_depend_only_on_seed_and_client():
    mix = harness.resolve("wau_16m.dash16").mix
    s1, s2 = (loadgen.ClientStream(mix, 2 ** 33 + 1, 3) for _ in range(2))
    assert [s1.next() for _ in range(9)] == [s2.next() for _ in range(9)]
    s3 = loadgen.ClientStream(mix, 2 ** 33 + 1, 4)
    s1 = loadgen.ClientStream(mix, 2 ** 33 + 1, 3)
    assert [s1.next() for _ in range(9)] != [s3.next() for _ in range(9)]


def test_uniform_draws_are_independent():
    mix = harness.resolve("wau_16m.dash16").mix
    s = loadgen.ClientStream(mix, 2 ** 40 + 7, 0)
    weeks = [s.next()["weeks"] for _ in range(3000)]
    assert set(weeks) == {2, 3, 4}
    assert all(900 < weeks.count(w) < 1100 for w in (2, 3, 4))
    # not dealt in blocks: some block of three repeats a value
    assert any(len(set(weeks[i:i + 3])) < 3 for i in range(0, 30, 3))

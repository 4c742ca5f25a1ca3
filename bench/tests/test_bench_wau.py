"""CPU rehearsal of the wau_16m cells at 2^14 users: every answered
count against reference.py, and the reference against numpy."""

import numpy as np
import pytest
import rehearse

from bench import harness


@pytest.mark.parametrize("cell", ["wau_16m.dash16", "wau_16m.console1"])
def test_cell_runs_correct(cell):
    r = rehearse.run(cell)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > r["device"]["count"]
    assert set(r["metrics"]) == {"req_per_s", "p50_ms", "p95_ms",
                                 "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())


def test_reference_matches_numpy():
    cell = rehearse.tiny_cell("wau_16m.dash16")
    raw = {k: np.asarray(v) for k, v in
           cell.data.raw(rehearse.SEED, cell.cfg).items()}

    def week(back):          # days 21-27 are the latest week
        return np.bitwise_or.reduce([raw[f"day{d}"] for d in
                                     range(21 - 7 * back, 28 - 7 * back)])

    keys = [("all_weeks", 4), ("all_weeks", 2), ("week_and_attribute", 1)]
    got = cell.reference.answers(rehearse.SEED, cell.cfg, keys)
    want = {
        keys[0]: week(0) & week(1) & week(2) & week(3),
        keys[1]: week(0) & week(1),
        keys[2]: week(1) & raw["attr0"],
    }
    for k in keys:
        assert got[k] == int(np.bitwise_count(want[k]).sum())


def test_queries_of_a_request():
    cell = rehearse.tiny_cell("wau_16m.dash16")
    qs = cell.data.queries(cell.mix.parts, {"weeks": 3}, cell.cfg)
    assert [q.key for q in qs] == [("all_weeks", 3),
                                   ("week_and_attribute", 2),
                                   ("week_and_attribute", 1),
                                   ("week_and_attribute", 0)]
    assert qs[0].operands == tuple(f"day{d}" for d in range(7, 28))
    assert qs[1].operands == tuple(f"day{d}" for d in range(7, 14)) + \
        ("attr0",)
    examples = cell.data.program_examples(
        cell.mix.parts, {p.name: p.values for p in cell.mix.params},
        cell.cfg)
    assert sorted((q.program, n) for q, n in examples) == [
        (("and_of_weeks", 2), 1), (("and_of_weeks", 3), 1),
        (("and_of_weeks", 4), 1), (("week_and_attr",), 4)]
    assert harness.resolve("wau_16m.console1").mix.parts == ("all_weeks",)


def test_plan_is_the_section_8_1_expression():
    """The planned expression, evaluated by numpy over the request's
    operands, gives the reference's count."""
    from repro.core import expr as E
    cell = rehearse.tiny_cell("wau_16m.dash16")
    raw = {k: np.asarray(v) for k, v in
           cell.data.raw(rehearse.SEED, cell.cfg).items()}
    qs = cell.data.queries(cell.mix.parts, {"weeks": 4}, cell.cfg)
    ref = cell.reference.answers(rehearse.SEED, cell.cfg,
                                 [q.key for q in qs])
    for q in qs:
        expr, env = cell.data.plan(dict(zip(raw, raw)), q)
        out = E.eval_expr(expr, {v: raw[nm] for v, nm in env.items()})
        assert int(np.bitwise_count(np.asarray(out)).sum()) == ref[q.key]

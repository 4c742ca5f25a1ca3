"""The check catches what it must: the control (the reference in a
precision that breaks "every count exact") and faults planted in the
timed path underneath a whole run."""

import rehearse

from bench import harness
from repro.pim import device_store


def _requests(cell, keys, seed, control):
    ref = cell.reference.answers(seed, cell.cfg, keys, control=control)
    reqs = []
    for k in keys:
        q = type("Q", (), {"key": k})()
        reqs.append(harness.Request(0, [q], 0, {0: ref[k]}, done_ns=1))
    return reqs


def test_wau_control_fails_the_check():
    # bfloat16 keeps 8 significant bits: counts near 2^14 round to
    # multiples of 64.
    cell = rehearse.tiny_cell("wau_16m.dash16")
    keys = [("week_and_attribute", k) for k in range(4)] + \
        [("all_weeks", w) for w in (2, 3, 4)]
    exact = _requests(cell, keys, rehearse.SEED, control=False)
    checks, _ = harness.check(cell, rehearse.SEED, exact)
    assert checks["count_gap_max"]["value"] == 0
    ctl = _requests(cell, keys, rehearse.SEED, control=True)
    checks, _ = harness.check(cell, rehearse.SEED, ctl)
    assert checks["count_gap_max"]["value"] > \
        checks["count_gap_max"]["limit"]


def test_answer_altered_where_produced(monkeypatch):
    real = device_store.DeviceStore.popcount
    calls = []

    def off_by_one(self, rbv):
        calls.append(1)
        return real(self, rbv) + (1 if len(calls) == 40 else 0)

    monkeypatch.setattr(device_store.DeviceStore, "popcount", off_by_one)
    r = rehearse.run("wau_16m.console1")
    assert r["correct"] is False and r["failed"] == 1
    assert r["checks"]["count_gap_max"]["value"] == 1


def test_half_of_each_epoch_left_out(monkeypatch):
    """The second half of every stacked epoch is computed from the first
    query's operands instead of its own."""
    real = device_store.DevicePlanner.execute_epoch

    def half(self, jobs):
        h = (len(jobs) + 1) // 2
        env0 = jobs[0][1]
        return real(self, list(jobs[:h]) + [(e, env0, name, out)
                                            for e, _, name, out in jobs[h:]])

    monkeypatch.setattr(device_store.DevicePlanner, "execute_epoch", half)
    r = rehearse.run("wau_16m.dash16")
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["count_gap_max"]["value"] > 0

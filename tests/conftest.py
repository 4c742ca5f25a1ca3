import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: do NOT set XLA_FLAGS / host device count here - smoke tests and
# benchmarks must see the single real CPU device.

# -- ledger determinism hook --------------------------------------------------
# pim tests record the ledgers their canonical workloads produce; when
# $PIM_LEDGER_OUT is set the sorted lines are written there at session end.
# CI runs the pim shard twice under PYTHONHASHSEED=0 and diffs the two
# files: any nondeterministic placement/eviction/transfer order shows up
# as a ledger diff even when the bit-level results still agree.

_LEDGER_LINES = []


@pytest.fixture
def record_ledger():
    def _record(name: str, text: str) -> None:
        _LEDGER_LINES.append(f"{name}: {text}")
    return _record


def pytest_sessionfinish(session, exitstatus):
    path = os.environ.get("PIM_LEDGER_OUT")
    if path:
        with open(path, "w") as fh:
            for line in sorted(_LEDGER_LINES):
                fh.write(line + "\n")

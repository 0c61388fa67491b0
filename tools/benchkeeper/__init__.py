"""benchkeeper: the perf-regression gate over bench.py results.

Compares a fresh bench results JSON against the checked-in, reasoned
``tools/benchkeeper/baseline.json`` (fingerprint-scoped reference
numbers with explicit tolerance bands — device-attributed metrics
tight, host-inclusive wall metrics wide). See core.py for the gate
semantics and smoke.py for the tier-1 self-test.

    python -m tools.benchkeeper BENCH_r06.json       # gate a run
    python -m tools.benchkeeper --smoke              # machinery self-test
    python -m tools.benchkeeper --update-baseline r06.json r07.json
"""

from tools.benchkeeper.core import (BaselineError, compare, load_baseline,
                                    load_run, main, update_baseline)

__all__ = ["BaselineError", "compare", "load_baseline", "load_run",
           "main", "update_baseline"]

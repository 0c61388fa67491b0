"""CPU a dispatch costs on the two dispatch threads (roles
``batcher_worker`` and ``batcher_drain``: every batcher's, where a
collection has several), as ``cpu_ms_per_search`` reads the account,
over the dispatches of the window (``compile_bucket_total``). None where
the program keeps no such account."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_ms_per_search as account  # noqa: E402


def read(ctx):
    return account.per(ctx, ("batcher_worker", "batcher_drain"),
                       account.dispatches(ctx))

"""CPU a Search costs on the handlers' pool (role ``grpc_pool``: the
request's own thread), as ``cpu_ms_per_search`` reads the account, over
the Searches of the window. ``handler_cpu_ms`` is the part of it inside
the handler; the difference is grpc's own Python on the request's thread
(deserialising, the pool's queue, the reply's serialisation and send).
None where the program keeps no such account."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_ms_per_search as account  # noqa: E402


def read(ctx):
    return account.per(ctx, ("grpc_pool",), account.searches(ctx))

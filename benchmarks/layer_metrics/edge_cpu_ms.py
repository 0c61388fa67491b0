"""CPU a Search costs at the gRPC edge outside the handlers' pool: the
roles ``grpc_serve`` (gRPC's ONE Python serving thread, which runs
Python for every event of every RPC) and ``grpc_core`` (grpc's native
threads), as ``cpu_ms_per_search`` reads the account, over the Searches
of the window. What a native data plane at the edge (ROADMAP S2) takes
away. None where the program keeps no such account."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_ms_per_search as account  # noqa: E402


def read(ctx):
    return account.per(ctx, ("grpc_serve", "grpc_core"),
                       account.searches(ctx))

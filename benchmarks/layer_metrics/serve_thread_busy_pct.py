"""Share of the window in which gRPC's one Python serving thread was on
a core: the delta of role ``grpc_serve``'s CPU over the delta of
``weaviate_tpu_scrape_clock_seconds`` (the server's ``time.monotonic()``
at the two scrapes). One serial thread every RPC passes twice: near
100 % it is the cap, whatever the chip does. None where the program
keeps no such account."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_ms_per_search as account  # noqa: E402


def read(ctx):
    used = account.cpu_seconds(ctx, ("grpc_serve",))
    wall = account.moved(ctx, account.CLOCK)
    if used is None or wall <= 0:
        return None
    return 100.0 * used / wall

"""worker.py's one client loop against a stand-in gRPC server that answers
every Search after a fixed delay: a closed loop sends on the reply, an open
loop when a request is due and times it from then, a burst sends m at once."""

import time
from concurrent import futures

import grpc
import numpy as np
import pytest

import traffic
import wire
import worker

K = 3
SERVICE_S = 0.04
REQUEST = {"kind": "near_vector", "metadata": ["uuid", "distance"]}
JOB = {"seed": 5, "collection": "X", "k": K}
QUERIES = np.zeros((16, 8), np.float32)


@pytest.fixture(scope="module")
def channel():
    pb = wire.load_pb()

    def search(request, context):
        time.sleep(SERVICE_S)
        reply = pb.SearchReply()
        for i in range(request.limit):
            result = reply.results.add()
            result.metadata.id = wire.obj_uuid(i)
            result.metadata.distance = float(i)
        return reply

    server = grpc.server(futures.ThreadPoolExecutor(4))
    server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
        wire.SERVICE, {"Search": grpc.unary_unary_rpc_method_handler(
            search, request_deserializer=pb.SearchRequest.FromString,
            response_serializer=pb.SearchReply.SerializeToString)}),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    chan = wire.Grpc(port)
    yield chan
    chan.close()
    server.stop(0)


def drive(channel, mix, seconds, burst=None):
    sink = []
    t0 = time.time() + 0.05
    worker.run_client(channel, JOB, traffic.check(mix), QUERIES, 0, t0,
                      t0 + seconds, burst, 0, sink)
    return t0, sink


def test_closed_loop_sends_on_the_reply(channel):
    mix = {"loop": "closed", "clients": 1, "processes": 1,
           "request": REQUEST, "filter": None}
    t0, sink = drive(channel, mix, 0.5)
    assert 8 <= len(sink) <= 13          # 0.5 s of 40-ms requests
    assert all(r[8] is None and r[6] == list(range(K)) for r in sink)
    assert all(r[9] == 0.0 and SERVICE_S <= r[3] < 3 * SERVICE_S
               for r in sink)
    assert sink[0][2] >= t0
    t0, burst = drive(channel, mix, 60.0, burst=3)
    assert len(burst) == 3 and all(r[8] is None for r in burst)
    assert time.time() - t0 < 3 * SERVICE_S     # at once, not in turn


def test_open_loop_is_due_on_schedule_and_timed_from_then(channel):
    # 50 requests/s are due from this client; the server takes 25
    mix = {"loop": "open", "rate_per_s": 200.0, "clients": 4,
           "processes": 1, "request": REQUEST, "filter": None}
    t0, sink = drive(channel, mix, 1.0)
    gaps = traffic.client_plan(mix, JOB["seed"], 0, len(QUERIES))[2]
    due = np.array([r[2] for r in sink])
    assert np.allclose(due - t0, np.cumsum(gaps)[:len(sink)])
    late = np.array([r[9] for r in sink])
    latency = np.array([r[3] for r in sink])
    assert late[-1] > 5 * SERVICE_S      # the backlog grew
    assert np.all(latency >= late + SERVICE_S)
    assert abs(gaps.mean() - 4 / 200.0) < 1e-3


@pytest.mark.parametrize("bad", [
    {"loop": "spiral"}, {"loop": "open", "rate_per_s": 0},
    {"request": {"kind": "hybrid"}},
    {"filter": {"property": "bucket", "operator": "like", "values": [1]}}])
def test_a_mix_the_generator_cannot_make_is_refused(bad):
    mix = dict({"loop": "closed", "clients": 1, "processes": 1,
                "request": REQUEST, "filter": None}, **bad)
    with pytest.raises(ValueError):
        traffic.check(mix)

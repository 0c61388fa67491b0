"""The benchmark's own socket clients: REST over http.client, gRPC over the
wire protocol's generated messages. Nothing here imports JAX or the
program's packages: the message classes are loaded from the proto module's
file, so the parent and the load workers stay off the chip."""

from __future__ import annotations

import http.client
import importlib.util
import json
import os
import queue
import re
import threading
import uuid as uuid_mod

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVICE = "weaviate.v1.Weaviate"
_PB = None


def load_pb():
    """The wire protocol's message classes, from the file (importing the
    ``weaviate_tpu.api`` package would import the servers and JAX)."""
    global _PB
    if _PB is None:
        path = os.path.join(REPO, "weaviate_tpu", "api", "grpc", "v1_pb2.py")
        spec = importlib.util.spec_from_file_location("bench_v1_pb2", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PB = mod
    return _PB


def obj_uuid(i: int) -> str:
    return str(uuid_mod.UUID(int=i + 1))


def row_of(uid: str) -> int:
    return uuid_mod.UUID(uid).int - 1


class Rest:
    def __init__(self, addr: str, timeout: float = 600.0):
        self.host, _, port = addr.partition(":")
        self.port = int(port)
        self.timeout = timeout

    def request(self, method: str, path: str, body=None):
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            conn.request(method, path,
                         body=None if body is None else json.dumps(body),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        if resp.status >= 400:
            raise RuntimeError(f"{method} {path}: HTTP {resp.status} "
                               f"{raw[:300]!r}")
        return raw

    def create_class(self, config: dict) -> None:
        self.request("POST", "/v1/schema", config)

    def get_object(self, class_name: str, uid: str) -> dict:
        return json.loads(self.request(
            "GET", f"/v1/objects/{class_name}/{uid}?include=vector"))

    def metrics(self) -> "Prom":
        return Prom(self.request("GET", "/v1/metrics").decode())


_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


class Prom:
    """One scrape of a Prometheus text page."""

    def __init__(self, text: str):
        self.series: list[tuple[str, dict, float]] = []
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            m = _LINE.match(line)
            if m:
                self.series.append((m.group(1),
                                    dict(_LABEL.findall(m.group(2) or "")),
                                    float(m.group(3))))

    def total(self, name: str, labels: dict | None = None) -> float:
        """Sum of every series of ``name`` whose labels include ``labels``."""
        want = (labels or {}).items()
        return sum(v for n, lab, v in self.series
                   if n == name and all(lab.get(k) == x for k, x in want))

    def by_label(self, name: str, label: str) -> dict[str, float]:
        out: dict[str, float] = {}
        for n, lab, v in self.series:
            if n == name and label in lab:
                out[lab[label]] = out.get(lab[label], 0.0) + v
        return out


class Grpc:
    """One channel; ``search`` is safe to call from many threads."""

    def __init__(self, port: int):
        import grpc

        self.pb = load_pb()
        self.chan = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_send_message_length", 64 << 20),
                     ("grpc.max_receive_message_length", 64 << 20)])
        self._batch = self.chan.unary_unary(
            f"/{SERVICE}/BatchObjects",
            request_serializer=self.pb.BatchObjectsRequest.SerializeToString,
            response_deserializer=self.pb.BatchObjectsReply.FromString)
        self._search = self.chan.unary_unary(
            f"/{SERVICE}/Search",
            request_serializer=self.pb.SearchRequest.SerializeToString,
            response_deserializer=self.pb.SearchReply.FromString)

    def close(self):
        self.chan.close()

    def import_rows(self, collection: str, vectors: np.ndarray,
                    props: dict[str, np.ndarray], batch: int) -> None:
        """BatchObjects over one stream (the shard lock serialises writers;
        four streams measured no faster, PERF.md PR 22); row i gets
        ``obj_uuid(i)``; the next message is built in a thread while the
        server works on this one. Every object must be acknowledged
        without an error."""
        vectors = np.ascontiguousarray(vectors, dtype="<f4")
        names = list(props)
        cols = [props[n].tolist() for n in names]
        starts = list(range(0, len(vectors), batch))
        box: queue.Queue = queue.Queue(maxsize=2)

        def build():
            for start in starts:
                req = self.pb.BatchObjectsRequest()
                for i in range(start, min(start + batch, len(vectors))):
                    bo = req.objects.add(collection=collection,
                                         uuid=obj_uuid(i))
                    bo.vector_bytes = vectors[i].tobytes()
                    bo.properties.non_ref_properties.update(
                        {n: c[i] for n, c in zip(names, cols)})
                box.put(req)

        threading.Thread(target=build, daemon=True).start()
        for start in starts:
            reply = self._batch(box.get())
            if len(reply.errors):
                raise RuntimeError(f"import errors at row {start}: "
                                   f"{reply.errors[:1]}")

    def search_request(self, collection: str, vec: np.ndarray, spec: dict,
                       k: int, flt: dict | None, bound):
        """A Search message as the traffic file's ``request`` describes,
        for the configuration's k."""
        req = self.pb.SearchRequest(collection=collection, limit=k,
                                    uses_123_api=True)
        req.near_vector.vector_bytes = vec.astype("<f4").tobytes()
        for field in spec["metadata"]:
            setattr(req.metadata, field, True)
        if flt is not None:
            req.filters.operator = getattr(
                self.pb.Filters, "OPERATOR_" + flt["operator"].upper())
            req.filters.target.property = flt["property"]
            req.filters.value_int = int(bound)
        return req

    def search(self, req):
        """-> (row ids, distances) of the reply."""
        return self.parse(self._search(req))

    def search_future(self, req):
        """The call in flight; ``parse(call.result())`` is its answer."""
        return self._search.future(req)

    @staticmethod
    def parse(reply):
        return ([row_of(r.metadata.id) for r in reply.results],
                [r.metadata.distance for r in reply.results])

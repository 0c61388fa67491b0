"""Collection (class) configuration model.

Reference parity:
- class schema + properties: entities/models (Class, Property), validated in
  usecases/schema/class.go:95 (AddClass defaults + validation)
- vector index configs: entities/vectorindex/{hnsw,flat,dynamic}/config.go
- sharding config: usecases/sharding/config.go (shard count fixed at
  creation)
- multi-tenancy: one shard per tenant (sharding/state.go:293)
- replication: usecases/replica/config.go (factor, consistency levels)
- inverted index config: BM25 k1/b, stopwords (entities/models +
  inverted/stopwords)
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field, asdict


class DataType:
    TEXT = "text"
    TEXT_ARRAY = "text[]"
    INT = "int"
    INT_ARRAY = "int[]"
    NUMBER = "number"
    NUMBER_ARRAY = "number[]"
    BOOL = "boolean"
    BOOL_ARRAY = "boolean[]"
    DATE = "date"
    DATE_ARRAY = "date[]"
    UUID = "uuid"
    UUID_ARRAY = "uuid[]"
    GEO = "geoCoordinates"
    BLOB = "blob"
    OBJECT = "object"
    REFERENCE = "cref"

    ALL = {TEXT, TEXT_ARRAY, INT, INT_ARRAY, NUMBER, NUMBER_ARRAY, BOOL,
           BOOL_ARRAY, DATE, DATE_ARRAY, UUID, UUID_ARRAY, GEO, BLOB, OBJECT,
           REFERENCE}


_NAME_RE = re.compile(r"^[A-Za-z][A-Za-z0-9_]*$")


@dataclass
class Property:
    name: str
    data_type: str = DataType.TEXT
    tokenization: str = "word"  # word | lowercase | whitespace | field
    index_filterable: bool = True
    index_searchable: bool = True  # only meaningful for text
    description: str = ""
    nested: list["Property"] | None = None

    def validate(self):
        if not _NAME_RE.match(self.name):
            raise ValueError(f"invalid property name {self.name!r}")
        if self.data_type not in DataType.ALL:
            raise ValueError(f"unknown data type {self.data_type!r} for {self.name}")
        if self.tokenization not in ("word", "lowercase", "whitespace", "field"):
            raise ValueError(f"unknown tokenization {self.tokenization!r}")


@dataclass
class VectorIndexConfig:
    index_type: str = "flat"  # flat | hnsw | dynamic | noop (reference set + ivf)
    metric: str = "l2-squared"
    storage_dtype: str = "float32"  # float32 | bfloat16
    # quantization: product (ops/pq.py), binary (ops/bq.py) or scalar
    # (ops/sq.py: one byte a dimension); at most one a vector space
    quantization: str | None = None  # None | pq | bq | sq
    pq_segments: int | None = None
    # TPU-first default: 16 centroids = 4-bit codes whose ADC lookup is one
    # MXU matmul (ops/pallas_kernels.pq4_lut_block); 256 selects the
    # reference-style 8-bit codebook (reconstruct-matmul scan)
    pq_centroids: int = 16
    # upstream's pq.trainingLimit: a pq class answers exactly from full
    # rows until its shard holds this many vectors, then fits the
    # codebook on the first pq_training_limit of them, once, and goes on
    # compressed (compress_due below is the one gate)
    pq_training_limit: int = 100_000
    # upstream's pq.encoder.type; "tile" has no form here
    pq_encoder: str = "kmeans"
    # upstream's sq.trainingLimit: as pq_training_limit, for the two
    # scalars an sq class fits (the range of its first rows)
    sq_training_limit: int = 100_000
    rescore_limit: int = 16
    # two-stage scan: width (bits, 128/256) of the separately-stored
    # transposed sign prefix — the capacity-regime operating point
    # (BASELINE r5: 10M×768 PQ 7.9 ms @ B=64 vs 30.5 exhaustive);
    # ignored for mesh-sharded stores and dims the prefix cannot cover
    prefix_bits: int | None = None
    # hnsw-ish knobs (used by graph/ivf indexes)
    ef: int = -1
    ef_construction: int = 128
    max_connections: int = 32
    # dynamic index upgrade threshold (dynamic/index.go:348)
    flat_to_ann_threshold: int = 10_000
    # upstream's hnsw.flatSearchCutoff: a filter that allows fewer rows
    # than this is answered by an exact scan over them, the ANN index
    # bypassed (0: never). Read by the graph index and by the IVF index
    # a dynamic class upgrades into (engine/ivf.py)
    flat_search_cutoff: int = 40_000
    # ivf
    ivf_nlist: int = 0  # 0 = auto
    ivf_nprobe: int = 0  # 0 = auto
    # epoch-stacked device corpus (engine/epochs.py): seal the active
    # epoch every N rows; sealed epochs are immutable, compact in the
    # background (deletes reclaim HBM) and can migrate under memory
    # pressure. 0 = legacy single donated buffer. Flat indexes only —
    # graph/ivf layouts have their own reorganize stories.
    epoch_rows: int = 0

    def validate(self):
        from weaviate_tpu.ops.distances import DISTANCE_METRICS

        if self.index_type not in ("flat", "hnsw", "dynamic", "noop", "ivf"):
            raise ValueError(f"unknown vector index type {self.index_type!r}")
        if self.metric not in DISTANCE_METRICS:
            raise ValueError(f"unknown distance metric {self.metric!r}")
        if self.quantization not in (None, "pq", "bq", "sq"):
            raise ValueError(f"unknown quantization {self.quantization!r}")
        if self.pq_encoder != "kmeans":
            raise ValueError(
                f"pq encoder must be 'kmeans', got {self.pq_encoder!r}")
        if (not isinstance(self.pq_training_limit, int)
                or isinstance(self.pq_training_limit, bool)
                or self.pq_training_limit < max(self.pq_centroids, 1)):
            raise ValueError(
                f"pq trainingLimit must be an int >= centroids "
                f"({self.pq_centroids}), got {self.pq_training_limit!r}")
        if (not isinstance(self.flat_search_cutoff, int)
                or isinstance(self.flat_search_cutoff, bool)
                or self.flat_search_cutoff < 0):
            raise ValueError(
                f"flatSearchCutoff must be an int >= 0, got "
                f"{self.flat_search_cutoff!r}")
        if (not isinstance(self.sq_training_limit, int)
                or isinstance(self.sq_training_limit, bool)
                or self.sq_training_limit < 1):
            raise ValueError(
                f"sq trainingLimit must be an int >= 1, got "
                f"{self.sq_training_limit!r}")
        if self.quantization == "sq":
            self._validate_sq()
        if self.prefix_bits is not None:
            if not isinstance(self.prefix_bits, int) \
                    or self.prefix_bits not in (128, 256):
                raise ValueError(
                    f"prefix_bits must be 128 or 256, got "
                    f"{self.prefix_bits!r}")
            if self.quantization not in ("pq", "bq"):
                raise ValueError(
                    "prefix_bits requires quantization pq or bq")
        if self.epoch_rows:
            if not isinstance(self.epoch_rows, int) or self.epoch_rows < 0:
                raise ValueError(
                    f"epoch_rows must be a non-negative int, got "
                    f"{self.epoch_rows!r}")
            if self.index_type != "flat":
                raise ValueError(
                    "epoch_rows requires index_type 'flat' (graph/ivf "
                    "layouts have their own reorganize stories)")


    def _validate_sq(self):
        """Where sq cannot be honoured the class is refused, never built
        as something else: the scalar-quantized store is the flat scan's
        (engine/quantized.py), one device, one buffer."""
        from weaviate_tpu.ops.sq import SQ_METRICS

        if self.index_type in ("hnsw", "ivf"):
            raise ValueError(
                f"sq is not supported on vectorIndexType "
                f"{self.index_type!r}: the scalar-quantized scan is the "
                f"flat index's (ROADMAP D4)")
        if self.epoch_rows:
            raise ValueError("sq requires epoch_rows 0: the epoch-stacked "
                             "store has no sq form")
        if self.metric not in SQ_METRICS:
            raise ValueError(
                f"sq is not supported for distance {self.metric!r}")

    @property
    def training_limit(self) -> int | None:
        """Rows the enabled quantizer waits for before it is fitted
        (upstream's pq.trainingLimit / sq.trainingLimit); None where
        nothing is fitted."""
        return {"pq": self.pq_training_limit,
                "sq": self.sq_training_limit}.get(self.quantization)

    def compress_due(self, rows: int) -> bool:
        """THE gate of runtime compression: whether an index of this
        config that still holds full rows, ``rows`` of them, compresses
        now. bq needs no training; pq and sq wait for their
        ``training_limit`` rows (upstream compress.go:38 behind
        pq.trainingLimit)."""
        limit = self.training_limit
        return self.quantization is not None and (
            limit is None or rows >= limit)


@dataclass
class VectorConfig:
    """One named vector space (reference: hasTargetVectors, shard.go:130)."""

    name: str = ""  # "" = default/legacy single vector
    dim: int = 0  # 0 = inferred from first insert
    index: VectorIndexConfig = field(default_factory=VectorIndexConfig)
    vectorizer: str = "none"  # module name, or "none" = client provides
    # per-module settings (reference: moduleConfig per class/vector —
    # e.g. {"vectorizeClassName": false, "properties": [...]})
    module_config: dict = field(default_factory=dict)


@dataclass
class ShardingConfig:
    desired_count: int = 1
    virtual_per_physical: int = 128


@dataclass
class MultiTenancyConfig:
    enabled: bool = False
    auto_tenant_creation: bool = False
    auto_tenant_activation: bool = False


@dataclass
class ReplicationConfig:
    factor: int = 1
    async_enabled: bool = False


@dataclass
class InvertedIndexConfig:
    bm25_k1: float = 1.2
    bm25_b: float = 0.75
    stopwords_preset: str = "en"  # en | none
    stopwords_additions: list[str] = field(default_factory=list)
    stopwords_removals: list[str] = field(default_factory=list)
    index_timestamps: bool = False
    index_null_state: bool = False
    index_property_length: bool = False


@dataclass
class CollectionConfig:
    name: str
    description: str = ""
    properties: list[Property] = field(default_factory=list)
    vectors: list[VectorConfig] = field(default_factory=lambda: [VectorConfig()])
    sharding: ShardingConfig = field(default_factory=ShardingConfig)
    multi_tenancy: MultiTenancyConfig = field(default_factory=MultiTenancyConfig)
    replication: ReplicationConfig = field(default_factory=ReplicationConfig)
    inverted: InvertedIndexConfig = field(default_factory=InvertedIndexConfig)
    # class-level module settings keyed by module name (reference:
    # models.Class.ModuleConfig) — generative-*, reranker-* live here
    module_config: dict = field(default_factory=dict)

    def validate(self):
        if not _NAME_RE.match(self.name) or not self.name[0].isupper():
            raise ValueError(
                f"invalid collection name {self.name!r} (GraphQL-compatible "
                "UpperCamelCase required)"
            )
        seen = set()
        for p in self.properties:
            p.validate()
            if p.name.lower() in seen:
                raise ValueError(f"duplicate property {p.name!r}")
            seen.add(p.name.lower())
        vec_names = set()
        for v in self.vectors:
            v.index.validate()
            if v.name in vec_names:
                raise ValueError(f"duplicate vector name {v.name!r}")
            vec_names.add(v.name)
        if self.sharding.desired_count < 1:
            raise ValueError("shard count must be >= 1")
        if self.replication.factor < 1:
            raise ValueError("replication factor must be >= 1")

    def property(self, name: str) -> Property | None:
        for p in self.properties:
            if p.name == name:
                return p
        return None

    def vector_config(self, name: str = "") -> VectorConfig | None:
        for v in self.vectors:
            if v.name == name:
                return v
        return None

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "CollectionConfig":
        d = dict(d)
        d["properties"] = [
            Property(**{**p, "nested": None}) if not p.get("nested")
            else Property(**{**p, "nested": [Property(**n) for n in p["nested"]]})
            for p in d.get("properties", [])
        ]
        d["vectors"] = [
            VectorConfig(
                name=v.get("name", ""),
                dim=v.get("dim", 0),
                index=VectorIndexConfig(**v.get("index", {})),
                vectorizer=v.get("vectorizer", "none"),
                module_config=v.get("module_config", {}),
            )
            for v in d.get("vectors", [{}])
        ]
        d["sharding"] = ShardingConfig(**d.get("sharding", {}))
        d["multi_tenancy"] = MultiTenancyConfig(**d.get("multi_tenancy", {}))
        d["replication"] = ReplicationConfig(**d.get("replication", {}))
        d["inverted"] = InvertedIndexConfig(**d.get("inverted", {}))
        return cls(**d)

"""Arrow <-> bytes helpers for the wire contract.

Schemas and record batches travel as Arrow IPC — the Arrow wire format
itself — instead of the reference's hand-rolled type enum
(reference rust/core/proto/ballista.proto:611-800).
"""

from __future__ import annotations

import io
from typing import Dict, List, Tuple

import pyarrow as pa

from ballista_tpu.utils import tracing


# A plan names the same few schemas and types once per expression node, and a
# stage's tasks carry the same plan: each distinct one is parsed once per
# process. Schemas and types are immutable and the memos are keyed on content,
# so what they return is what a fresh parse returns. Plain dicts, written with
# no lock: a race costs one parse more, and a full memo starts over.
_MEMO_ENTRIES = 4096
_schemas: Dict[bytes, pa.Schema] = {}
_dtype_bytes: Dict[pa.DataType, Tuple[pa.DataType, bytes]] = {}


def _keep(memo: dict, key, value):
    if len(memo) >= _MEMO_ENTRIES:
        memo.clear()
    memo[key] = value
    return value


def schema_to_ipc(schema: pa.Schema) -> bytes:
    # not memoised: hashing a schema walks its fields in Python and costs four
    # times what serialising it does (16 fields: 16.7 us against 4.0, sandbox)
    return schema.serialize().to_pybytes()


def schema_from_ipc(data: bytes) -> pa.Schema:
    schema = _schemas.get(data)
    if schema is None:
        tracing.incr("serde.schema_parse")
        schema = _keep(_schemas, data, pa.ipc.read_schema(pa.BufferReader(data)))
    return schema


def dtype_to_ipc(dtype: pa.DataType) -> bytes:
    # `==` on types ignores the metadata of nested fields: a kept encoding
    # serves only the type it was made from, metadata included
    kept = _dtype_bytes.get(dtype)
    if kept is None or not kept[0].equals(dtype, check_metadata=True):
        kept = _keep(_dtype_bytes, dtype,
                     (dtype, schema_to_ipc(pa.schema([pa.field("f", dtype)]))))
    return kept[1]


def dtype_from_ipc(data: bytes) -> pa.DataType:
    return schema_from_ipc(data).field(0).type


def batches_to_ipc(batches: List[pa.RecordBatch], schema: pa.Schema) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, schema) as w:
        for b in batches:
            w.write_batch(b)
    return sink.getvalue()


def batches_from_ipc(data: bytes) -> List[pa.RecordBatch]:
    with pa.ipc.open_stream(pa.BufferReader(data)) as r:
        return list(r)

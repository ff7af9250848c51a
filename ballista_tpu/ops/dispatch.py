"""Backend dispatch: route operator compute to JAX/XLA kernels.

Each hook returns None when the device path declines the shape (always
with a recorded reason, ops/kernels.py); operators then fall back to the
host Arrow path. A kernel module that fails to IMPORT is not a decline: it
raises, so a broken installation cannot pass for a host-side answer.
"""

from __future__ import annotations

from typing import Optional

import pyarrow as pa


def tpu_filter(batch: pa.RecordBatch, predicate) -> Optional[pa.RecordBatch]:
    from ballista_tpu.ops import kernels

    return kernels.filter_batch(batch, predicate)


def tpu_hash_aggregate(exec_node, partition: int, ctx, keyset=None) -> Optional[pa.Table]:
    from ballista_tpu.ops import kernels

    return kernels.hash_aggregate(exec_node, partition, ctx, keyset)

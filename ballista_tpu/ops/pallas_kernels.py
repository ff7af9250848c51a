"""Pallas TPU kernels.

Grouped masked aggregation as a one-hot matmul: for small group counts
(G <= 128 — a dictionary-coded GROUP BY like TPC-H q1), the segment-sum
becomes ``onehot(codes)^T @ values`` which maps directly onto the MXU
systolic array instead of the VPU scatter the XLA segment_sum lowering uses.
One grid pass streams row blocks HBM -> VMEM, accumulating [G, A] partials
in the output block that stays resident in VMEM across grid steps.

Two kernels: grouped_aggregate (small-G, one-hot matmul with the output
block resident in VMEM) and sorted_grouped_sum (cardinality-independent,
RMW DMA windows over sorted dense ranks). The latter is wired into the
fused stage behind ballista.tpu.sorted_kernel=pallas
(stage.py::_run_pallas_sorted); the chunked-segment layout remains the
default (see the status note on _build_sorted). dev/probe_pallas.py runs
both kernels compiled on the chip against numpy.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

BLOCK_ROWS = 1024


def pallas_available() -> bool:
    try:
        from jax.experimental import pallas as pl  # noqa: F401
        from jax.experimental.pallas import tpu as pltpu  # noqa: F401

        return True
    except Exception:
        return False


def _interpret_by_default() -> bool:
    """Mosaic compiles for the TPU only; on the CPU (the test lane) the
    kernels run in the Pallas interpreter. The platform is the established
    one (ops/device.py), not a guess."""
    from ballista_tpu.ops import device

    return device.establish().platform != "tpu"


@functools.lru_cache(maxsize=None)
def _build(num_groups: int, n_values: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    G, A = num_groups, n_values

    def kernel(codes_ref, values_ref, mask_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        codes = codes_ref[:]                      # [B]
        maskf = mask_ref[:].astype(jnp.float32)   # [B]
        vals = values_ref[:] * maskf[:, None]     # [B, A] masked values suffice
        onehot = (
            codes[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, G), 1)
        ).astype(jnp.float32)                     # [B, G]
        # the group-by: [G, B] @ [B, A] on the MXU. HIGHEST keeps the value
        # products at effectively f32: at the default precision the MXU
        # rounds the values to bf16, and the compiled kernel was 1.7e-3 off
        # numpy at 6M rows on a v5e (my chip run, PR 21) where the
        # interpreter, which multiplies in f32, had always agreed.
        out_ref[:] += jnp.dot(
            onehot.T, vals, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    @jax.jit
    def run(codes, values, mask):
        n = codes.shape[0]
        grid = (n // BLOCK_ROWS,)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
                pl.BlockSpec((BLOCK_ROWS, A), lambda i: (i, 0)),
                pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
            ],
            out_specs=pl.BlockSpec((G, A), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((G, A), jnp.float32),
            interpret=interpret,
        )(codes, values, mask)

    return run


SORT_BLOCK = 1024
_LANE = 128


@functools.lru_cache(maxsize=None)
def _build_sorted(n_values_padded: int, block: int, interpret: bool):
    """Sorted-rank grouped sum: rows are pre-sorted by group key and codes are
    DENSE ranks (consecutive distinct keys differ by exactly 1), so every
    block of B rows spans a rank window of at most B. Each grid step:

        local[v, w] = sum_b vals[v, b] * (codes[b] - base == w)

    — one [AV, B] @ [B, W] one-hot matmul on the MXU — accumulated into the
    HBM output at dynamic offset `base` via a read-modify-write DMA of the
    [AV, W] window. Cost is O(N * B) regardless of the total group count:
    this is what removes the device path's group-cardinality ceiling
    (reference hash aggregate: rust/core/proto/ballista.proto:370-384).

    Precision: one-hot entries are exact in bf16; HIGHEST precision keeps
    value products at effectively f32, accumulation is f32 adds.

    Status: compiles under Mosaic (JAX 0.9.0) and agrees with numpy at 6M
    rows for G=6 and G=1.5M; one call took 40 ms on a v5e (my chip run, PR
    21, dev/probe_pallas.py) — MXU utilization is capped by the skinny
    value dimension, and the RMW DMA serializes the grid. The
    chunked-segment layout (ops/layout.py + stage._sorted_core) is the
    default and has not been timed against it on the current machine; this
    kernel is selectable with ballista.tpu.sorted_kernel=pallas
    (sum/count/avg stages, stage.py::_run_pallas_sorted).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = block
    # window start is aligned down to the 128-lane tile so the dynamic DMA
    # offset is provably tile-divisible for Mosaic; the extra lane covers the
    # alignment slack, one more covers the in-block rank growth
    W = B + 2 * _LANE
    AV = n_values_padded

    def kernel(bases_ref, codes_ref, vals_ref, init_ref, out_ref,
               acc_ref, sem_in, sem_out):
        i = pl.program_id(0)
        base = (bases_ref[i] // _LANE) * _LANE
        window = out_ref.at[:, pl.ds(base, W)]
        copy_in = pltpu.make_async_copy(window, acc_ref, sem_in)
        copy_in.start()
        local = (codes_ref[:] - base)[None, :]
        onehot = (
            local == jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
        ).astype(jnp.float32)  # [W, B]
        prod = jax.lax.dot_general(
            vals_ref[:], onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # [AV, W]
        copy_in.wait()
        acc_ref[:] += prod
        copy_out = pltpu.make_async_copy(acc_ref, window, sem_out)
        copy_out.start()
        copy_out.wait()

    @jax.jit
    def run(bases, codes, vals, init):
        nb = codes.shape[0] // B
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((B,), lambda i, bases: (i,)),
                pl.BlockSpec((AV, B), lambda i, bases: (0, i)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((AV, W), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(init.shape, jnp.float32),
            input_output_aliases={3: 0},
            interpret=interpret,
        )(bases, codes, vals, init)

    return run


def sorted_grouped_sum(
    codes,
    values,
    num_groups: int,
    interpret: Optional[bool] = None,
):
    """Device arrays in, device array out: out[v, g] = sum of values[v, i]
    where codes[i] == g. codes must be sorted dense ranks (int32); values
    rows are pre-masked (a count output is just a mask row). Returns a
    device array [n_values, num_groups]; pure jit-compatible pieces, one
    pallas_call.
    """
    import jax.numpy as jnp

    if interpret is None:
        interpret = _interpret_by_default()
    nv, n = values.shape
    assert codes.shape == (n,)
    B = SORT_BLOCK
    assert n % B == 0, "pad rows to SORT_BLOCK host-side"
    AV = max(8, -(-nv // 8) * 8)  # sublane-pad the value dim
    if AV != nv:
        values = jnp.concatenate(
            [values, jnp.zeros((AV - nv, n), jnp.float32)], axis=0
        )
    bases = codes[::B]
    gpad = num_groups + B + 2 * _LANE
    init = jnp.zeros((AV, gpad), jnp.float32)
    out = _build_sorted(AV, B, interpret)(bases, codes, values, init)
    return out[:nv, :num_groups]


def grouped_aggregate(
    codes: np.ndarray,
    values: np.ndarray,
    mask: np.ndarray,
    num_groups: int,
    interpret: Optional[bool] = None,
) -> Optional[np.ndarray]:
    """Masked per-group sums: out[g, a] = sum(values[i, a] for codes[i]==g and
    mask[i]). Returns None when the kernel declines (no pallas, G too large).

    values: [N, A] float32; codes: [N] int32; mask: [N] bool.
    """
    if not pallas_available() or num_groups > 128:
        return None
    import jax.numpy as jnp

    if interpret is None:
        interpret = _interpret_by_default()
    n = len(codes)
    if n == 0:
        return np.zeros((num_groups, values.shape[1]), dtype=np.float32)
    pad = (-n) % BLOCK_ROWS
    if pad:
        codes = np.concatenate([codes, np.full(pad, -1, dtype=codes.dtype)])
        values = np.concatenate(
            [values, np.zeros((pad, values.shape[1]), dtype=values.dtype)]
        )
        mask = np.concatenate([mask, np.zeros(pad, dtype=bool)])
    run = _build(num_groups, values.shape[1], interpret)
    out = run(
        jnp.asarray(codes.astype(np.int32)),
        jnp.asarray(values.astype(np.float32)),
        jnp.asarray(mask),
    )
    from ballista_tpu.ops.runtime import readback

    return readback(out, rows=num_groups)  # [G, A]: the row axis leads

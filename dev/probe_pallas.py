"""Do the Pallas kernels compile, and are they right? Both kernels of
ops/pallas_kernels.py, COMPILED (interpret=False) on the current device at a
production shape (6M rows; G=6 and G~1.5M), against numpy.

    python dev/probe_pallas.py                      # on the chip
    JAX_PLATFORMS=cpu python dev/probe_pallas.py    # interpreted, 32k rows

Exit code 1 when a kernel fails to compile or disagrees with numpy; the
times are one-off observations of this run, not benchmark numbers.
"""

import sys
import time
import traceback

import numpy as np

sys.path.insert(0, ".")


def _sorted_case(rng, N, G, interpret):
    import jax.numpy as jnp

    from ballista_tpu.ops.pallas_kernels import SORT_BLOCK, sorted_grouped_sum

    # sorted dense ranks with random segment lengths
    lens = rng.integers(1, max(2, 2 * N // G), G)
    codes_np = np.repeat(np.arange(G, dtype=np.int32), lens)[:N]
    if len(codes_np) < N:
        codes_np = np.concatenate(
            [codes_np, np.full(N - len(codes_np), codes_np[-1], np.int32)]
        )
    G_real = int(codes_np.max()) + 1
    v_np = rng.uniform(0, 100_000, N).astype(np.float32)
    mask_np = (rng.uniform(size=N) < 0.54).astype(np.float32)
    pad = (-N) % SORT_BLOCK
    if pad:
        codes_np = np.concatenate([codes_np, np.full(pad, codes_np[-1], np.int32)])
        v_np = np.concatenate([v_np, np.zeros(pad, np.float32)])
        mask_np = np.concatenate([mask_np, np.zeros(pad, np.float32)])
    codes = jnp.asarray(codes_np)
    vals = jnp.asarray(np.stack([mask_np, v_np * mask_np]))

    out = sorted_grouped_sum(codes, vals, G_real, interpret=interpret)
    out.block_until_ready()
    t0 = time.perf_counter()
    out = sorted_grouped_sum(codes, vals, G_real, interpret=interpret)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    got = np.asarray(out, dtype=np.float64)
    want_sum = np.zeros(G_real)
    np.add.at(want_sum, codes_np, (v_np * mask_np).astype(np.float64))
    want_cnt = np.zeros(G_real)
    np.add.at(want_cnt, codes_np, mask_np.astype(np.float64))
    rel = np.abs(got[1] - want_sum).max() / max(1.0, want_sum.max())
    cnt = np.abs(got[0] - want_cnt).max()
    print(f"sorted_grouped_sum N={N} G={G_real}: second call {dt * 1e3:.2f}ms "
          f"sum maxrel {rel:.2e} count maxabs {cnt:.1e}")
    assert rel < 1e-4 and cnt == 0, (rel, cnt)


def _grouped_case(rng, N, G, interpret):
    from ballista_tpu.ops.pallas_kernels import grouped_aggregate

    A = 4
    codes = rng.integers(0, G, N).astype(np.int32)
    vals = rng.uniform(-5, 5, (N, A)).astype(np.float32)
    mask = rng.random(N) > 0.4
    grouped_aggregate(codes, vals, mask, G, interpret=interpret)
    t0 = time.perf_counter()
    out = grouped_aggregate(codes, vals, mask, G, interpret=interpret)
    dt = time.perf_counter() - t0
    want = np.zeros((G, A))
    np.add.at(want, codes[mask], vals[mask].astype(np.float64))
    rel = np.abs(out - want).max() / max(1.0, np.abs(want).max())
    print(f"grouped_aggregate N={N} G={G}: second call (h2d + kernel + d2h) "
          f"{dt * 1e3:.2f}ms maxrel {rel:.2e}")
    assert rel < 1e-4, rel


def main() -> int:
    import jax

    d = jax.devices()[0]
    print(f"platform={d.platform} device_kind={d.device_kind} "
          f"count={len(jax.devices())}")
    interpret = d.platform == "cpu"
    N = 1 << 15 if interpret else 6_000_000
    rng = np.random.default_rng(0)
    cases = [
        ("grouped_aggregate G=6", lambda: _grouped_case(rng, N, 6, interpret)),
        ("sorted_grouped_sum G=6", lambda: _sorted_case(rng, N, 6, interpret)),
        ("sorted_grouped_sum G~N/4",
         lambda: _sorted_case(rng, N, max(8, N // 4), interpret)),
    ]
    failed = 0
    for name, case in cases:
        try:
            case()
            print(f"OK   {name} ({'interpreted' if interpret else 'compiled'})")
        except Exception:
            failed += 1
            print(f"FAIL {name}")
            traceback.print_exc(limit=6, file=sys.stdout)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

"""On-chip gather-rate probes: the cost model behind every ELL-family
kernel (PERFORMANCE.md "layout-padding law").

Measures the XLA gather rate (slots/s) as a function of feature count,
dtype, and index sortedness, plus the SELL fold step at protocol scale
for k in {16, 128}.  Run on the chip:

    python tools/gather_probe.py
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench(f, *a, reps: int = 5) -> float:
    import jax

    o = f(*a)
    jax.block_until_ready(o)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        o = f(*a)
        jax.block_until_ready(o)
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e3


def gather_rates() -> None:
    import jax
    import jax.numpy as jnp

    n, m = 1 << 20, 16
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n, size=n * m, dtype=np.int32)
    idx_sorted = np.sort(idx)
    slots = idx.size
    for k in (16, 64, 128):
        for dt in ("f32", "bf16"):
            x = rng.standard_normal((k, n)).astype(np.float32)
            xd = jnp.asarray(x if dt == "f32" else x.astype(jnp.bfloat16))
            f = jax.jit(lambda xx, ii: jnp.take(xx, ii, axis=1))
            ms = bench(f, xd, jnp.asarray(idx))
            ms_s = bench(f, xd, jnp.asarray(idx_sorted))
            print(f"k={k:4d} {dt}: {ms:8.2f} ms "
                  f"({slots / ms / 1e3:.0f}M slots/s) sorted {ms_s:8.2f} ms",
                  flush=True)


def fold_step(k: int) -> None:
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    from bench import _cached_levels, _measure

    from arrow_matrix_tpu.parallel.multi_level import (
        MultiLevelArrow,
        resolve_feature_dtype,
    )
    from arrow_matrix_tpu.utils.graphs import random_dense

    n = 1 << 20
    levels = _cached_levels(n, 8, 2048, seed=7, max_levels=12)
    x_host = random_dense(n, k, seed=3)
    # One build, both carriage dtypes: feature_dtype is consumed only
    # by set_features (the operator blocks are bit-identical), so
    # retargeting the attribute measures bf16 without a second
    # multi-GB build + upload.
    multi = MultiLevelArrow(levels, 2048, mesh=None, fmt="fold")
    sell = multi.blocks[0]
    print(f"fold k={k}: tiers={len(sell.cols)} slots={sell.n_slots} "
          f"({sell.n_slots / sum(l.matrix.nnz for l in levels):.2f}x nnz) "
          f"bytes={sell.device_nbytes() / 2**30:.2f}GB", flush=True)
    for fd in (None, "bf16"):
        multi.feature_dtype = resolve_feature_dtype(fd)
        x = multi.set_features(x_host)
        ms = _measure(multi, x, 10)
        print(f"fold k={k} feat={fd or 'f32'}: {ms:.2f} ms/iter "
              f"({sell.n_slots / ms / 1e3:.0f}M slots/s)", flush=True)


def main() -> None:
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    gather_rates()
    for k in (16, 128):
        fold_step(k)


if __name__ == "__main__":
    main()

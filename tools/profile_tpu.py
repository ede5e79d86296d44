"""Per-component timing of the multi-level SpMM step on the live chip.

Breaks the bench iteration into its constituent device programs — each
level's full arrow SpMM, that level's head/diag/col stacks separately,
and the inter-level routing gathers — so a slow iteration can be
attributed to a specific kernel (the reference's per-segment timing
philosophy, reference arrow/common/wb_logging.py, applied at kernel
granularity).

Timing goes through the shared ``obs/tracer.py:call_time_ms`` harness
(this script's former private ``timeit`` loop, promoted there), and
every probe is also sunk to a run-dir ledger with the live host load
attached, so an attribution taken on a loaded host is recognisable
after the fact.  Set ``AMT_PROFILE_LEDGER`` to choose the sink
directory (default: a timestamped ``bench_results/profile_runs/``
subdirectory — never the committed drift-gate store).

Usage:  python tools/profile_tpu.py [n] [width] [k]
"""

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from arrow_matrix_tpu.obs.tracer import call_time_ms

_LEDGER = None


def _ledger():
    """Lazy run-dir ledger sink (one per process)."""
    global _LEDGER
    if _LEDGER is None:
        from arrow_matrix_tpu.ledger.store import Ledger
        d = os.environ.get("AMT_PROFILE_LEDGER")
        if not d:
            d = os.path.join("bench_results", "profile_runs",
                             time.strftime("%Y%m%d-%H%M%S"))
        os.makedirs(d, exist_ok=True)
        _LEDGER = Ledger(d)
        print(f"ledger: {_LEDGER.path}", flush=True)
    return _LEDGER


def timeit(fn, *args, iters=5, name="call", **labels) -> float:
    """ms per call via the shared harness, sunk to the run ledger.

    ``host_load`` is left to the ledger's live lookup on purpose:
    these are load-SENSITIVE wall-clock probes, unlike the
    load-invariant lens ratios which pin it to None.
    """
    ms = call_time_ms(fn, *args, iters=iters)
    _ledger().record(
        "probe", "call_time_ms", ms, unit="ms",
        knobs={"call": name, "iters": iters,
               **{k: v for k, v in labels.items() if v is not None}})
    return ms


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
    width = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
    k = int(sys.argv[3]) if len(sys.argv) > 3 else 16

    from arrow_matrix_tpu.ops.arrow_blocks import (
        arrow_spmm,
        block_spmm,
        block_spmm_shared,
        head_block_spmm,
    )
    from arrow_matrix_tpu.parallel.multi_level import (
        MultiLevelArrow,
        gather_budget_for,
        resolve_chunk,
    )
    from arrow_matrix_tpu.utils.graphs import random_dense
    from arrow_matrix_tpu.utils.platform import device_memory_budget

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)

    # Cached, CONVERGED decomposition — the same problem bench.py runs
    # (a max_levels cap would re-create the degenerate-last-level
    # pathology the bench no longer executes; see PERFORMANCE.md).
    from bench import _cached_levels

    t0 = time.perf_counter()
    levels = _cached_levels(n, 8, width, seed=7, max_levels=12)
    print(f"{n} rows -> {len(levels)} levels "
          f"in {time.perf_counter() - t0:.1f}s", flush=True)

    budget = device_memory_budget(dev)
    fmt = os.environ.get("AMT_PROFILE_FMT", "auto")
    if fmt in ("sell", "sell-space"):
        # Feature-major mesh orchestrations: per-level step attribution
        # (one shard_map'd slim step each) + full chained step.  Mesh
        # from AMT_PROFILE_DEVICES (default: all).
        from arrow_matrix_tpu.parallel import (
            SellMultiLevel,
            SellSpaceShared,
            make_mesh,
        )

        n_dev = int(os.environ.get("AMT_PROFILE_DEVICES",
                                   len(jax.devices())))
        x_host = random_dense(n, k, seed=3)
        if fmt == "sell":
            sm = SellMultiLevel(levels, width,
                                make_mesh((n_dev,), ("blocks",)),
                                routing="a2a")
            print(f"sell/a2a on {n_dev} devices; "
                  f"total_out={sm.ops[0].total_out}", flush=True)
            from arrow_matrix_tpu.parallel.sell_slim import (
                make_sharded_step,
            )

            x = sm.set_features(x_host)
            print(f"full step: "
                  f"{timeit(sm.step, x, name='full_step', fmt='sell'):.1f}"
                  f" ms", flush=True)
            steps = [make_sharded_step(sm.mesh, sm.axis, width,
                                       o.rows_out, hops=o.hops,
                                       rem=o.rem,
                                       gather_budget=sm.gather_budget)
                     for o in sm.ops]
            for i, (o, st) in enumerate(zip(sm.ops, steps)):
                f = jax.jit(st)
                ms_i = timeit(f, o.body, o.head, o.head_unsort,
                              o.orig_pos, x[:, :o.total_out],
                              name=f"level{i}", fmt="sell")
                print(f"level {i}: hops={o.hops} rows_out={o.rows_out} "
                      f"{ms_i:.2f} ms", flush=True)
        else:
            K = len(levels)
            sp = SellSpaceShared(levels, width,
                                 make_mesh((K, max(n_dev // K, 1)),
                                           ("lvl", "blocks")))
            x = sp.set_features(x_host)
            print(f"sell/space on ({K},{max(n_dev // K, 1)}) mesh: "
                  f"full step "
                  f"{timeit(sp.step, x, name='full_step', fmt='sell-space'):.1f}"
                  f" ms", flush=True)
        return
    multi = MultiLevelArrow(levels, width, mesh=None, fmt=fmt,
                            dense_budget=budget)
    print(f"fmts: {multi.fmts}  total_rows: {multi.total_rows}", flush=True)

    x_host = random_dense(n, k, seed=3)
    x = multi.set_features(x_host)

    ms = timeit(multi.step, x, name="full_step", fmt=fmt)
    print(f"full step: {ms:.1f} ms", flush=True)

    if fmt == "fold":
        # Per-tier attribution of the folded SELL operator.
        from arrow_matrix_tpu.ops.ell import auto_chunk, ell_spmm_t
        from arrow_matrix_tpu.parallel.multi_level import gather_budget_for

        sell = multi.blocks[0]
        gb = gather_budget_for(multi.dense_budget)
        for t, cols in enumerate(sell.cols):
            m_t, n_t = cols.shape
            if m_t == 0:
                print(f"tier {t}: m=0 n={n_t} (zero-degree rows)",
                      flush=True)
                continue
            chunk = auto_chunk(n_t, k, m_t, gb)
            f = jax.jit(lambda c, dg, xx, ch=chunk: ell_spmm_t(
                c, xx, deg=dg, chunk=ch))
            ms_t = timeit(f, cols, sell.deg[t], x,
                          name=f"tier{t}", fmt="fold")
            print(f"tier {t}: m={m_t} n={n_t} slots={m_t * n_t} "
                  f"{ms_t:.2f} ms ({m_t * n_t / ms_t / 1e3:.0f}M slots/s)",
                  flush=True)
        return

    total = multi.total_rows
    gather_budget = gather_budget_for(multi.dense_budget)
    for i, blk in enumerate(multi.blocks):
        w = multi.widths[i]
        xb = jnp.reshape(x, (total // w, w, k))
        chunk = resolve_chunk("auto", blk, total, k, gather_budget)
        lvl_ms = timeit(jax.jit(functools.partial(arrow_spmm, chunk=chunk)),
                        blk, xb, name=f"level{i}_full", fmt=blk.fmt)
        if blk.head_gell:
            from arrow_matrix_tpu.ops.ell import ell_spmm

            head_ms = timeit(
                jax.jit(lambda b, xx, c=chunk: ell_spmm(
                    b.head_cols, b.head_data,
                    xx.reshape(-1, xx.shape[-1]), chunk=c,
                    deg=b.head_deg)), blk, xb,
                name=f"level{i}_head", fmt=blk.fmt)
        else:
            head_ms = timeit(
                jax.jit(functools.partial(head_block_spmm, chunk=chunk)),
                blk, xb, name=f"level{i}_head", fmt=blk.fmt)
        diag_ms = timeit(
            jax.jit(lambda b, xx, c=chunk: block_spmm(
                b.fmt, b.diag_cols, b.diag_data, xx, chunk=c,
                deg=b.diag_deg)), blk, xb,
            name=f"level{i}_diag", fmt=blk.fmt)
        col_ms = timeit(
            jax.jit(lambda b, xx, c=chunk: block_spmm_shared(
                b.fmt, b.col_cols, b.col_data, xx[0], chunk=c,
                deg=b.col_deg)), blk, xb,
            name=f"level{i}_col", fmt=blk.fmt)
        nnz = int(levels[i].matrix.nnz)
        head_kind = ("gell" if blk.head_gell
                     else "flat" if blk.head_flat else blk.fmt)
        print(f"level {i}: fmt={blk.fmt} w={w} head={head_kind} "
              f"nnz={nnz} full={lvl_ms:.1f}ms head={head_ms:.1f}ms "
              f"diag={diag_ms:.1f}ms col={col_ms:.1f}ms", flush=True)

    if len(multi.blocks) > 1:
        fwd = multi.fwd
        take_ms = timeit(jax.jit(lambda xx, t: jnp.take(xx, t, axis=0)),
                         x, fwd[0], name="routing_gather")
        print(f"routing gather (one exchange): {take_ms:.1f} ms", flush=True)


if __name__ == "__main__":
    main()

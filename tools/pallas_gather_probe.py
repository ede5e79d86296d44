"""Bounded Pallas granule-DMA gather experiment (VERDICT r3 item 2iii).

PERFORMANCE.md's "why no Pallas gather kernel" analysis rejected
*per-row* async DMAs (64 B copies, issue-cost-bound) from first
principles.  This probe settles the remaining open case empirically:
**granule-sized** DMAs — features packed so 8 consecutive rows form one
contiguous 512 B line ``(n/8, 128) f32`` — against XLA's materializing
take on the same chip, same indices.

Three measured variants, each its own jit/pallas program:

1. ``xla_take``      — jnp.take feature-major (k, n), the framework's
                       production gather (reference rate).
2. ``xla_granule``   — jnp.take of packed granule rows (n/8, 128) +
                       in-register sub-row select: tests whether XLA's
                       row gather of full-lane 512 B rows beats its
                       sub-transaction 64 B column gather per slot.
3. ``pallas_granule``— hand-pipelined Pallas kernel: per-slot async
                       copies of 512 B granule lines HBM->VMEM in
                       waves of W in-flight DMAs, then a vectorized
                       sub-row select.  Measures the DMA issue rate
                       against the analysis' ~50-cycle estimate.

Output: one JSON line with M slots/s per variant (plus ms), the
committed confirm-or-falsify artifact.
Run on CPU (AMT_PROBE_CPU=1, interpret mode, small shapes) only to
validate correctness of the select logic — rates are chip-only.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

C = 8          # rows per granule: 8 x 16 feats x f32 = 512 B lines
K = 16         # features (the k=16 headline regime — the hard case)
LANES = C * K  # 128


def _bench_ms(f, *args, reps: int = 5) -> float:
    import jax

    o = f(*args)
    jax.block_until_ready(o)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        o = f(*args)
        jax.block_until_ready(o)
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e3


def xla_take(x_t, idx):
    """Production gather: feature-major materializing take."""
    import jax.numpy as jnp

    return jnp.take(x_t, idx, axis=1)


def xla_granule(x_packed, idx):
    """Packed-granule take + sub-row select, pure XLA."""
    import jax.numpy as jnp

    g = jnp.take(x_packed, idx // C, axis=0)          # (S, 128)
    off = (idx % C).astype(jnp.int32)                  # (S,)
    lane = jnp.arange(LANES, dtype=jnp.int32) // K     # (128,) -> granule row
    mask = (lane[None, :] == off[:, None])             # (S, 128)
    masked = jnp.where(mask, g, 0.0)
    # Fold the C segments of 16 lanes into one (S, 16) result.
    return masked.reshape(-1, C, K).sum(axis=1)


def make_pallas_granule(n_granules: int, block: int, wave: int,
                        interpret: bool = False):
    """Pallas kernel: gather ``block`` granule lines per grid step with
    ``wave`` async copies in flight, select sub-rows, emit (block, K)
    packed as (block // C, LANES)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert block % C == 0 and block % wave == 0

    def kernel(idx_smem, idx_vmem, x_hbm, out_ref, scratch, sems):
        n_waves = block // wave
        # idx_smem is the WHOLE (S,) index array (scalar prefetch);
        # this grid step owns slots [pid*block, (pid+1)*block).
        blk0 = pl.program_id(0) * block

        def do_wave(w, _):
            base = w * wave

            def start(j, __):
                s = base + j
                g = idx_smem[blk0 + s] // C
                pltpu.make_async_copy(
                    x_hbm.at[g], scratch.at[s], sems.at[j]).start()
                return __

            jax.lax.fori_loop(0, wave, start, 0)

            def wait(j, __):
                s = base + j
                g = idx_smem[blk0 + s] // C
                pltpu.make_async_copy(
                    x_hbm.at[g], scratch.at[s], sems.at[j]).wait()
                return __

            jax.lax.fori_loop(0, wave, wait, 0)
            return _

        jax.lax.fori_loop(0, n_waves, do_wave, 0)
        # Vectorized sub-row select over the whole block.
        off = (idx_vmem[:] % C).astype(jnp.int32)          # (block,)
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (block, LANES), 1) // K
        masked = jnp.where(lane == off[:, None], scratch[:], 0.0)
        picked = masked.reshape(block // C, C, C, K).sum(axis=2)
        out_ref[:] = picked.reshape(block // C, LANES)

    def run(x_packed, idx):
        s = idx.shape[0]
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,        # idx -> SMEM, whole array
            grid=(s // block,),
            in_specs=[
                pl.BlockSpec((block,), lambda i, sc: (i,),
                             memory_space=pltpu.VMEM),  # idx, vector math
                pl.BlockSpec(memory_space=pl.ANY),      # x stays in HBM
            ],
            out_specs=pl.BlockSpec((block // C, LANES),
                                   lambda i, sc: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((block, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((wave,)),
            ],
        )
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((s // C, LANES), jnp.float32),
            grid_spec=gs,
            interpret=interpret,
        )(idx, idx, x_packed)

    return jax.jit(run)


def main() -> None:
    cpu = os.environ.get("AMT_PROBE_CPU") == "1"
    if cpu:
        from arrow_matrix_tpu.utils.platform import force_cpu_devices

        force_cpu_devices()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    out: dict = {"metric": "pallas_gather_probe",
                 "platform": dev.platform, "device_kind": dev.device_kind,
                 "variants": {}}
    n = 1 << 14 if cpu else 1 << 20
    s = 1 << 12 if cpu else 1 << 21
    block, wave = (64, 16) if cpu else (1024, 32)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((n, K)).astype(np.float32)
    idx = rng.integers(0, n, size=s, dtype=np.int32)
    x_t = jnp.asarray(np.ascontiguousarray(x.T))               # (K, n)
    x_packed = jnp.asarray(x.reshape(n // C, LANES))           # (n/8, 128)
    idx_d = jnp.asarray(idx)
    out.update({"n": n, "slots": s, "k": K, "granule": C,
                "block": block, "wave": wave})

    want = x[idx]                                              # (S, K)

    def check(name, got, reshape_packed=False):
        g = np.asarray(got)
        if reshape_packed:
            g = g.reshape(-1, K)
        err = float(np.abs(g - want).max())
        ok = err < 1e-6
        out["variants"].setdefault(name, {})["exact"] = ok
        if not ok:
            out["variants"][name]["max_err"] = err
        return ok

    f1 = jax.jit(xla_take)
    check("xla_take", f1(x_t, idx_d).T)
    ms = _bench_ms(f1, x_t, idx_d)
    out["variants"]["xla_take"].update(
        ms=round(ms, 2), mslots_s=round(s / ms / 1e3, 1))

    f2 = jax.jit(xla_granule)
    check("xla_granule", f2(x_packed, idx_d))
    ms = _bench_ms(f2, x_packed, idx_d)
    out["variants"]["xla_granule"].update(
        ms=round(ms, 2), mslots_s=round(s / ms / 1e3, 1))

    try:
        f3 = make_pallas_granule(n // C, block, wave, interpret=cpu)
        check("pallas_granule", f3(x_packed, idx_d),
              reshape_packed=True)
        ms = _bench_ms(f3, x_packed, idx_d)
        out["variants"]["pallas_granule"].update(
            ms=round(ms, 2), mslots_s=round(s / ms / 1e3, 1))
    except Exception as e:
        out["variants"]["pallas_granule"] = {
            "error": f"{type(e).__name__}: {str(e)[:400]}"}
    v = out["variants"]
    # Verdict gates on the MEASURED platform, not the env flag: CPU
    # timings must never write a "productionize" verdict.
    if dev.platform != "cpu" and all(("mslots_s" in v.get(k, {})
                                      and v[k].get("exact") is True)
                                     for k in ("xla_take",
                                               "pallas_granule")):
        # Verdict requires BOTH variants exact: a fast kernel that
        # returns wrong gathers must never read "productionize".
        # The committed confirm-or-falsify verdict (VERDICT r4 item
        # 5): does the wave-pipelined granule DMA beat XLA's take by
        # enough to productionize as the SELL gather kernel?
        ratio = (v["pallas_granule"]["mslots_s"]
                 / max(v["xla_take"]["mslots_s"], 1e-9))
        out["pallas_vs_xla"] = round(ratio, 2)
        out["verdict"] = ("pallas_wins — productionize"
                          if ratio > 1.1 else "xla_holds")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

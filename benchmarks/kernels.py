"""Kernel-level benchmarks + the CMAX-side §Perf iteration evidence.

1) allclose sanity + CPU(interpret) wall-times for both Pallas kernels
   (wall-time on CPU interpret mode is NOT TPU-representative; it's the
   correctness-under-load harness).
2) The tile-config hillclimb for iwe_accum, with the two quantities that
   ARE structural (target-valid): per-tile VMEM working set and the
   measured spill rate on realistic (poster-like) event windows as a
   function of per-tile capacity. The chosen default (8x128 tile, cap 1024)
   is the smallest config with 0 measured spill and MXU-aligned shapes.
3) HBM-traffic ratio of the kernel dataflow vs the scatter-RMW baseline —
   the TPU analogue of the paper's Table 3 'effective memory accesses'.
4) The batched megakernel suite: per stage config, megakernel-vs-reference
   equivalence, measured spill, interpret-mode wall time, and the analytic
   roofline placement (achieved vs roofline FLOPs/byte, HBM-traffic ratio
   vs the unfused kernel pair and the scatter baseline) from
   repro.roofline's CMAX-kernel mode. Persisted as BENCH_kernels.json
   (env BENCH_KERNELS_OUT overrides the path) and gated by
   scripts/check_kernels_baseline.py.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from .common import emit, time_call
from repro.core import Camera, EventWindow
from repro.core.geometry import warp_events
from repro.core.pipeline import make_engine_pass
from repro.core.types import CmaxConfig
from repro.kernels import batched_engine_pass, blur_stats, iwe_accum
from repro.kernels.iwe_accum import CHUNK
from repro.kernels.ref import blur_stats_ref, iwe_accum_ref
from repro.data import events as ev_data
from repro.roofline import (cmax_megakernel_costs, cmax_scatter_costs,
                            cmax_unfused_costs, default_hw, kernel_roofline)


def _window(n=8192, seed=0):
    spec = dataclasses.replace(ev_data.POSTER, n_windows=1,
                               events_per_window=n, n_features=2000,
                               jerk_prob=0.0)
    wins, om_true, _ = ev_data.make_sequence(spec)
    return ev_data.window_slice(wins, 0), jnp.asarray(om_true[0]), \
        spec.camera


def _batch(n_windows=2, n=4096):
    spec = dataclasses.replace(ev_data.POSTER, n_windows=n_windows,
                               events_per_window=n, n_features=2000,
                               jerk_prob=0.0)
    wins, om_true, _ = ev_data.make_sequence(spec)
    return wins, jnp.asarray(om_true), spec.camera


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


def _megakernel_suite(out: dict) -> dict:
    """Batched megakernel: equivalence, spill, timing, roofline placement.

    Interpret-mode wall time is reported (achieved_* fields) but NOT the
    gated quantity — it is not TPU-representative. The gate rides on the
    structural numbers: equivalence error, spill rate, and the analytic
    HBM-traffic ratios."""
    B, N = 2, 4096
    capacity, rb = 4096, 8
    batch, om_true, cam = _batch(B, N)
    cfg = CmaxConfig(camera=cam)   # paper-default stages
    hw = default_hw()

    report = {
        "hw_profile": "tpu_v5e_estimate",
        "hw": dataclasses.asdict(hw),
        "config": {"B": B, "n_events": N, "capacity": capacity, "rb": rb,
                   "chunk": CHUNK,
                   "camera": f"{cam.width}x{cam.height}"},
        "kernels": {},
    }

    for stage in cfg.stages:
        s, k = stage.scale, stage.blur_taps
        Hs, Ws = cam.grid(s)
        half = k // 2
        n_slabs = -(-(Hs + half) // rb)
        Wp = _ceil_to(Ws + half, 128)
        # size the per-slab tap budget from measured occupancy at the
        # entry hypothesis (+25% drift margin), same philosophy as the
        # iwe tile hillclimb: smallest zero-spill budget, chunk-aligned
        occ = 0
        for b in range(B):
            w = warp_events(ev_data.window_slice(batch, b), om_true[b],
                            cam, s)
            rows = np.concatenate([np.asarray(w.y0) + dy
                                   for dy in (0, 0, 1, 1)])
            ok = np.concatenate([np.asarray(w.in_range)] * 4)
            cnt = np.bincount(np.clip(rows[ok], 0, n_slabs * rb - 1) // rb,
                              minlength=n_slabs)
            occ = max(occ, int(cnt.max()))
        cap_s = max(int(1.25 * occ), CHUNK)
        cap = _ceil_to(cap_s, CHUNK)

        call = lambda om: batched_engine_pass(
            batch, om, cam, s, k, stage.blur_sigma, rb=rb,
            capacity=cap_s)
        v_mk, g_mk, spilled = call(om_true)
        us = time_call(lambda: call(om_true), iters=2)

        ref_engine = jax.vmap(make_engine_pass(cam, stage, jnp.float32))
        v_ref, g_ref = ref_engine(batch, jnp.ones((B, N), jnp.float32),
                                  om_true)
        rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                                 (jnp.max(jnp.abs(b)) + 1e-12))
        err = max(rel(v_mk, v_ref), rel(g_mk, g_ref))
        spill_rate = float(jnp.sum(spilled)) / (B * N * 4)

        mk = cmax_megakernel_costs(Hs, Ws, n_slabs, cap, k, rb, Wp)
        uf = cmax_unfused_costs(Hs, Ws, N, n_slabs * cap, k, Wp)
        sc = cmax_scatter_costs(Hs, Ws, N, k)
        roof = kernel_roofline(mk["flops"], mk["hbm_bytes"],
                               seconds=us * 1e-6 / B, hw=hw)
        roof["achieved_flops_interpret"] = roof.pop("achieved_flops")
        roof["achieved_fraction_interpret"] = roof.pop("achieved_fraction")
        entry = dict(
            roof,
            interpret_us_per_window=us / B,
            spill_rate=spill_rate,
            max_rel_err_vs_reference=err,
            traffic_ratio_vs_unfused=mk["hbm_bytes"] / uf["hbm_bytes"],
            traffic_ratio_vs_scatter=mk["hbm_bytes"] / sc["hbm_bytes"],
        )
        name = f"megakernel_s{s:g}"
        report["kernels"][name] = entry
        report["kernels"][f"unfused_pair_s{s:g}"] = kernel_roofline(
            uf["flops"], uf["hbm_bytes"], hw=hw)
        report["kernels"][f"scatter_reference_s{s:g}"] = kernel_roofline(
            sc["flops"], sc["hbm_bytes"], hw=hw)
        emit(name, us,
             f"rel_err={err:.2e};spill={100 * spill_rate:.2f}%;"
             f"AI={roof['arithmetic_intensity']:.0f}flops/B;"
             f"roofline_frac={roof['roofline_fraction']:.2f};"
             f"traffic_vs_scatter={entry['traffic_ratio_vs_scatter']:.2f}")
        out[name] = dict(err=err, spill=spill_rate)

    out_path = os.environ.get(
        "BENCH_KERNELS_OUT",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_kernels.json"))
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("kernels_baseline_written", 0.0, out_path)
    return report


def run() -> dict:
    ev, om, cam = _window()
    out = {}

    # --- correctness + interpret timings ---
    t_ref = time_call(lambda: iwe_accum_ref(ev, om, cam, 1.0))
    t_ker = time_call(lambda: iwe_accum(ev, om, cam, 1.0, capacity=2048))
    got = iwe_accum(ev, om, cam, 1.0, capacity=2048)
    ref = iwe_accum_ref(ev, om, cam, 1.0)
    err = float(jnp.max(jnp.abs(got.channels - ref)))
    emit("kernel_iwe_accum_ref", t_ref, "pure-XLA scatter oracle")
    emit("kernel_iwe_accum_pallas", t_ker,
         f"interpret-mode; max_abs_err={err:.2e}; spilled={int(got.spilled)}")

    ch = ref
    t_bref = time_call(lambda: blur_stats_ref(ch, 9, 1.0))
    t_bker = time_call(lambda: blur_stats(ch, 9, 1.0))
    bk = np.asarray(blur_stats(ch, 9, 1.0))
    br = np.asarray(blur_stats_ref(ch, 9, 1.0))
    # normalized by the stats-vector scale (T_j sums are ~0 by symmetry,
    # plain relative error there is meaningless)
    nerr = float(np.max(np.abs(bk - br)) / (np.max(np.abs(br)) + 1e-12))
    emit("kernel_blur_stats_ref", t_bref, "materializing oracle")
    emit("kernel_blur_stats_pallas", t_bker,
         f"interpret-mode; norm_err={nerr:.2e}")

    # --- tile-config hillclimb: spill rate vs capacity (measured) ---
    w = warp_events(ev, om, cam, 1.0)
    for (TH, TW) in ((8, 128), (16, 128), (4, 256), (8, 256)):
        Hs, Ws = cam.grid(1.0)
        nty, ntx = -(-Hs // TH), -(-Ws // TW)
        ty = np.concatenate([np.asarray(w.y0) + dy for dy in (0, 0, 1, 1)])
        tx = np.concatenate([np.asarray(w.x0) + dx for dx in (0, 1, 0, 1)])
        valid = np.concatenate([np.asarray(w.in_range)] * 4)
        tid = np.where(valid, (ty // TH) * ntx + tx // TW, nty * ntx)
        cnt = np.bincount(tid[valid], minlength=nty * ntx)
        for cap in (256, 512, 1024, 2048):
            spilled = np.maximum(cnt - cap, 0).sum()
            frac = spilled / max(valid.sum(), 1)
            vmem_kb = (cap * TH * TW * 4            # onehot f32
                       + cap * 4 * 4 + TH * TW * 4 * 4) / 1024
            emit(f"iwe_tile_{TH}x{TW}_cap{cap}", 0.0,
                 f"spill={100 * frac:.2f}%;vmem={vmem_kb:.0f}KB;"
                 f"mxu_aligned={'yes' if (TH * TW) % 128 == 0 else 'no'}")
            out[f"{TH}x{TW}/{cap}"] = dict(spill=float(frac),
                                           vmem_kb=float(vmem_kb))

    # --- per-pass HBM traffic vs scatter-RMW baseline (Table-3 analogue),
    # at the paper's 40k-event window scale ---
    Hs, Ws = cam.grid(1.0)
    for n in (8192, 40000):
        raw = n * 16                                  # event records read
        scatter_rmw = raw + n * 16 * 2 * 4            # 16 lanes RMW, f32
        kernel_traffic = (raw + n * 4 * 4             # sorted tap indices
                          + Hs * Ws * 4 * 4)          # one image commit
        emit(f"iwe_hbm_traffic_ratio_n{n}", 0.0,
             f"scatter_rmw={scatter_rmw / 1e6:.2f}MB;"
             f"kernel={kernel_traffic / 1e6:.2f}MB;"
             f"reduction={100 * (1 - kernel_traffic / scatter_rmw):.1f}%")
        out[f"traffic_reduction_n{n}"] = 1 - kernel_traffic / scatter_rmw

    # --- batched megakernel: equivalence + spill + roofline placement ---
    out["megakernel_report"] = _megakernel_suite(out)
    return out


if __name__ == "__main__":
    run()

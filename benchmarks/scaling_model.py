"""Render the analytic scaling-model table (utils/scaling_model.py) —
the committed artifact for the ≥90 % v4-8 → v4-128 north star.

Usage: python benchmarks/scaling_model.py [--json PATH] [--markdown]

Pure host-side arithmetic: no jax import, no device work.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_vgg_f_tpu.utils.scaling_model import (  # noqa: E402
    ASSUMPTIONS, HOST_DECODE_RATE_R5, HOST_DECODE_RATE_R6,
    HOST_DECODE_RATE_R7, HOST_DECODE_RATE_R8, HOST_DECODE_RATE_R9,
    MEASURED, V4, V5E,
    host_provisioning_requirement,
    host_provisioning_table, north_star_summary, predict, predict_table,
    ring_attention_comm_model, ulysses_comm_model)


def sp_layout_comparison(n_chips: int = 8,
                         t_locals=(512, 1024, 1910, 3820, 8192)) -> dict:
    """The committed ring-vs-ulysses layout table (parallel/ring_attention
    vs parallel/ulysses): per T_local, the ring's EXPOSED comm (what its
    pipeline fails to hide under block compute) against the ulysses
    all-to-all wire time (charged fully exposed). The rule the numbers
    show: ulysses wins below ≈ half the ring's break-even length; from
    there up the ring's exposure shrinks to zero while the all-to-alls
    remain. Indivisible head counts no longer disqualify ulysses — they
    are zero-padded (parallel/ulysses.py) and charged ceil(H/n)·n/H here."""
    rows = []
    for t in t_locals:
        r = ring_attention_comm_model(t, n_chips)
        u = ulysses_comm_model(t, n_chips)
        ring_exposed = r.comm_exposed_fraction * r.ring_time_s
        rows.append({
            "t_local": t,
            "ring_exposed_comm_s": ring_exposed,
            "ulysses_wire_s": u.comm_time_s,
            "ulysses_wire_bytes_vs_ring": round(1 / u.bytes_ratio_vs_ring, 4),
            "preferred": "ulysses" if u.comm_time_s < ring_exposed
                         else "ring",
        })
        # same invariant the unit tests pin: per-chip attention FLOPs are
        # layout-independent (n hops × one block == full T over H/n heads)
        # up to ulysses's head-padding overhead. A real exception (not a
        # -O-stripped assert — ADVICE r4): artifact generation must fail
        # LOUDLY if the two comm models ever drift apart.
        if abs(u.compute_s - n_chips * r.hop_compute_s * u.padding_overhead) \
                > 1e-9 * u.compute_s:
            raise RuntimeError(
                f"SP comm models drifted: ulysses compute_s {u.compute_s} "
                f"!= ring total {n_chips * r.hop_compute_s} x padding "
                f"{u.padding_overhead} at t_local={t}")
    return {
        "n_chips": n_chips,
        "ring_break_even_t_local": ring_attention_comm_model(
            1024, n_chips).min_t_local_to_hide,
        "rows": rows,
        "rule": "prefer ulysses while its padding-adjusted wire time "
                "(ceil(H/n)*n/H overhead when H doesn't divide) beats the "
                "ring's exposed comm — for divisible H, t_local < ~half "
                "the ring break-even; the ring above (zero exposure, "
                "O(T/n^2) memory, any n)",
    }




def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the full table as JSON")
    ap.add_argument("--markdown", action="store_true",
                    help="print the README-ready markdown table")
    args = ap.parse_args()

    rows = predict_table()
    worst_no_overlap = [predict(p, 128, overlap_fraction=0.0)
                        for p in MEASURED]
    ns = north_star_summary()

    if args.markdown:
        print("| model | layout | chips | step ms | comm ms (wire) | "
              "exposed ms | efficiency | img/s/chip (device) | "
              "host ceiling | binds |")
        print("|---|---|---|---|---|---|---|---|---|---|")
        for r in rows:
            print(f"| {r.model} | {r.layout} | {r.n_chips} "
                  f"| {r.step_time_s * 1e3:.1f} "
                  f"| {r.comm_time_s * 1e3:.2f} "
                  f"| {r.exposed_comm_s * 1e3:.2f} "
                  f"| {r.efficiency:.4f} "
                  f"| {r.images_per_sec_per_chip:,.0f} "
                  f"| {r.host_bound_images_per_sec_per_chip:,.0f} "
                  f"| {r.binding_constraint} |")
        print()
        print("no-overlap worst case at 128 chips "
              "(overlap_fraction=0 — every wire byte exposed):")
        print("| model | efficiency | exposed ms |")
        print("|---|---|---|")
        for r in worst_no_overlap:
            print(f"| {r.model} | {r.efficiency:.4f} "
                  f"| {r.exposed_comm_s * 1e3:.2f} |")
        print()
        import inspect
        default_rate = inspect.signature(
            host_provisioning_requirement).parameters[
                "decode_per_core"].default
        print(f"host provisioning (cores/chip at the measured "
              f"{default_rate:.1f} img/s/core decode rate, 1.2x headroom):")
        print("| chip | model | device img/s/chip | cores/chip bare | "
              "with margin | stock | sufficient |")
        print("|---|---|---|---|---|---|---|")
        for chip in (V4, V5E):
            for r in host_provisioning_table(chip=chip):
                print(f"| {r.chip} | {r.model} "
                      f"| {r.device_rate_img_s_chip:,.0f} "
                      f"| {r.cores_per_chip_required:.1f} "
                      f"| {r.cores_per_chip_with_margin:.1f} "
                      f"| {r.stock_cores_per_chip:.0f} "
                      f"| {'yes' if r.stock_sufficient else 'NO'} |")

    payload = {
        "north_star": {
            "target": ">=0.90 scaling efficiency v4-8 -> v4-128",
            "model": ns["model"],
            "predicted_efficiency_8_to_128": round(
                ns["efficiency_8_to_128"], 4),
            "host_bound_ceiling_img_s_chip": round(
                ns["host_bound_ceiling_img_s_chip"], 1),
            "note": ns["note"],
        },
        "worst_case_no_overlap_128": {
            r.model: round(r.efficiency, 4) for r in worst_no_overlap},
        "worst_case_no_overlap_128_bf16_reduce": {
            p.name: round(predict(p, 128, overlap_fraction=0.0,
                                  grad_bytes_per_param=2).efficiency, 4)
            for p in MEASURED},
        "table": [dataclasses.asdict(r) for r in rows],
        "sp_layouts": sp_layout_comparison(),
        # the deployable host spec (VERDICT r4 #8): cores/chip each model
        # needs at the measured decode rate, with the sensitivity rows the
        # number is only honest with (decode rate ±20 % spans the measured
        # host variance; headroom 1.0 = no-margin bare minimum)
        "host_provisioning": {
            chip.name: [dataclasses.asdict(r)
                        for r in host_provisioning_table(chip=chip)]
            for chip in (V4, V5E)},
        "host_provisioning_sensitivity": {
            # HOST_DECODE_RATE_R9 = the r9 measured default (restart-marker
            # excerpt entropy decode on the u8 wire — assumes the dataset
            # carries interval-1 markers, reencode_restart.py);
            # HOST_DECODE_RATE_R8 = the r8 uint8-wire rate (also what a
            # marker-ABSENT dataset decodes at, modulo drift);
            # HOST_DECODE_RATE_R7 = the r7 host-bf16+s2d-wire rate;
            # HOST_DECODE_RATE_R6 = the r6 SIMD-resample point value (the
            # r6→r7 gap is committed box drift — host_r7/README.md);
            # HOST_DECODE_RATE_R5 = the r5 scalar-hoist rate; 556.34 = the
            # frozen r4 baseline; ±20% brackets host variance
            f"decode_{int(rate)}": {
                r.model: round(r.cores_per_chip_with_margin, 1)
                for r in host_provisioning_table(decode_per_core=rate)}
            for rate in (556.34, HOST_DECODE_RATE_R5, HOST_DECODE_RATE_R6,
                         HOST_DECODE_RATE_R7, HOST_DECODE_RATE_R8,
                         HOST_DECODE_RATE_R9 * 0.8, HOST_DECODE_RATE_R9,
                         HOST_DECODE_RATE_R9 * 1.2)},
        "assumptions": dict(ASSUMPTIONS),
    }
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
    print(json.dumps({"metric": "predicted_scaling_efficiency_v4_8_to_128",
                      "value": round(ns["efficiency_8_to_128"], 4),
                      "unit": "ratio",
                      "vs_baseline": round(ns["efficiency_8_to_128"] / 0.90,
                                           4)}))


if __name__ == "__main__":
    main()

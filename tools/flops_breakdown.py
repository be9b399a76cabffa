"""MXU/VPU FLOPs breakdown of a model's forward pass (PERF.md input).

CLI and roofline over the package's walk (``obs/flops.py``: the count under
the live MFU gauge, whose docstring says how each op is classified).

``--ceilings`` turns the placement split into the PERF.md §2 roofline.
The headline ``mfu_ceiling_post_fusion`` is §2's compute-only arithmetic
``T ≥ F_mxu/R_mxu + F_dw/R_vpu`` — the bound the r3 measurement validated
(B4 measured 0.548 vs 0.555) and the bound the Pallas fused depthwise
kernel (ops/depthwise_pallas.py) makes STRUCTURAL: one VMEM-resident pass
per dw stage, no epilogue round-trips to lose.  Next to it,
``mfu_ceiling_unfused_worst`` prices the failure mode the kernel
eliminates — every dw → BN → act epilogue splitting into separate HBM
passes (write conv output, re-read, write activated) — which is where the
stock lowering lands whenever XLA's fusion heuristics miss.  Measured
step time lives between the two; fusion pins it to the good end.  Usage::

    python tools/flops_breakdown.py efficientnet_b4 --size 380 --ceilings
    python tools/flops_breakdown.py efficientnet_deepfake_v4 --size 600 \
        --chans 12 --ceilings --stem-s2d
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfake_detection_tpu.obs.flops import analyze  # noqa: E402

# v5e rates used by PERF.md §2 (bf16 MXU; VPU at 2-way bf16 packing; HBM)
R_MXU = 197e12
R_VPU = 15.4e12
BW_HBM = 819e9
BYTES = 2          # bf16 end-to-end on the hot path


def mfu_ceilings(buckets, dw_out_elems: float,
                 ref_flops: float = None, batch: int = 1) -> dict:
    """PERF.md §2 roofline from a placement split.

    ``mfu_ceiling_post_fusion`` is the compute-only bound the fused kernel
    guarantees: ``T = F_mxu/R_mxu + F_dw/R_vpu`` (stems count MXU, exactly
    as §2's pre-registered arithmetic — the bound r3 measured B4 at 98.7%
    of).  ``mfu_ceiling_unfused_worst`` adds the HBM cost of every dw
    epilogue failing to fuse: two extra passes over each dw conv output
    (write pre-BN, re-read for BN+act, the activated write replaces one
    the fused pass also pays — net ``2·out·BYTES``).  MFU is normalized to
    ``ref_flops`` (pass the STOCK model's total when analyzing an s2d
    build: the embedded zero taps are overhead, not useful work).
    """
    # conv_grouped_partial (grouped-but-not-depthwise, e.g. CondConv expert
    # mixes) is priced at the full MXU rate here — optimistic, since those
    # tile the MXU only partially.  None of the EfficientNet/B4/flagship
    # targets this tool's PERF.md tables cover emit that bucket; a model
    # that does gets a ceiling that is an UPPER bound on its upper bound.
    f_dw = buckets.get("conv_depthwise_vpu", 0.0)
    f_mxu = sum(v for k, v in buckets.items()
                if k != "conv_depthwise_vpu")
    total = f_mxu + f_dw
    useful = ref_flops if ref_flops is not None else total
    t_compute = f_mxu / R_MXU + f_dw / R_VPU
    extra_bytes = 2.0 * dw_out_elems * BYTES
    return {
        "mfu_ceiling_post_fusion": round((useful / R_MXU) / t_compute, 4),
        "mfu_ceiling_unfused_worst": round(
            (useful / R_MXU) / (t_compute + extra_bytes / BW_HBM), 4),
        "dw_vpu_share_of_step": round(
            (f_dw / R_VPU) / t_compute, 4),
        # dw_out_elems comes from the jaxpr of the full batch — normalize
        # so the label stays honest under --batch > 1 (the MFU ratios above
        # are batch-invariant: FLOPs and bytes both scale linearly)
        "dw_epilogue_extra_mb_per_sample": round(
            extra_bytes / max(1, batch) / 1e6, 2),
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("model")
    ap.add_argument("--size", type=int, default=380)
    ap.add_argument("--chans", type=int, default=3)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--stem-s2d", action="store_true",
                    help="analyze the space-to-depth stem rewrite (builds "
                         "the model with stem_s2d=True and reclassifies "
                         "the stem from ITS jaxpr)")
    ap.add_argument("--ceilings", action="store_true",
                    help="print the PERF.md §2 roofline: post-fusion and "
                         "unfused-worst-case predicted MFU ceilings")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from deepfake_detection_tpu.models import create_model, init_model

    model = create_model(args.model, num_classes=2, in_chans=args.chans,
                         stem_s2d=args.stem_s2d)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, args.size, args.size, args.chans))
    x = jnp.zeros((args.batch, args.size, args.size, args.chans))
    buckets, stem, dw_out_elems = analyze(model, variables, x, args.chans)

    total = sum(buckets.values())
    out = {"model": args.model, "input":
           f"{args.size}x{args.size}x{args.chans}", "batch": args.batch,
           "stem_s2d": bool(args.stem_s2d),
           "total_gflops_fwd": round(total / 1e9, 2)}
    for k, v in sorted(buckets.items(), key=lambda kv: -kv[1]):
        out[k] = {"gflops": round(v / 1e9, 2),
                  "pct": round(100 * v / total, 2)}
    out["stem"] = stem["convs"]
    if args.ceilings:
        ref = total
        if args.stem_s2d:
            # normalize MFU to the STOCK model's useful FLOPs (the s2d
            # kernel's embedded zero taps are overhead, not work)
            stock = create_model(args.model, num_classes=2,
                                 in_chans=args.chans)
            sbuckets, _, _ = analyze(stock, variables, x, args.chans)
            ref = sum(sbuckets.values())
        out["ceilings"] = mfu_ceilings(buckets, dw_out_elems,
                                       ref_flops=ref, batch=args.batch)
    print(json.dumps(out, indent=1))

"""Compute the end-to-end images/sec/chip budget from committed artifacts.

BASELINE's ">=10k img/s sustained" is an end-to-end claim: decode on the
host, transform+score on the chip, encode on the host. The chip side is
measured (bench + tail experiment); the host side is measured per core
(host codec rows). This tool derives the e2e budget those measurements
imply — where the wall is, and how many host cores feed one chip — and
writes it as one artifact so the numbers stay consistent whenever either
input regenerates.

Pipeline model (miss path, steady state, stages overlapped):
    rate(N_cores) = min(device_rate,
                        N_dec_cores * decode_rate,
                        N_enc_cores * encode_rate)
with N_dec + N_enc = N and the split chosen optimally; equivalently the
host-side rate of one core running both stages is 1/(1/dec + 1/enc) and
host rate scales ~linearly with cores (the native pool decodes and
encodes without the GIL).

Usage: python tools/e2e_budget.py --device-rate N --device-rate-source S
           [--out benchmarks/e2e_budget_r5.json]
"""

from __future__ import annotations

import argparse
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel):
    with open(os.path.join(REPO, rel)) as fh:
        return json.load(fh)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/e2e_budget_r5.json")
    ap.add_argument(
        "--device-rate", type=float, required=True,
        help="kernel-only img/s/chip of the flagship program, from a "
             "PERF_LEDGER.jsonl row (no device record is tracked here)",
    )
    ap.add_argument(
        "--device-rate-source", required=True,
        help="where --device-rate comes from, e.g. 'ledger, PR 22'",
    )
    args = ap.parse_args()

    codec = load("benchmarks/host_codec_r5.json")
    host = {r["op"]: r.get("images_per_sec") for r in codec["results"]}
    device_rate = args.device_rate
    device_src = args.device_rate_source

    # serving shape: decode the 512^2 source, encode the 300x250 output
    dec = host["jpeg_decode_512_1thread"]
    enc_trellis = host["jpeg_encode_trellis_300x250_1thread"]
    enc_optimized = host["jpeg_encode_optimized_300x250_1thread"]
    enc_baseline = host["jpeg_encode_baseline_300x250_1thread"]

    rows = []
    for enc_name, enc in (
        ("trellis (moz_1, default)", enc_trellis),
        ("optimized+progressive (cjpeg pair)", enc_optimized),
        ("baseline (moz_0: fixed Huffman, sequential)", enc_baseline),
    ):
        core_rate = 1.0 / (1.0 / dec + 1.0 / enc)
        cores_for_chip = device_rate / core_rate
        rows.append({
            "encoder": enc_name,
            "host_core_e2e_img_s": round(core_rate, 1),
            "cores_to_saturate_one_chip": round(cores_for_chip, 1),
            "e2e_img_s_on_16_cores": round(min(device_rate,
                                               16 * core_rate), 1),
            "e2e_img_s_on_64_cores": round(min(device_rate,
                                               64 * core_rate), 1),
            "baseline_1250_cores_needed": round(1250.0 / core_rate, 1),
        })

    doc = {
        "what": ("End-to-end img/s/chip budget derived from committed "
                 "measurements (see module docstring for the pipeline "
                 "model). Host rates are PHOTOGRAPHIC-corpus rates per "
                 "core on this build host (host_codec_r5.json; the "
                 "round-4 noise-content floors were ~3-9x lower)."),
        "inputs": {
            "device_rate_img_s_chip": device_rate,
            "device_rate_source": device_src,
            "decode_512_img_s_core": dec,
            "encode_trellis_300x250_img_s_core": enc_trellis,
            "encode_optimized_300x250_img_s_core": enc_optimized,
            "encode_baseline_300x250_img_s_core": enc_baseline,
        },
        "budget": rows,
        "supported_claim": (
            f"{min(device_rate, 16 * rows[0]['host_core_e2e_img_s']):,.0f} "
            "img/s/chip end-to-end with 16 host cores at the DEFAULT "
            "quality tier (moz_1 trellis), measured components, "
            "photographic content; "
            f"{rows[0]['baseline_1250_cores_needed']:.1f} cores reach the "
            "BASELINE 1,250 img/s/chip"
        ),
        "conclusions": [
            ("The chip is never the wall: one chip sustains "
             f"{device_rate:,.0f} img/s device-side vs the 1,250 target."),
            (f"The BASELINE 1,250 img/s/chip end-to-end needs "
             f"~{rows[0]['baseline_1250_cores_needed']:.1f} host cores with "
             "the default trellis encoder on photographic content, "
             f"~{rows[1]['baseline_1250_cores_needed']:.1f} with the "
             "optimized pair, "
             f"~{rows[2]['baseline_1250_cores_needed']:.1f} at baseline "
             "quality — ordinary serving-host core counts, closing the "
             "round-4 'is the headline reachable' question."),
            ("Saturating the full device rate takes "
             f"~{rows[0]['cores_to_saturate_one_chip']:.0f} cores (trellis) "
             f"to ~{rows[2]['cores_to_saturate_one_chip']:.0f} (baseline) — "
             "the host codec, not the TPU, bounds this framework, the "
             "reverse of the reference (whose wall was per-request "
             "ImageMagick processes)."),
        ],
    }
    out = os.path.join(REPO, args.out)
    with open(out, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    print(json.dumps(doc["budget"], indent=1))
    for c in doc["conclusions"]:
        print("-", c)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

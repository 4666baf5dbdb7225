"""Measured ZeRO-Offload DPU overlap: sync vs delayed-param-update wall time.

Runs GPT-2 125M with the host-offload optimizer at a gradient-accumulation
depth where the device step rivals the host sweep, so the one-step-delayed
parameter update's overlap (device computes step k+1 while the host applies
step k) shows up as wall-clock — the ZeRO-Offload paper's DPU, the
reference's "communication overlap centric design"
(docs/_posts/2021-03-08-zero3-offload.md:72).

Writes OFFLOAD_BENCH.json at the repo root.  Run solo (one process per
chip).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def main():
    gas = int(sys.argv[1]) if len(sys.argv) > 1 else 192
    sync = bench.measure_offload("gpt2-125m", 1024, 8, gas=gas,
                                 steps=2, warmup=1, dpu=False, unroll=True)
    dpu = bench.measure_offload("gpt2-125m", 1024, 8, gas=gas,
                                steps=2, warmup=2, dpu=True, unroll=True)
    out = {
        "config": f"gpt2-125m T=1024 micro=8 gas={gas} z3 offload=cpu",
        "sync": sync,
        "dpu": dpu,
        "dpu_overlap_speedup": round(
            sync["step_wall_s"] / dpu["step_wall_s"], 3),
        "note": ("the overlap hides the device step behind the "
                 "transfer-bound host sweep"),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "OFFLOAD_BENCH.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

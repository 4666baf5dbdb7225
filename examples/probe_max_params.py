"""Largest model trainable on ONE chip with ZeRO param streaming.

The reference's marquee single-GPU claim is 13B params on one 32GB V100
with CPU offload and 40B with NVMe (docs/_posts/2020-09-09-ZeRO-Offload.md:9,
docs/_posts/2021-03-08-zero3-offload.md:49).  With
``offload_param: {device: cpu}`` (runtime/zero/param_stream.py) parameters
are NEVER materialized whole in HBM — 16-bit layer blocks stream
host→device through forward and backward — so the trainable-size bound
moves from the chip's 16 GB HBM to host memory:

    RAM bytes/param = 4 (fp32 master) + 4 (fp32 grad accum)
                    + 2 (16-bit image) [+ 8 moments unless NVMe]
    => 18 B/param with CPU moments (~6.9B params on this 125 GB host) or
       10 B/param with NVMe moments (~12.5B).  The device holds ~2
       streamed layer blocks + activations.

This probe trains TWO full optimizer steps at each rung of an ASCENDING
ladder (1.3B → 2.0B → 2.7B → 6.7B → 8.3B) and records the largest that
completes.  Rungs whose 18 B/param fit comfortably in RAM keep Adam
moments on the host (fast); larger rungs put moments on NVMe (the
ZeRO-Infinity tier) so RAM holds only 10 B/param.

Failure capture (a probe is only evidence if its failures are visible):
the parent polls the worker's VmHWM (peak RSS) via /proc while it runs,
records the exit code (negative = killed by signal; -9 usually the OOM
killer), keeps a long stderr tail, and greps the kernel ring buffer for
oom-kill lines.  The worker itself emits one PROGRESS line per completed
step so a mid-rung death still leaves per-step data.

Run solo on the TPU:  python examples/probe_max_params.py [size ...]
"""
import json
import os
import shutil
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# (name, n_embd, n_layer, n_head) — GPT-3-style ladder, ASCENDING.
CANDIDATES = [
    ("1.3b", 2048, 24, 16),
    ("2.0b", 2560, 24, 32),
    ("2.7b", 2560, 32, 32),
    ("6.7b", 4096, 32, 32),
    ("8.3b", 4096, 40, 32),
]

SEQ = 512
PEAK_FLOPS = 197e12          # v5e bf16
HOST_RAM_GB = 125
# moments stay in host RAM while 18 B/param + slack fits; beyond that the
# NVMe optimizer tier (10 B/param in RAM) carries the rung.
CPU_MOMENT_RAM_CAP_GB = 90


def _vm_hwm_gb(pid="self"):
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmHWM"):
                    return round(int(line.split()[1]) / 1e6, 2)   # kB → GB
    except OSError:
        pass
    return None


def _approx_params(n_embd, n_layer, vocab=50257, max_seq=SEQ):
    return 12 * n_layer * n_embd ** 2 + (vocab + max_seq) * n_embd


def try_size(n_embd, n_layer, n_head, seq=SEQ, micro=1):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config(n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                            max_seq=seq, embd_pdrop=0.0, attn_pdrop=0.0,
                            resid_pdrop=0.0, remat=False,
                            attention_impl="flash"),
                 dtype=jnp.bfloat16)
    n_approx = _approx_params(n_embd, n_layer)
    moments = ("cpu" if n_approx * 18 / 1e9 < CPU_MOMENT_RAM_CAP_GB
               else "nvme")
    nvme = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".nvme_probe")
    os.makedirs(nvme, exist_ok=True)
    off_opt = {"device": moments}
    off_param = {"device": "cpu", "fast_init": True}
    sub_group = int(5e8)
    if moments == "nvme":
        off_opt.update(nvme_path=nvme, pipeline_read=True,
                       pipeline_write=True)
        # big rungs: the 16-bit param payload ALSO moves to NVMe
        # (drop_payload frees the RAM image — 13.4 GB at 6.7B; the r5
        # first 6.7B attempt host-OOM'd at 130.7/125 GB with the image
        # resident), and smaller sub-groups halve the moment-swap pools
        off_param = {"device": "nvme", "nvme_path": nvme,
                     "fast_init": True}
        sub_group = int(2.5e8)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "gradient_clipping": 1.0,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {
            "stage": 3,
            "sub_group_size": sub_group,
            "offload_optimizer": off_opt,
            "offload_param": off_param},
    }
    toks = np.random.default_rng(0).integers(
        0, model.config.vocab_size, (2 * micro, seq + 1)).astype(np.int32)
    t0 = time.time()
    engine, _, _, _ = ds.initialize(config=config, model=model,
                                    training_data=(toks,))
    t_init = time.time() - t0
    print("PROGRESS" + json.dumps(
        {"event": "init_done", "init_s": round(t_init, 1),
         "moments": moments, "rss_hwm_gb": _vm_hwm_gb()}), flush=True)
    losses, walls, comps = [], [], []
    for i in range(2):
        t0 = time.time()
        losses.append(float(engine.train_batch()))
        walls.append(time.time() - t0)
        comps.append(dict(engine._param_stream.last_times))
        print("PROGRESS" + json.dumps(
            {"event": "step_done", "step": i, "loss": round(losses[-1], 3),
             "wall_s": round(walls[-1], 1), "rss_hwm_gb": _vm_hwm_gb(),
             "components": comps[-1]}), flush=True)
    assert all(np.isfinite(l) for l in losses)
    n = model.num_params()
    wire_gb = {
        "param_h2d_per_step": round(2 * n * 2 / 1e9, 1),   # fwd + bwd passes
        "grad_d2h_per_step": round(n * 2 / 1e9, 1),
    }
    # PCIe projection: all wire at 16 GB/s, measured host Adam kept, device
    # compute estimated from the model's flop count at 40% MFU
    flops_step = model.flops_per_token() * micro * seq
    dev_s = flops_step / (0.40 * PEAK_FLOPS)
    adam_s = comps[-1].get("host_adam_s", 0.0)
    pcie_s = (wire_gb["param_h2d_per_step"] + wire_gb["grad_d2h_per_step"]) / 16.0
    proj_wall = max(dev_s, pcie_s) + adam_s   # streaming overlaps compute
    return {"params_b": round(n / 1e9, 2),
            "init_s": round(t_init, 1),
            "moments_tier": moments,
            "rss_hwm_gb": _vm_hwm_gb(),
            "losses": [round(l, 2) for l in losses],
            "step_wall_s": [round(w, 1) for w in walls],
            "components": comps,
            "wire_gb": wire_gb,
            "projected_step_s_pcie16": round(proj_wall, 2),
            "projected_mfu_pcie16": round(
                flops_step / (proj_wall * PEAK_FLOPS), 4)}


def _signal_name(num):
    try:
        return signal.Signals(num).name
    except ValueError:
        return f"signal {num}"


def _dmesg_oom_tail():
    """Kernel ring-buffer lines mentioning the OOM killer (best effort)."""
    try:
        r = subprocess.run(["dmesg"], capture_output=True, text=True,
                           timeout=10)
        lines = [l for l in r.stdout.splitlines()
                 if "oom" in l.lower() or "out of memory" in l.lower()]
        return lines[-5:] if lines else None
    except Exception:
        return None


def _run_rung(name, root):
    """Launch one worker, polling its peak RSS; capture ALL failure modes."""
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--worker", name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=root)
    peak_gb = 0.0
    import threading

    def _poll():
        nonlocal peak_gb
        while proc.poll() is None:
            hwm = _vm_hwm_gb(proc.pid)
            if hwm:
                peak_gb = max(peak_gb, hwm)
            time.sleep(2.0)

    out_lines, err_chunks = [], []

    def _pump(stream, sink, echo):
        for line in stream:
            sink.append(line)
            if echo:                  # live progress in the parent's log
                print("  | " + line.rstrip(), flush=True)

    threads = [threading.Thread(target=_poll, daemon=True),
               threading.Thread(target=_pump,
                                args=(proc.stdout, out_lines, True),
                                daemon=True),
               threading.Thread(target=_pump,
                                args=(proc.stderr, err_chunks, False),
                                daemon=True)]
    for t in threads:
        t.start()
    proc.wait()
    for t in threads:
        t.join(timeout=5)
    out, err = "".join(out_lines), "".join(err_chunks)
    rc = proc.returncode
    progress = [json.loads(l[8:]) for l in out.splitlines()
                if l.startswith("PROGRESS")]
    done = [l for l in out.splitlines() if l.startswith("WORKER")]
    if done and rc == 0:
        res = json.loads(done[0][6:])
        res["parent_observed_rss_hwm_gb"] = round(peak_gb, 2)
        return res, True
    failure = {
        "error": "worker failed",
        "exit_code": rc,
        "killed_by_signal": (_signal_name(-rc) if rc and rc < 0 else None),
        "parent_observed_rss_hwm_gb": round(peak_gb, 2),
        "progress_before_failure": progress,
        "stderr_tail": (err or "")[-3000:],
        "stdout_tail": "\n".join(
            l for l in out.splitlines()[-20:]
            if not l.startswith(("PROGRESS", "WORKER"))),
        "dmesg_oom": _dmesg_oom_tail(),
    }
    if rc == -9 or (failure["dmesg_oom"] and peak_gb > 0.8 * HOST_RAM_GB):
        failure["diagnosis"] = (
            f"host OOM kill (SIGKILL, peak RSS {peak_gb:.1f} GB of "
            f"{HOST_RAM_GB} GB)")
    return failure, False


def main():
    known = {c[0]: c[1:] for c in CANDIDATES}
    args = sys.argv[1:]
    if len(args) == 2 and args[0] == "--worker" and args[1] in known:
        print("WORKER" + json.dumps(try_size(*known[args[1]])), flush=True)
        return
    bad = [a for a in args if a not in known]
    if bad:
        sys.exit(f"unknown size(s) {bad}; choose from {sorted(known)}")
    ladder = [c for c in CANDIDATES if not args or c[0] in args]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "MAXPARAMS.json")
    nvme = os.path.join(root, ".nvme_probe")
    results = {}
    largest = None
    if os.path.exists(path):          # merge: partial re-runs keep old rungs
        with open(path) as f:
            prev = json.load(f)
        results = prev.get("per_size", {})
        largest = prev.get("largest_trainable_params_b")
    for name, *_ in ladder:
        print(f"=== probing {name} ===", flush=True)
        # fresh NVMe scratch per rung so earlier moment files can't fill
        # the disk out from under a later rung
        shutil.rmtree(nvme, ignore_errors=True)
        free_gb = shutil.disk_usage(root).free / 1e9
        r, ok = _run_rung(name, root)
        r["disk_free_before_gb"] = round(free_gb, 1)
        results[name] = r
        if ok:
            largest = max(largest or 0, r["params_b"])
        out = {
            "largest_trainable_params_b": largest,
            "chip": "TPU v5e 16GB HBM (device holds ~2 streamed layer "
                    "blocks + activations; params NEVER whole in HBM)",
            "host_ram_gb": HOST_RAM_GB,
            "criterion": "2 full optimizer steps (streamed fwd/bwd, host "
                         "fused Adam; moments cpu<=2.7B / nvme above), "
                         "finite losses",
            "per_size": results,
            "ram_arithmetic_bytes_per_param": {
                "fp32_master": 4, "fp32_grad_accum": 4,
                "16bit_image": "2 (cpu param tier) / 0 (nvme tier)",
                "adam_moments": "0 (NVMe) / 8 (cpu)"},
            "note": ("offload_param streaming: 16-bit layer blocks stream "
                     "host->device in fwd AND bwd (zero/param_stream.py); "
                     "projected_* fields rescale wire seconds to PCIe "
                     "16 GB/s. Reference claim "
                     "shape: 13B on one 32GB V100 (0.41 B/GB device) "
                     "(docs/_posts/2020-09-09-ZeRO-Offload.md:9)."),
        }
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({name: ("ok" if ok else "FAILED"),
                          "largest": largest}), flush=True)
        if not ok:
            break                     # ascending: larger would fail too
    shutil.rmtree(nvme, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Roofline table: per (architecture × input shape), the three roofline
terms derived from the compiled dry-run (§Roofline deliverable).

Each combination is lowered+compiled in a SUBPROCESS with 512 forced host
devices (jax locks device count at first init), its post-SPMD HLO walked
by launch/hlo_analysis (while-trip-scaled per-device FLOPs / byte proxy /
collective bytes), and the terms computed against TPU v5e constants:
197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Also reports MODEL_FLOPS = 6·N(_active)·D and its ratio to HLO FLOPs
(compute "usefulness" — catches remat/redundancy waste), and an analytic
per-chip memory-fit estimate (weights + optimizer + KV caches from the
sharding specs — XLA:CPU's memory_analysis is not per-partition).

Usage:
    python -m benchmarks.roofline [--arch all] [--shape all] [--multi-pod]
Results cached at benchmarks/results/roofline.json (used by benchmarks.run).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import RESULTS_DIR, emit

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

ARCHS = ["qwen3-moe-235b-a22b", "smollm-360m", "qwen2.5-3b", "mixtral-8x7b",
         "phi3-mini-3.8b", "internvl2-26b", "mamba2-2.7b", "whisper-large-v3",
         "jamba-1.5-large-398b", "qwen3-14b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

TOKENS = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
          "decode_32k": 128, "long_500k": 1}


def run_one(arch: str, shape: str, multi_pod: bool = False,
            timeout: int = 3600) -> dict:
    """Dry-run one combo in a fresh 512-device subprocess."""
    out = os.path.join(
        tempfile.gettempdir(),
        f"roofline_{arch}_{shape}{'_mp' if multi_pod else ''}.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)   # dryrun sets its own
    env["JAX_PLATFORMS"] = "cpu"     # 512 virtual devices: a CPU-only lane
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--json-out", out]
    if multi_pod:
        cmd.append("--multi-pod")
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=timeout)
    if res.returncode != 0:
        return {"arch": arch, "shape": shape, "error":
                (res.stderr or res.stdout)[-400:]}
    with open(out) as f:
        recs = json.load(f)
    return recs[0] if recs else {"arch": arch, "shape": shape,
                                 "error": "no record"}


def summarize(rec: dict) -> dict:
    from repro.configs.base import get_config
    arch, shape = rec["arch"], rec["shape"]
    if "skipped" in rec:
        return dict(arch=arch, shape=shape, status="skip",
                    note=rec["skipped"])
    if "error" in rec:
        return dict(arch=arch, shape=shape, status="FAIL",
                    note=rec["error"][:120])
    cfg = get_config(arch)
    ha, rf = rec["hlo_analysis"], rec["roofline"]
    n_tokens = TOKENS[shape]
    n_active = cfg.active_param_count()
    factor = 3 if shape == "train_4k" else 1      # fwd+bwd
    model_flops = 2.0 * factor * n_active * n_tokens / rec["n_devices"]
    return dict(
        arch=arch, shape=shape, status="ok", mesh=rec["mesh"],
        t_compute_s=round(rf["t_compute_s"], 5),
        t_memory_s=round(rf["t_memory_s"], 5),
        t_collective_s=round(rf["t_collective_s"], 5),
        bottleneck=rf["bottleneck"],
        hlo_gflops_dev=round(ha["flops"] / 1e9, 2),
        model_gflops_dev=round(model_flops / 1e9, 2),
        useful_flops_ratio=round(model_flops / ha["flops"], 3)
        if ha["flops"] else 0.0,
        coll_gb_dev=round(ha["collective_total"] / 1e9, 3),
        compile_s=rec.get("compile_s"),
        note=rec.get("note", ""),
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--refresh", action="store_true",
                    help="re-run combos already cached")
    args = ap.parse_args(argv)

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = SHAPES if args.shape == "all" else [args.shape]

    cache_path = os.path.join(
        RESULTS_DIR, "roofline_mp.json" if args.multi_pod
        else "roofline.json")
    cache: dict = {}
    if os.path.exists(cache_path):
        with open(cache_path) as f:
            cache = {f"{r['arch']}|{r['shape']}": r for r in json.load(f)}

    rows = []
    for arch in archs:
        for shape in shapes:
            key = f"{arch}|{shape}"
            if key in cache and not args.refresh \
                    and cache[key].get("status") == "ok":
                rows.append(cache[key])
                continue
            print(f"[roofline] {arch} × {shape} "
                  f"({'2x16x16' if args.multi_pod else '16x16'}) ...",
                  flush=True)
            rec = run_one(arch, shape, multi_pod=args.multi_pod)
            rows.append(summarize(rec))
            cache[key] = rows[-1]
            os.makedirs(RESULTS_DIR, exist_ok=True)
            with open(cache_path, "w") as f:
                json.dump(list(cache.values()), f, indent=1)
    emit("roofline_mp" if args.multi_pod else "roofline", rows)
    return rows


if __name__ == "__main__":
    main()

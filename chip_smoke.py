#!/usr/bin/env python3
"""Cold smoke run of the serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py              # one chip: phases (a) (b) (c)
    python chip_smoke.py --mesh 2x2   # four chips: phases (a) (d) only

(a) device  — what ``jax.devices()`` reports; anything but a TPU exits
              non-zero before any work.
(b) kernels — ``paged_attention`` and ``flash_prefill`` compiled for the
              chip at qwen2.5-3b's widths, against their ``ref.py``
              oracles.
(c) serve   — 8 trace requests (shared prefixes included) through
              ``ServingLoop`` → ``PrefillWorker`` →
              ``DecodeWorker(substrate="paged")`` over one
              ``DevicePagePool``, built by ``repro.launch.serve`` at
              qwen2.5-3b's published widths and depth; every request must
              complete, and its tokens must equal the dense-arena oracle's.
(d) mesh    — the same requests through the paged decode sharded over a
              data×model mesh of real chips, against the unsharded 1×1
              path: tokens bit-exact, page slabs on distinct devices.

Each phase prints its findings on lines of its own. The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed; any failure raises and exits non-zero. Times are one cold
run, compilation included, not a benchmark. Weights are random, made from
a seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.base import get_config  # noqa: E402
from repro.core.trace import BLOCK_TOKENS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (build_engine, serve_requests,  # noqa: E402
                                trace_requests)
from repro.models.transformer import init_params  # noqa: E402
from repro.serving.engine import HostKVPool  # noqa: E402

ARCH = "qwen2.5-3b"
SEED = 0            # weights
TRACE_SEED = 5      # its first 8 requests share a 3-block system prompt
N_REQUESTS = 8
MAX_NEW = 16
MAX_BATCH = 8

# Kernel vs oracle, as max |err| over max |oracle|. The outputs are bf16
# (8 significant bits: 2^-8 ≈ 0.4% of a value), and the kernel keeps its
# softmax in float32 where the paged oracle rounds probabilities to bf16
# before the PV contraction: together well under 1%. A wrong page, mask
# position or head swaps whole tokens in or out of a softmax over ~1k
# tokens and moves the output by tens of percent.
KERNEL_TOL = 2e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts backend compilations (and persistent-cache hits among them)
    and their seconds while active, from ``jax.monitoring`` events."""

    def __init__(self) -> None:
        self.n = self.hits = 0
        self.seconds = 0.0

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def __str__(self) -> str:
        return (f"{self.n} compiles ({self.hits} from the persistent cache), "
                f"{self.seconds:.1f} s compiling")


def device_phase() -> dict:
    """(a) The device as JAX reports it; no TPU is a failure."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: {info['platform']} {info['kind']} x{info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{info['platform']!r}); nothing runs elsewhere")
    return info


def _rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(out).all():
        return float("inf")
    return float(np.abs(out - ref).max() / np.abs(ref).max())


def kernel_phase(cfg, *, interpret: bool = False, batch: int = 8,
                 max_pages: int = 32, prefill_q: int = 256,
                 prefill_k: int = 1024) -> dict:
    """(b) Both main-path kernels at ``cfg``'s head widths vs their
    oracles (float32 matmuls at full precision). ``interpret`` runs the
    Pallas interpreter instead of the compiled kernel (CPU tests)."""
    from repro.kernels.flash_prefill.ops import flash_prefill_attention
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    page = 64
    n_pages = batch * max_pages + 1
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    rng = np.random.default_rng(SEED)
    q = jax.random.normal(ks[0], (batch, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_pages, page, KV, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_pages, page, KV, D), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[
        :batch * max_pages].reshape(batch, max_pages), jnp.int32)
    lens = jnp.asarray(rng.integers(1, max_pages * page + 1, batch),
                       jnp.int32)
    out = paged_decode_attention(q, kp, vp, table, lens, use_pallas=True,
                                 interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q, kp, vp, table, lens)
    e_paged = _rel_err(out, ref)

    qf = jax.random.normal(ks[3], (1, prefill_q, H, D), jnp.bfloat16)
    kf = jax.random.normal(ks[4], (1, prefill_k, KV, D), jnp.bfloat16)
    vf = jax.random.normal(ks[5], (1, prefill_k, KV, D), jnp.bfloat16)
    off = prefill_k - prefill_q
    out = flash_prefill_attention(qf, kf, vf, q_offset=off,
                                  use_pallas=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = flash_prefill_ref(qf, kf, vf, q_offset=off)
    e_flash = _rel_err(out, ref)
    mode = "interpreted" if interpret else "compiled"
    print(f"kernels ({mode}, H/KV/D {H}/{KV}/{D}): paged_attention "
          f"max|err|/max|ref| {e_paged:.3e} over {batch} slots x "
          f"{max_pages} pages; flash_prefill {e_flash:.3e} at "
          f"{prefill_q} queries over {prefill_k} keys; tolerance "
          f"{KERNEL_TOL:g}")
    if not (e_paged <= KERNEL_TOL and e_flash <= KERNEL_TOL):
        raise AssertionError(f"kernel disagrees with its oracle: paged "
                             f"{e_paged:.3e}, flash {e_flash:.3e}")
    return dict(paged_err=e_paged, flash_err=e_flash)


def _serve(params, cfg, requests, *, mesh=None,
           substrate: str = "paged") -> dict:
    """One engine from ``repro.launch.serve`` serving ``requests`` through
    a ``ServingLoop``; every request must complete with all its tokens."""
    pool = HostKVPool(capacity_blocks=4096)
    try:
        with CompileCounter() as cc:
            t0 = time.perf_counter()
            pws, dw = build_engine(params, cfg, pool, max_batch=MAX_BATCH,
                                   substrate=substrate, mesh=mesh)
            logits = set()
            if mesh is not None:
                # record where every step the served worker runs leaves
                # its logits (sharding, global shape)
                step = dw._step_paged

                def recording_step(*args):
                    out = step(*args)
                    logits.add((out[0].sharding, out[0].shape))
                    return out
                dw._step_paged = recording_step
            stats, outputs = serve_requests(pws, dw, requests,
                                            admission="baseline",
                                            max_queue=64)
            wall = time.perf_counter() - t0
        tokens = {r.req_id: list(outputs[r.req_id].tokens)
                  if r.req_id in outputs else [] for r in requests}
        short = [r.req_id for r in requests
                 if len(tokens[r.req_id]) != r.max_new
                 or not outputs[r.req_id].done]
        if stats["completed"] != len(requests) or stats["rejected"] or short:
            raise AssertionError(
                f"{substrate} serving: {stats['completed']}/{len(requests)} "
                f"complete, {stats['rejected']} rejected, incomplete "
                f"{short}")
        run = dict(tokens=tokens, wall=wall, compiles=cc, stats=stats,
                   reused=sum(pw.stats()["reused_blocks"] for pw in pws),
                   page_pool=dw.page_pool, logits=logits)
        if dw.page_pool is not None:
            pp = dw.page_pool
            pp.check_leaks()
            if pp.pressure()["pinned"]:
                raise AssertionError(f"pages still pinned after serving: "
                                     f"{pp.pressure()}")
        return run
    finally:
        pool.close()


def _matching(a: dict, b: dict) -> tuple[int, int]:
    """(tokens equal position by position, tokens in ``a``) of two runs."""
    n_eq = sum(x == y for rid, t in a.items() for x, y in zip(t, b[rid]))
    return n_eq, sum(len(t) for t in a.values())


def serve_phase(cfg, *, n_requests: int = N_REQUESTS,
                max_new: int = MAX_NEW) -> dict:
    """(c) Serve the smoke trace on the paged substrate, then again on the
    dense arena (the bit-exactness oracle), and compare the tokens."""
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    requests = trace_requests(cfg, n_requests, seed=TRACE_SEED,
                              max_new=max_new, align=BLOCK_TOKENS)
    lens = sorted({len(r.tokens) for r in requests})
    paged = _serve(params, cfg, requests, substrate="paged")
    ps = paged["page_pool"].stats()
    print(f"serve: {len(requests)}/{len(requests)} requests complete "
          f"through ServingLoop -> PrefillWorker -> DecodeWorker(paged) at "
          f"{cfg.name} ({cfg.n_layers}L d{cfg.d_model} "
          f"{cfg.n_heads}H/{cfg.n_kv_heads}KV Dh{cfg.head_dim} "
          f"ff{cfg.d_ff} V{cfg.vocab_size}); prompts {lens} tokens, "
          f"{sum(len(t) for t in paged['tokens'].values())} new tokens; "
          f"{paged['reused']} prefix blocks reused, "
          f"{ps['shared_adoptions']} shared page-run adoptions")
    if not ps["shared_adoptions"]:
        raise AssertionError("no request adopted a shared page run")
    dense = _serve(params, cfg, requests, substrate="dense")
    n_eq, n_tok = _matching(paged["tokens"], dense["tokens"])
    print(f"serve: paged vs dense decode tokens {n_eq}/{n_tok} equal")
    for name, run in (("paged", paged), ("dense", dense)):
        print(f"serve: cold smoke run, not a benchmark: {name} "
              f"{run['wall']:.1f} s wall, {run['compiles']}, "
              f"{run['stats']['decode_steps']} decode steps")
    mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        print(f"serve: device peak memory {mem['peak_bytes_in_use'] / 2**30:.2f}"
              f" GiB of {mem.get('bytes_limit', 0) / 2**30:.2f} GiB")
    if n_eq != n_tok:
        raise AssertionError(f"paged decode diverged from the dense "
                             f"arena: {n_eq}/{n_tok} tokens equal")
    return dict(tokens_equal=n_eq, tokens=n_tok, paged=paged, dense=dense)


def mesh_phase(cfg, data: int, model: int, *, n_requests: int = N_REQUESTS,
               max_new: int = MAX_NEW) -> dict:
    """(d) The paged decode sharded over a ``data``×``model`` mesh vs the
    unsharded 1×1 path, on the same requests: tokens bit-exact, and the
    page slabs and logits really spread over ``data*model`` devices."""
    from repro.launch.mesh import make_decode_mesh
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    requests = trace_requests(cfg, n_requests, seed=TRACE_SEED,
                              max_new=max_new, align=BLOCK_TOKENS)
    flat = _serve(params, cfg, requests)
    del flat["page_pool"]       # its slabs are not needed on device 0 now
    mesh = make_decode_mesh(data, model)
    sharded = _serve(params, cfg, requests, mesh=mesh)
    pp = sharded["page_pool"]
    slab_devs = {sh.device for sh in pp.k_pages.addressable_shards}
    L, n_pages, page, width = pp.k_pages.shape
    slab_shards = {sh.data.shape for sh in pp.k_pages.addressable_shards}
    # the served steps' logits: one placement, each device its data shard's
    # rows
    if len(sharded["logits"]) != 1:
        raise AssertionError(f"served steps left their logits in "
                             f"{len(sharded['logits'])} placements")
    (logit_sharding, logit_shape), = sharded["logits"]
    logit_devs = logit_sharding.device_set
    logit_shards = {logit_sharding.shard_shape(logit_shape)}
    n_eq, n_tok = _matching(flat["tokens"], sharded["tokens"])
    need = data * model
    print(f"mesh: {data}x{model} over {sorted(d.id for d in mesh.devices.flat)}"
          f" ({cfg.name} widths, {cfg.n_layers} layers): "
          f"k_pages {pp.k_pages.shape} on {len(slab_devs)} devices as "
          f"{pp.k_pages.sharding.spec}, served-step logits on "
          f"{len(logit_devs)} devices as {logit_sharding.spec}; tokens "
          f"{n_eq}/{n_tok} equal to 1x1")
    for name, run in (("1x1", flat), (f"{data}x{model}", sharded)):
        print(f"mesh: cold smoke run, not a benchmark: {name} "
              f"{run['wall']:.1f} s wall, {run['compiles']}")
    mem = [d.memory_stats() or {} for d in mesh.devices.flat]
    if all("peak_bytes_in_use" in m for m in mem):
        print("mesh: device peak memory " + ", ".join(
            f"{m['peak_bytes_in_use'] / 2**30:.2f}" for m in mem) +
            f" GiB of {mem[0].get('bytes_limit', 0) / 2**30:.2f} GiB")
    # each device: its data bank's pages and its model stripe of KV heads;
    # its data shard's rows of the logits
    want_slab = {(L, n_pages // data, page, width // model)}
    want_logits = {(MAX_BATCH // data,) + logit_shape[1:]}
    if len(slab_devs) != need or len(logit_devs) != need \
            or slab_shards != want_slab or logit_shards != want_logits:
        raise AssertionError(
            f"mesh placement: slab shards {slab_shards} on {len(slab_devs)} "
            f"devices, logits shards {logit_shards} on {len(logit_devs)} of "
            f"{need} devices")
    if n_eq != n_tok:
        raise AssertionError(f"sharded decode diverged from 1x1: "
                             f"{n_eq}/{n_tok} tokens equal")
    return dict(tokens_equal=n_eq, tokens=n_tok, slab_devices=len(slab_devs),
                logit_devices=len(logit_devs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", metavar="DxM", default=None,
                    help="run only phase (d): the sharded paged decode over "
                         "a DxM mesh of chips (e.g. 2x2 on four chips) and "
                         "the 1x1 path it is compared with")
    args = ap.parse_args(argv)
    enable_compile_cache()
    info = device_phase()
    cfg = get_config(ARCH)
    if args.mesh:
        from repro.launch.mesh import parse_mesh_arg
        data, model = parse_mesh_arg(args.mesh)
        mesh_phase(cfg, data, model)
    else:
        kernel_phase(cfg)
        serve_phase(cfg)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

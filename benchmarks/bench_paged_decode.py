"""Paged vs dense decode substrate — join latency, page-budget capacity,
per-step latency, bit-exactness.

The decode-side counterpart of the pool benches: §3's "load pool blocks
into these pages" made executable. Three claims are measured against the
dense (L, B, max_len) arena at EQUAL batch:

* ``join()`` — the paged worker ADOPTS the prefill-staged page run (a
  host-side block-table splice + refcounts) where the dense worker
  copies the request's full-depth KV into its arena: paged join must be
  strictly faster (assertion, wall-clock table, not gated).
* capacity — shared-prefix workloads: slots on the same hash chain share
  physical prefix pages, so a fixed page budget must hold ≥ 2× the
  sequences the private-arena equivalent holds (assertion; deterministic
  counts → the ``paged_decode_capacity`` table is CI-gated).
* ``step()`` — paged attention over the live page span (table sliced to
  the deepest active slot) must be no slower than dense attention over
  ``max_len`` at max_len-scale depths (assertion, wall-clock).

Every token emitted by the paged substrate must be bit-exact against the
dense oracle.

Two more lanes ride along:

* width buckets — a depth-skewed batch steps as per-width sub-batches
  (``width_buckets=2``) instead of padding every slot to the deepest
  slot's pow2 width; tokens must stay bit-exact (wall-clock table).
* mesh capacity — on 4 virtual CPU devices (subprocess), the (data,
  model)-sharded pool must serve ≥ 1.9x the KV tokens per device-byte
  when either axis doubles (deterministic, CI-gated), with every mesh's
  streams identical and per-step time within the host-overhead bound.

    PYTHONPATH=src python -m benchmarks.bench_paged_decode [--quick]
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.core.trace import BLOCK_TOKENS

PAGE_TOKENS = 64


def _workload(vocab, shared_blocks, n_reqs, suffix=64, seed=0):
    """n_reqs prompts sharing a shared_blocks-deep prefix chain, each with
    a distinct suffix (the Figure-6 hot-system-prompt shape)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, shared_blocks * BLOCK_TOKENS)
    return [np.concatenate([shared, rng.integers(0, vocab, suffix)])
            for _ in range(n_reqs)]


def _build(substrate, params, cfg, reqs, *, max_batch, max_len,
           page_pool=None):
    from repro.serving.engine import DecodeWorker, HostKVPool, PrefillWorker

    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256,
                       page_pool=page_pool)
    dw = DecodeWorker(params, cfg, max_batch=max_batch, max_len=max_len,
                      substrate=substrate, page_pool=page_pool)
    return dw, [pw(t) for t in reqs]


def _probe_step(dw, reps=8):
    """Steady-state per-step latency of a worker's jitted step at its
    CURRENT depth: re-time the (pure) step executable on frozen inputs,
    best-of-reps — immune to one-shot scheduler noise on a shared box."""
    import jax
    import jax.numpy as jnp

    if dw.substrate == "paged":
        pp = dw.page_pool
        pt = pp.page_tokens
        active = [i for i, s in enumerate(dw.slots) if s is not None]
        need = max(int(dw.seq_lens[i]) // pt + 1 for i in active)
        width = 1
        while width < need:
            width *= 2
        width = min(width, dw.max_pages)
        tbl = jnp.asarray(dw.block_table[:, :width].copy())
        lens = jnp.asarray(dw.seq_lens.copy())
        args = (dw.params, dw.tokens, pp.k_pages, pp.v_pages, tbl, lens)
        fn = dw._step_paged
    else:
        args = (dw.params, dw.tokens, dw.caches)
        fn = dw._step
    best = float("inf")
    for _ in range(reps + 1):            # +1 warmup (compile already done)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out[0])
        best = min(best, time.perf_counter() - t0)
        if dw.substrate == "paged":
            # the step donates its slabs: thread them (re-writing the same
            # rows leaves the pages as they were)
            args = args[:2] + tuple(out[1:3]) + args[4:]
            pp.k_pages, pp.v_pages = out[1], out[2]
    return best


def _head_to_head(params, cfg, reqs, *, max_batch, max_len, max_new,
                  page_pool):
    """Join and step the paged and dense workers INTERLEAVED, so load
    noise on a shared box hits both substrates alike; join latency is
    compared on the min and step latency via best-of-N probes of the
    step executables at full depth."""
    import jax

    dw_p, res_p = _build("paged", params, cfg, reqs, max_batch=max_batch,
                         max_len=max_len, page_pool=page_pool)
    dw_d, res_d = _build("dense", params, cfg, reqs, max_batch=max_batch,
                         max_len=max_len)

    times = {"paged": dict(join=[], step=[]), "dense": dict(join=[], step=[])}
    streams = {"paged": {}, "dense": {}}
    for i in range(len(reqs)):
        for name, dw, r in (("paged", dw_p, res_p[i]),
                            ("dense", dw_d, res_d[i])):
            t0 = time.perf_counter()
            dw.join(i, r, max_new=max_new)
            jax.block_until_ready(dw.tokens)
            times[name]["join"].append(time.perf_counter() - t0)
            streams[name][i] = [r.first_token]
    n_steps = 0
    while dw_p.n_active or dw_d.n_active:
        n_steps += 1
        if n_steps == max_new - 1:       # deepest full batch: probe here
            for name, dw in (("paged", dw_p), ("dense", dw_d)):
                times[name]["step"].append(_probe_step(dw))
        for name, dw in (("paged", dw_p), ("dense", dw_d)):
            if not dw.n_active:
                continue
            out = dw.step()
            for rid, tok, _ in out:
                streams[name][rid].append(tok)
    return times, streams, dw_p


def _capacity(params, cfg, budget_pages, *, shared_blocks, cap, max_new=2):
    """How many shared-prefix sequences fit a fixed page budget, vs the
    private-arena equivalent. Deterministic counts (CI-gated)."""
    from repro.serving.engine import DecodeWorker, HostKVPool, PrefillWorker
    from repro.serving.paged_cache import DevicePagePool

    suffix = PAGE_TOKENS                   # one private tail page per seq
    prompt_len = shared_blocks * BLOCK_TOKENS + suffix
    prompt_pages = (prompt_len + PAGE_TOKENS - 1) // PAGE_TOKENS
    dense_fit = budget_pages // prompt_pages   # private pages per sequence

    pp = DevicePagePool(cfg, n_pages=budget_pages + 1,
                        page_tokens=PAGE_TOKENS)
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=cap, max_len=prompt_len + 64,
                      substrate="paged", page_pool=pp)
    reqs = _workload(cfg.vocab_size, shared_blocks, cap, suffix=suffix,
                     seed=1)
    paged_fit = 0
    for i, t in enumerate(reqs):
        r = pw(t)
        try:
            dw.join(i, r, max_new=max_new)
        except MemoryError:
            break
        paged_fit += 1
    logical = int(sum(dw.n_pages_slot[:]))
    return dict(budget_pages=budget_pages, prompt_pages=prompt_pages,
                dense_fit=dense_fit, paged_fit=paged_fit,
                fit_ratio=round(paged_fit / max(dense_fit, 1), 2),
                logical_pages=logical, physical_pages=pp.used_pages)


def _buckets(params, cfg, *, max_new=6):
    """Per-slot width buckets vs the single global pow2 width on a
    depth-skewed batch: one 10-page slot forces the global width to 16,
    so the shallow slots attend 8x the pages they own. Buckets split the
    step into per-width sub-batches; tokens must stay bit-exact."""
    from repro.serving.engine import DecodeWorker, HostKVPool, PrefillWorker
    from repro.serving.paged_cache import DevicePagePool
    from repro.serving.request import ServingRequest

    rng = np.random.default_rng(4)
    prompts = {0: rng.integers(0, cfg.vocab_size, 600),    # 10 pages
               1: rng.integers(0, cfg.vocab_size, 600),
               2: rng.integers(0, cfg.vocab_size, 70),     # 2 pages
               3: rng.integers(0, cfg.vocab_size, 40)}     # 1 page

    rows, streams = [], {}
    for wb in (1, 2):
        pp = DevicePagePool(cfg, n_pages=1 + 5 * 16, page_tokens=PAGE_TOKENS)
        pool = HostKVPool()
        pw = PrefillWorker(params, cfg, pool, prefill_chunk=256,
                           page_pool=pp)
        dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                          substrate="paged", page_pool=pp, width_buckets=wb)
        outs = {}
        for rid, toks in prompts.items():
            r = pw(toks)
            dw.join(ServingRequest(req_id=rid, tokens=toks,
                                   max_new=max_new), r)
            outs[rid] = [r.first_token]
        steps, t_step = 0, float("inf")
        while dw.n_active:
            steps += 1
            t0 = time.perf_counter()
            out = dw.step()
            t_step = min(t_step, time.perf_counter() - t0)
            for rid, tok, _ in out:
                outs[rid].append(tok)
        streams[wb] = outs
        rows.append(dict(width_buckets=wb, steps=steps,
                         bucket_substeps=dw.stats()["bucket_substeps"],
                         step_ms_min=1e3 * t_step))
        pp.check_leaks()
    assert streams[2] == streams[1], \
        "width-bucketed step diverged from the single-width oracle"
    assert rows[1]["bucket_substeps"] >= 2 * rows[1]["steps"], rows
    return rows


_MESH_SUB = r"""
import dataclasses, json, time
import jax
import numpy as np
from repro.configs.base import get_config
from repro.launch.mesh import make_decode_mesh
from repro.models.transformer import init_params
from repro.serving.engine import DecodeWorker, HostKVPool, PrefillWorker
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest

assert jax.device_count() == 4, jax.devices()
cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                          n_heads=16, n_kv_heads=4)
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(2)
prompts = [rng.integers(0, cfg.vocab_size, 200) for _ in range(4)]
BANK_PAGES = 65                     # fixed PER-BANK budget incl. null page

rows = []
for d, m in [(1, 1), (2, 1), (1, 2), (2, 2)]:
    mesh = make_decode_mesh(d, m)
    pp = DevicePagePool(cfg, n_pages=BANK_PAGES, mesh=mesh, page_tokens=64)
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                      substrate="paged", page_pool=pp)
    outs = {}
    for rid, toks in enumerate(prompts):
        r = pw(toks)
        dw.join(ServingRequest(req_id=rid, tokens=toks, max_new=5), r)
        outs[rid] = [r.first_token]
    t_step = float("inf")
    while dw.n_active:
        t0 = time.perf_counter()
        out = dw.step()
        t_step = min(t_step, time.perf_counter() - t0)
        for rid, tok, _ in out:
            outs[rid].append(tok)
    pp.check_leaks()
    # per-device KV bytes: one addressable shard of each slab
    shard_b = (pp.k_pages.addressable_shards[0].data.nbytes
               + pp.v_pages.addressable_shards[0].data.nbytes)
    cap = pp.pressure()["capacity"]
    rows.append(dict(mesh=f"{d}x{m}", banks=d, model_shards=m,
                     bank_pages=BANK_PAGES, capacity_pages=cap,
                     capacity_tokens=cap * 64,
                     per_device_kv_kib=shard_b // 1024,
                     step_ms_min=1e3 * t_step,
                     tokens=outs))
print("ROWS_JSON:" + json.dumps(rows))
"""


def _mesh_table():
    """Device-mesh capacity scaling on 4 virtual CPU devices (subprocess:
    the parent's jax is already initialised single-device). Deterministic
    columns are CI-gated; step wall-clock is reported separately."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"     # virtual devices: a CPU-only lane
    res = subprocess.run([sys.executable, "-c", _MESH_SUB], env=env,
                         capture_output=True, text=True, timeout=900)
    if res.returncode != 0:
        raise RuntimeError(f"mesh subprocess failed:\nSTDOUT:{res.stdout}\n"
                           f"STDERR:{res.stderr}")
    line = [l for l in res.stdout.splitlines()
            if l.startswith("ROWS_JSON:")][0]
    rows = json.loads(line[len("ROWS_JSON:"):])

    # shard invariance rides along: every mesh emitted the same streams
    base = rows[0].pop("tokens")
    for r in rows[1:]:
        assert r.pop("tokens") == base, f"mesh {r['mesh']} diverged"

    # capacity per device-byte: logical KV tokens the mesh serves per KiB
    # of any one device's slab share — data banks add pages, model
    # stripes thin each device's share of every page
    t0 = rows[0]["capacity_tokens"] / rows[0]["per_device_kv_kib"]
    for r in rows:
        r["capacity_per_device_x"] = round(
            (r["capacity_tokens"] / r["per_device_kv_kib"]) / t0, 2)
    step_rows = [dict(mesh=r["mesh"], step_ms_min=r.pop("step_ms_min"))
                 for r in rows]
    return rows, step_rows


def main(fast: bool = False) -> int:
    import jax

    from repro.configs.base import get_config
    from repro.models.transformer import init_params
    from repro.serving.paged_cache import DevicePagePool

    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))

    # ---- engine head-to-head: join / step / tokens ----
    if fast:
        shared_blocks, max_batch, max_new, max_len = 2, 4, 6, 1536
        extras, cap = [7, 23], 16
    else:
        shared_blocks, max_batch, max_new, max_len = 3, 4, 8, 2048
        extras, cap = [7, 23, 55], 32
    # capacity budgets: one prompt's pages + headroom (sequences beyond the
    # first cost only their private tail under prefix sharing)
    prompt_pages = shared_blocks * (BLOCK_TOKENS // PAGE_TOKENS) + 1
    budgets = [prompt_pages + e for e in extras]
    reqs = _workload(cfg.vocab_size, shared_blocks, max_batch)

    # page pool sized to the live working set, not the dense arena: the
    # shared prefix is physically resident ONCE, each slot adds only its
    # private tail + generated tokens — the §3 memory story in numbers
    suffix_pages = (64 + max_new + PAGE_TOKENS - 1) // PAGE_TOKENS + 1
    n_pages = (1 + shared_blocks * (BLOCK_TOKENS // PAGE_TOKENS)
               + max_batch * (suffix_pages + 1))
    pp = DevicePagePool(cfg, n_pages=n_pages, page_tokens=PAGE_TOKENS)
    times, streams, dw_p = _head_to_head(
        params, cfg, reqs, max_batch=max_batch, max_len=max_len,
        max_new=max_new, page_pool=pp)

    tokens_match = streams["paged"] == streams["dense"]
    if not tokens_match:
        for i in streams["paged"]:
            if streams["paged"][i] != streams["dense"][i]:
                print(f"req {i} diverged:\n  paged: {streams['paged'][i]}"
                      f"\n  dense: {streams['dense'][i]}")
    jp, jd = (float(np.min(times[s]["join"])) for s in ("paged", "dense"))
    sp, sd = (float(np.min(times[s]["step"])) for s in ("paged", "dense"))
    rows = [dict(substrate="paged", join_ms_min=1e3 * jp,
                 step_ms_min=1e3 * sp, tokens_match=tokens_match,
                 kv_tokens_held=pp.n_pages * PAGE_TOKENS,
                 zero_copy_joins=dw_p.stats()["zero_copy_joins"],
                 shared_adoptions=pp.stats()["shared_adoptions"]),
            dict(substrate="dense", join_ms_min=1e3 * jd,
                 step_ms_min=1e3 * sd, tokens_match=True,
                 kv_tokens_held=max_batch * max_len,
                 zero_copy_joins=0, shared_adoptions=0)]
    emit("paged_decode_engine", rows)
    print(f"join: paged {1e3 * jp:.2f} ms vs dense {1e3 * jd:.2f} ms "
          f"({jd / max(jp, 1e-9):.1f}x); step min: paged {1e3 * sp:.2f} ms "
          f"vs dense {1e3 * sd:.2f} ms; tokens_match={tokens_match}")
    assert tokens_match, "paged substrate diverged from the dense oracle"
    assert jp < jd, f"paged join ({jp:.4f}s) must beat dense ({jd:.4f}s)"
    assert sp <= 1.15 * sd, \
        f"paged step {sp:.4f}s worse than dense {sd:.4f}s at depth"

    # ---- capacity at equal page budget (deterministic, CI-gated) ----
    cap_rows = [_capacity(params, cfg, b, shared_blocks=shared_blocks,
                          cap=cap) for b in budgets]
    emit("paged_decode_capacity", cap_rows)
    for r in cap_rows:
        assert r["paged_fit"] >= 2 * max(r["dense_fit"], 1), (
            f"shared-prefix capacity win < 2x: {r}")
        if r["paged_fit"] > 1:        # sharing collapses physical residency
            assert r["physical_pages"] < r["logical_pages"], r

    # ---- per-slot width buckets on a depth-skewed batch ----
    bucket_rows = _buckets(params, cfg)
    emit("paged_decode_buckets", bucket_rows)
    b1, b2 = bucket_rows
    print(f"buckets: 1-width step {b1['step_ms_min']:.2f} ms vs 2-width "
          f"{b2['step_ms_min']:.2f} ms ({b2['bucket_substeps']} substeps, "
          f"tokens bit-exact)")

    # ---- (data, model) mesh capacity scaling (deterministic, CI-gated) ----
    mesh_rows, step_rows = _mesh_table()
    emit("paged_decode_mesh", mesh_rows)
    emit("paged_decode_mesh_step", step_rows)
    by = {r["mesh"]: r for r in mesh_rows}
    # doubling either axis must serve >= 1.9x the KV tokens per byte any
    # one device holds (exactly 2x minus per-bank null-page overhead)
    assert by["2x1"]["capacity_per_device_x"] >= 1.9, by["2x1"]
    assert by["1x2"]["capacity_per_device_x"] >= 1.9, by["1x2"]
    assert by["2x2"]["capacity_per_device_x"] >= 3.8, by["2x2"]
    s0 = step_rows[0]["step_ms_min"]
    for r in step_rows[1:]:
        assert r["step_ms_min"] <= 3.0 * s0 + 10.0, (
            f"mesh {r['mesh']} per-step time blew past the host-overhead "
            f"bound: {r['step_ms_min']:.2f} ms vs 1x1 {s0:.2f} ms")
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", "--quick", dest="fast", action="store_true")
    raise SystemExit(main(fast=ap.parse_args().fast))

"""Serving launcher: a single-host disaggregated Mooncake instance pair.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --requests 16 [--trace trace.jsonl] [--widths published]

Runs the REAL engines: the reduced model by default (CPU tests and CI),
or the arch at its published widths with ``--widths published`` (a chip
run; ``chip_smoke.py`` drives the same ``build_engine`` /
``serve_requests`` path). By default requests flow
through the always-on ``ServingLoop``: a thread feeds arrivals, prefill
chunks interleave between continuous-batching decode steps, and admission
backpressure sheds load when the queue/slots/page pool saturate — the §3
workflow as one sustained iteration. ``--no-loop`` keeps the original
phase-at-a-time driver (full prefill, join, then decode). With --trace,
request arrival order/lengths/prefix structure come from a Mooncake-format
trace (hash chains realised to actual tokens). With --peer-ssd-dir, blocks
a PREVIOUS run demoted to its SSD store become cross-node-fetchable
through a shared GlobalBlockDirectory (the global pool, across launcher
runs — same seed ⇒ same hash chains).
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

MAX_LEN = 2048          # decode slot length: prompt + new tokens
PREFILL_CHUNK = 256     # tokens per prefill device chunk
MAX_INPUT = 1536        # trace prompts are cut to this many tokens


def build_engine(params, cfg, pool, *, max_batch: int,
                 substrate: str = "paged", mesh=None,
                 prefill_workers: int = 1, ssd_mode: str = "overlap",
                 width_buckets: int = 1):
    """The serving engine of one instance: ``prefill_workers``
    ``PrefillWorker``s over the host pool ``pool`` and one
    ``DecodeWorker``, sharing one ``DevicePagePool`` on the paged
    substrate (the zero-copy §3 handoff). Returns ``(prefill_workers,
    decode_worker)``; the page pool, sized by the decode worker from
    ``max_batch`` and ``MAX_LEN``, is ``decode_worker.page_pool``."""
    from repro.serving.engine import DecodeWorker, PrefillWorker
    dw = DecodeWorker(params, cfg, max_batch=max_batch, max_len=MAX_LEN,
                      substrate=substrate, mesh=mesh,
                      width_buckets=width_buckets)
    pws = [PrefillWorker(params, cfg, pool, prefill_chunk=PREFILL_CHUNK,
                         ssd_mode=ssd_mode, page_pool=dw.page_pool)
           for _ in range(prefill_workers)]
    return pws, dw


def trace_requests(cfg, n: int, *, seed: int, max_new: int, align: int = 1,
                   trace_path=None) -> list:
    """``n`` ``ServingRequest``s from a Mooncake-format trace (loaded, or
    generated from ``seed``), realised to tokens whose 512-token blocks
    follow the trace's hash chains, so prefix sharing is the trace's.
    Prompts are cut to ``MAX_INPUT`` tokens and, with ``align`` > 1,
    rounded up to a multiple of it (fewer distinct prefill shapes, so
    fewer compiles)."""
    from repro.core.trace import (BLOCK_TOKENS, TraceSpec, generate_trace,
                                  load_trace)
    from repro.data.pipeline import realize_request_tokens
    from repro.serving.request import ServingRequest
    if trace_path:
        reqs = load_trace(trace_path, limit=n)
    else:
        spec = TraceSpec(n_requests=n, duration_ms=10_000, seed=seed,
                         max_input_tokens=2048, chat_turn_mu=5.0,
                         doc_len_mu=6.5)
        reqs = generate_trace(spec)[:n]
    out = []
    for r in reqs:
        length = min(-(-r.input_length // align) * align, MAX_INPUT)
        r.input_length = length
        r.hash_ids = r.hash_ids[:max(length // BLOCK_TOKENS, 1)]
        out.append(ServingRequest(
            req_id=r.req_id, tokens=realize_request_tokens(r, cfg.vocab_size),
            max_new=min(max_new, max(r.output_length, 2)),
            session=r.hash_ids[0] if r.hash_ids else None))
    return out


def serve_requests(pws, dw, requests, **loop_kw):
    """Serve ``requests`` through a ``ServingLoop`` fed from a client
    thread (``submit`` as a client calls it). Returns ``(loop stats,
    {req_id: RequestOutput})``."""
    from repro.serving.loop import ServingLoop
    loop = ServingLoop(pws, dw, **loop_kw)

    def feeder():
        for r in requests:
            loop.submit(r)
        loop.close_intake()

    th = threading.Thread(target=feeder, name="repro-loop-feeder")
    th.start()
    try:
        stats = loop.run()
    finally:
        th.join()
    return stats, loop.outputs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--widths", default="reduced",
                    choices=("reduced", "published"),
                    help="reduced: the arch's 2-layer smoke size (CPU "
                         "tests, CI); published: its published widths and "
                         "depth (run on the chip)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--trace", default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--no-loop", action="store_true",
                    help="phase-at-a-time driver (full prefill + join + "
                         "decode) instead of the interleaved serving loop")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="N PrefillWorkers feeding the loop's decode batch")
    ap.add_argument("--tbt-budget", type=float, default=None,
                    help="loop TBT budget in seconds: fit prefill chunks "
                         "into the slack it leaves per decode step "
                         "(default: deterministic chunks-per-iter mode)")
    ap.add_argument("--chunks-per-iter", type=int, default=1,
                    help="prefill chunks between decode steps when no "
                         "--tbt-budget is given")
    ap.add_argument("--admission", default="predictive",
                    choices=("baseline", "early", "predictive"),
                    help="backpressure policy evaluated at submit()")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable decode preemption (pending joins only "
                         "defer, never spill a lower-priority victim's KV "
                         "to the host tier)")
    ap.add_argument("--restore-mode", default="auto",
                    choices=("auto", "reload", "recompute"),
                    help="how preempted victims restore: reload spilled "
                         "bytes, recompute through prefill, or priced "
                         "per restore (auto)")
    ap.add_argument("--pool-blocks", type=int, default=4096)
    ap.add_argument("--ssd-blocks", type=int, default=0,
                    help="SSD-tier capacity in blocks (0 = flat DRAM pool)")
    ap.add_argument("--ssd-dir", default=None,
                    help="directory for the file-backed SSD block store; "
                         "with --ssd-blocks, demoted KV really hits disk")
    ap.add_argument("--ssd-mode", default="overlap",
                    choices=("blocking", "overlap"),
                    help="how SSD-resident prefixes load: synchronously, or "
                         "overlapped with head-chunk recompute (§5.2)")
    ap.add_argument("--peer-ssd-dir", default=None,
                    help="a PEER node's SSD store directory (e.g. left by a "
                         "previous run): its blocks join a shared "
                         "GlobalBlockDirectory and local misses resolve to "
                         "cross-node fetches — the Figure-3 global pool "
                         "across launcher runs")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard the paged decode engine over a "
                         "(data, model) device mesh, e.g. 2x2: decode "
                         "slots and page-pool banks split over the data "
                         "axis, KV-head stripes over the model axis. "
                         "Needs data*model jax devices (CPU: set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N) and, for model>1, a grouped-GQA head "
                         "layout (the arch's heads are adjusted with a "
                         "printed note if required)")
    ap.add_argument("--width-buckets", type=int, default=1,
                    help="per-step block-table width buckets (>1 runs "
                         "shallow slots on narrower tables instead of "
                         "padding to the deepest; single-device only)")
    ap.add_argument("--decode-substrate", default="paged",
                    choices=("paged", "dense"),
                    help="decode KV substrate: block-table pages with "
                         "zero-copy prefill→decode handoff and refcounted "
                         "prefix sharing (default), or the dense per-slot "
                         "arena (bit-exactness oracle)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.transformer import init_params
    from repro.serving.engine import HostKVPool

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.widths == "reduced":
        cfg = cfg.reduced()
    mesh = None
    mesh_d = 1
    if args.mesh:
        import dataclasses

        from repro.launch.mesh import make_decode_mesh, parse_mesh_arg
        from repro.models.transformer import paged_shard_reason
        if args.decode_substrate != "paged":
            ap.error("--mesh shards the PAGED decode engine; drop "
                     "--decode-substrate dense")
        mesh_d, mesh_m = parse_mesh_arg(args.mesh)
        if mesh_m > 1 and args.widths == "reduced" \
                and paged_shard_reason(cfg, mesh_m, mesh_d):
            kv = max(4, mesh_m)
            if 16 % kv or kv % mesh_m:
                ap.error(f"--mesh model axis {mesh_m} has no grouped-GQA "
                         f"head layout")
            print(f"--mesh {args.mesh}: adjusting the reduced arch to "
                  f"grouped GQA (n_heads=16, n_kv_heads={kv}) so KV heads "
                  f"stripe over the model axis")
            cfg = dataclasses.replace(cfg, n_heads=16, n_kv_heads=kv)
        reason = paged_shard_reason(cfg, mesh_m, mesh_d)
        if reason:
            ap.error(f"--mesh {args.mesh}: {reason}")
        if args.max_batch % mesh_d:
            ap.error(f"--max-batch {args.max_batch} must divide over the "
                     f"mesh data axis ({mesh_d})")
        mesh = make_decode_mesh(mesh_d, mesh_m)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    directory = peer_pool = None
    if args.peer_ssd_dir:
        from repro.core.directory import GlobalBlockDirectory
        directory = GlobalBlockDirectory()
        # restart recovery re-indexes the peer's flushed blocks; bind()
        # publishes them, so this run's misses can fetch across "nodes"
        peer_pool = HostKVPool(capacity_blocks=8, ssd_capacity_blocks=None,
                               ssd_dir=args.peer_ssd_dir,
                               directory=directory, node_id=1)
    pool = HostKVPool(capacity_blocks=args.pool_blocks,
                      ssd_capacity_blocks=args.ssd_blocks,
                      ssd_dir=args.ssd_dir,
                      directory=directory, node_id=0)
    if peer_pool is not None:
        pool.add_peer(1, peer_pool)
    pws, dw = build_engine(
        params, cfg, pool, max_batch=args.max_batch,
        substrate=args.decode_substrate, mesh=mesh,
        prefill_workers=1 if args.no_loop else args.prefill_workers,
        ssd_mode=args.ssd_mode, width_buckets=args.width_buckets)
    pw, page_pool = pws[0], dw.page_pool
    requests = trace_requests(cfg, args.requests, seed=args.seed,
                              max_new=args.max_new, trace_path=args.trace)
    t0 = time.time()
    if not args.no_loop:
        ls, outputs = serve_requests(
            pws, dw, requests, tbt_budget_s=args.tbt_budget,
            chunks_per_iter=args.chunks_per_iter,
            max_queue=max(args.requests, 8), admission=args.admission,
            preempt=not args.no_preempt, restore_mode=args.restore_mode)
        done = ls["completed"]
        total_new = sum(len(o.tokens) for o in outputs.values())
        print(f"loop: {ls['iterations']} iterations, {ls['decode_steps']} "
              f"decode steps, {ls['prefill_chunks']} prefill chunks "
              f"interleaved, {ls['rejected']} rejected by "
              f"'{args.admission}' backpressure, {ls['preemptions']} "
              f"preemptions ({ls['restores_reload']} reload / "
              f"{ls['restores_recompute']} recompute restores), TBT p50/p99 "
              f"{ls['tbt_p50_s'] * 1e3:.1f}/{ls['tbt_p99_s'] * 1e3:.1f} ms")
    else:
        done, total_new = 0, 0
        queue = list(requests)
        outputs: dict = {}
        while queue or dw.n_active:
            while queue and dw.n_active < args.max_batch:
                req = queue.pop(0)
                pres = pw(req.tokens, session=req.session)
                dw.join(req, pres)
                rid = req.req_id
                outputs[rid] = [pres.first_token]
                print(f"req {rid:4d}: prefill {pres.prompt_len:5d} tokens, "
                      f"reused {pres.reused_blocks} blocks, "
                      f"computed "
                      f"{pres.prompt_len - 512 * pres.reused_blocks}")
            for rid, tok, fin in dw.step():
                outputs[rid].append(tok)
                total_new += 1
                if fin:
                    done += 1
    dt = time.time() - t0
    pw_stats = [w.stats() for w in pws]
    st = {k: sum(s[k] for s in pw_stats) for k in pw_stats[0]}
    print(f"\nserved {done} requests in {dt:.1f}s — "
          f"{total_new / dt:.1f} tok/s decode, "
          f"pool: {pool.n_blocks} blocks resident, "
          f"prefix reuse {st['reused_blocks']} blocks "
          f"({512 * st['reused_blocks']} tokens skipped)")
    if page_pool is not None:
        ps = page_pool.stats()
        ds = dw.stats()
        if mesh is not None:
            print(f"mesh {args.mesh}: {page_pool.n_banks} page banks × "
                  f"{page_pool.bank_pages} pages (capacity "
                  f"{ps['capacity']} logical pages), "
                  f"{dw.slots_per_bank} slots per data shard")
        print(f"paged substrate: {page_pool.used_pages}/{page_pool.n_pages} "
              f"pages held, {ps['pages_written']} written, "
              f"{ps['shared_adoptions']} shared-prefix adoptions, "
              f"{ps['cow_copies']} COW, {ds['zero_copy_joins']} "
              f"zero-copy joins, {ps['pages_exported']} pages spilled / "
              f"{ps['pages_imported']} imported; hasher: "
              f"{pw.hasher.blocks_hashed} blocks SHA'd, "
              f"{pw.hasher.memo_hits} memo hits")
    if pool.store is not None:
        s = pool.store.stats()
        print(f"ssd store: {s['blocks']} blocks on disk "
              f"({s['file_bytes'] >> 10} KiB), {s['n_flushes']} write-back "
              f"flushes, {s['layer_reads']} layer reads, "
              f"{s['read_failures']} read failures; overlapped "
              f"{st['overlapped_requests']} prefills")
    if peer_pool is not None:
        print(f"global pool: fetched {pool.peer_blocks_fetched} blocks off "
              f"the peer store ({pool.peer_fetch_failures} failures"
              f"{', fallbacks ' + str(pool.fallback_reasons) if pool.fallback_reasons else ''}); "
              f"directory {directory.stats()}")
        peer_pool.close()
    pool.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

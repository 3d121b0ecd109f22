"""End-to-end disaggregated serving driver (§3 workflow, executable).

A miniature Mooncake deployment in one process: TWO prefill workers with
a shared CPU-DRAM KVCache pool, TWO continuous-batching decode workers,
and a Conductor (Algorithm 1) in front deciding, per request, which
prefill instance serves it (cache-aware + balancing) and which decode
instance it joins. Requests come from a generated Mooncake-format trace
and are realised to actual tokens whose block structure matches the hash
chains — so the engine's measured prefix reuse equals the trace's.

With ``--global-pool`` the two pools share one ``GlobalBlockDirectory``
(the Figure-3 cluster-wide pool): a block demoted to one instance's SSD
store is fetchable by the other, the Conductor prices the peer-SSD arm,
and the stores' measured read EMAs feed back into the arm prices.

With ``--processes N`` the cluster is N REAL OS processes: one parent
hosting the wire-protocol ``DirectoryServer``, N workers that each own a
``HostKVPool`` + ``BlockServer`` and fetch peer blocks over CRC-framed
sockets (``SocketPeer``). Each worker prefills its own document, then
serves a query extending ANOTHER node's document — a cross-process
socket fetch — and the parent checks every decoded token bit-exact
against a single-process DRAM-only oracle. ``--chaos kill-owner``
SIGKILLs the block owner mid-transfer; survivors must still match the
oracle, with the degradation accounted in ``fallback_reasons``. A chip
belongs to one process, so this lane runs on the CPU by design: every
worker is started with ``JAX_PLATFORMS=cpu`` and says so, and the parent
pins its own jax to the CPU and touches it only after the workers exit.

    PYTHONPATH=src python examples/serve_cluster.py [--requests 24]
    PYTHONPATH=src python examples/serve_cluster.py --ssd-blocks 64 \
        --ssd-dir /tmp/kvssd --dram-blocks 8 --global-pool
    PYTHONPATH=src python examples/serve_cluster.py --processes 3 \
        --chaos kill-owner
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.cache import CachePool
from repro.core.conductor import Conductor, DecodeInstance, PrefillInstance
from repro.core.costmodel import CostModel, InstanceSpec
from repro.core.messenger import Messenger
from repro.core.policies import list_policies
from repro.core.trace import BLOCK_TOKENS, TraceSpec, generate_trace
from repro.data.pipeline import realize_request_tokens
from repro.models.transformer import init_params
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker,
                                  prefix_hash_ids)
from repro.serving.request import ServingRequest


def _cluster_workload(n: int, vocab: int):
    """Deterministic docs + queries shared by parent, workers, and the
    oracle: query i extends node (i+1)%n's document, so serving it from
    node i forces a cross-process socket fetch."""
    rng = np.random.default_rng(42)
    docs = [rng.integers(0, vocab, size=2 * BLOCK_TOKENS, dtype=np.int32)
            for _ in range(n)]
    extras = [rng.integers(0, vocab, size=48, dtype=np.int32)
              for _ in range(n)]
    queries = [np.concatenate([docs[(i + 1) % n], extras[i]])
               for i in range(n)]
    return docs, queries


def _decode_all(params, cfg, pw, dw, tokens, max_new: int) -> list:
    pres = pw(tokens)
    dw.join(ServingRequest(req_id=0, tokens=tokens, max_new=max_new), pres)
    out = [pres.first_token]
    while dw.n_active:
        for _, tok, fin in dw.step():
            out.append(tok)
    return [int(t) for t in out]


def _worker_main(args) -> int:
    """One cluster node: HELLO the directory, serve blocks over a
    ``BlockServer``, fetch peers over ``SocketPeer``s, answer one query."""
    from repro.serving.directory_service import RemoteDirectory
    from repro.serving.transport import BlockServer, InProcPeer, SocketPeer

    n, i = args.processes, args.worker_node
    print(f"node {i}: jax on {jax.devices()[0].platform} (one chip cannot "
          f"be shared by {n} processes)", flush=True)
    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    docs, queries = _cluster_workload(n, cfg.vocab_size)

    # tiny DRAM tier: the node's own doc demotes straight to its SSD
    # store, so peers fetch it through the store's CRC'd slots
    pool = HostKVPool(capacity_blocks=1, ssd_capacity_blocks=64,
                      writeback_batch=1,
                      ssd_dir=os.path.join(args.ssd_dir, f"p{i}"))
    server = BlockServer(InProcPeer(pool), stall_s=args.serve_stall)
    host, port = args.directory.rsplit(":", 1)
    rdir = RemoteDirectory((host, int(port)), node_id=i,
                           block_port=server.port)
    pool.directory = rdir
    pool.node_id = i
    rdir.bind(i, pool.meta)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256,
                       ssd_mode=args.ssd_mode)
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=2048,
                      substrate="dense")

    pw(docs[i])                         # round 1: publish own doc
    br = rdir.barrier("published", n, timeout=600)
    if not br["met"]:
        print(f"node {i}: cluster failed to assemble ({br})", flush=True)
        return 2
    peers = {}
    for nid, (phost, pport) in sorted(rdir.nodes().items()):
        if nid != i:
            peers[nid] = SocketPeer((phost, pport), node=nid)
            pool.add_peer(nid, peers[nid])
    # parent joins this barrier too: it times the chaos kill off it
    br = rdir.barrier("round2", n + 1, timeout=600)
    if not br["met"]:
        print(f"node {i}: round-2 barrier failed ({br})", flush=True)
        return 2
    toks = _decode_all(params, cfg, pw, dw, queries[i], args.max_new)

    # modeled-vs-measured, wire edition: feed each peer's observed socket
    # bandwidth back into the Messenger's egress links
    msg = Messenger(list(range(n)), bw=100e9)
    bw = {}
    for nid, sp in peers.items():
        if sp.bw_ema:
            msg.set_link_bw(nid, sp.bw_ema)
            bw[str(nid)] = int(sp.bw_ema)
    print("RESULT " + json.dumps(dict(
        node=i, tokens=toks, peer_blocks=pool.peer_blocks_fetched,
        fallback=pool.fallback_reasons, bw=bw)), flush=True)
    for sp in peers.values():
        sp.close()
    server.close()
    rdir.close()
    pool.close()
    return 0


def _oracle(cfg, queries, max_new: int) -> dict:
    """Single-process DRAM-only decode of every query."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    opool = HostKVPool(capacity_blocks=4096)
    try:
        opw = PrefillWorker(params, cfg, opool, prefill_chunk=256)
        odw = DecodeWorker(params, cfg, max_batch=1, max_len=2048,
                           substrate="dense")
        return {i: _decode_all(params, cfg, opw, odw, q, max_new)
                for i, q in enumerate(queries)}
    finally:
        opool.close()


def _parent_main(args) -> int:
    """Launch N worker processes around an in-process DirectoryServer,
    optionally kill -9 the block owner mid-transfer, and hold every
    surviving answer bit-exact against a single-process oracle. The
    workers run on the CPU; so does the oracle, computed only after they
    have exited (the parent never holds a device while they run)."""
    import shutil
    import tempfile

    from repro.serving.directory_service import (DirectoryServer,
                                                 RemoteDirectory)

    jax.config.update("jax_platforms", "cpu")   # same backend as workers
    n = args.processes
    chaos = args.chaos == "kill-owner"
    stall = args.serve_stall if args.serve_stall is not None else \
        (0.2 if chaos else 0.0)
    cfg = get_config("smollm-360m").reduced()
    docs, queries = _cluster_workload(n, cfg.vocab_size)

    dserver = DirectoryServer()
    base = args.ssd_dir or tempfile.mkdtemp(prefix="serve-cluster-")
    made_tmp = args.ssd_dir is None
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    procs = []
    print(f"cluster: directory @ 127.0.0.1:{dserver.port}, "
          f"{n} worker processes"
          + (f", chaos={args.chaos} (stall {stall}s/layer)" if chaos else ""),
          flush=True)
    for i in range(n):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--processes", str(n), "--worker-node", str(i),
             "--directory", f"127.0.0.1:{dserver.port}",
             "--ssd-dir", base, "--ssd-mode", args.ssd_mode,
             "--max-new", str(args.max_new), "--serve-stall", str(stall)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env))
    rd = RemoteDirectory(dserver.addr)
    failures = 0
    try:
        br = rd.barrier("round2", n + 1, timeout=600)
        if not br["met"]:
            print(f"cluster never reached round 2: {br}", flush=True)
            return 1
        print(f"round 2 underway: nodes {sorted(dserver.endpoints())}",
              flush=True)
        if chaos:
            time.sleep(args.chaos_delay)
            print(f"chaos: SIGKILL node 0 (pid {procs[0].pid}) "
                  f"mid-FETCH_BLOCK", flush=True)
            os.kill(procs[0].pid, signal.SIGKILL)
            # the dead node's directory conn is its lease: its claims
            # must drop without any explicit withdraw
            doc0 = prefix_hash_ids(docs[0])
            deadline = time.time() + 10
            while time.time() < deadline and \
                    0 in dserver.directory.holders(doc0[0]):
                time.sleep(0.05)
            if 0 in dserver.directory.holders(doc0[0]):
                print("FAIL: dead node 0 still owns blocks in the "
                      "directory", flush=True)
                failures += 1
            else:
                print("directory self-healed: node 0's claims dropped",
                      flush=True)

        results = {}
        for i, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            for line in out.splitlines():
                if line.startswith("RESULT "):
                    r = json.loads(line[len("RESULT "):])
                    results[r["node"]] = r
            if chaos and i == 0:
                if p.returncode != -signal.SIGKILL:
                    print(f"FAIL: node 0 exited {p.returncode}, "
                          f"expected SIGKILL", flush=True)
                    failures += 1
            elif p.returncode != 0:
                print(f"FAIL: node {i} exited {p.returncode}:\n{out}",
                      flush=True)
                failures += 1
            for line in out.splitlines():
                if line.startswith(f"node {i}: jax on"):
                    print(line, flush=True)

        # every worker has exited: only now does the parent touch jax
        oracle = _oracle(cfg, queries, args.max_new)
        survivors = range(1 if chaos else 0, n)
        reasons: dict = {}
        for i in survivors:
            r = results.get(i)
            if r is None:
                print(f"FAIL: no RESULT from node {i}", flush=True)
                failures += 1
                continue
            ok = r["tokens"] == oracle[i]
            if not ok:
                failures += 1
            for k, v in r["fallback"].items():
                reasons[k] = reasons.get(k, 0) + v
            print(f"node {i}: {len(r['tokens'])} tokens "
                  f"{'bit-exact' if ok else 'MISMATCH'} vs oracle — "
                  f"peer_blocks={r['peer_blocks']} "
                  f"fallback={r['fallback']} bw={r['bw']}", flush=True)
        if chaos:
            if not (reasons.get("peer_unreachable")
                    or reasons.get("verify_failed")):
                print("FAIL: no survivor accounted the dead owner in "
                      f"fallback_reasons ({reasons})", flush=True)
                failures += 1
        else:
            expect = 2 * len(list(survivors))   # every query = 2 peer blocks
            got = sum(results[i]["peer_blocks"] for i in survivors
                      if i in results)
            if got != expect:
                print(f"FAIL: {got} peer blocks fetched over the wire, "
                      f"expected {expect}", flush=True)
                failures += 1
        print(("PASS" if not failures else f"FAIL ({failures})")
              + f": {len([i for i in survivors if i in results])}/{n} "
              f"nodes answered, degradations {reasons or '{}'}", flush=True)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        rd.close()
        dserver.close()
        if made_tmp:
            shutil.rmtree(base, ignore_errors=True)
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--dram-blocks", type=int, default=2048,
                    help="per-instance DRAM KVCache tier capacity (blocks)")
    ap.add_argument("--ssd-blocks", type=int, default=0,
                    help="per-instance SSD tier capacity (blocks); "
                         "0 = flat DRAM pool (seed behaviour)")
    ap.add_argument("--ssd-dir", default=None,
                    help="base directory for the file-backed SSD store "
                         "(one subdir per prefill instance); omit to keep "
                         "demoted bytes in host arrays")
    ap.add_argument("--ssd-mode", default="overlap",
                    choices=("blocking", "overlap"),
                    help="SSD prefix loads: synchronous, or overlapped "
                         "with head-chunk recompute (§5.2)")
    ap.add_argument("--strategy", default="kvcache",
                    choices=list_policies("prefill"),
                    help="prefill routing policy (from the registry)")
    ap.add_argument("--global-pool", action="store_true",
                    help="share one GlobalBlockDirectory across the prefill "
                         "instances' pools: blocks demoted on one node are "
                         "peer-fetchable from the other, and the Conductor "
                         "prices the peer-SSD arm (requires --ssd-blocks)")
    ap.add_argument("--decode-substrate", default="paged",
                    choices=("paged", "dense"),
                    help="decode KV substrate: block-table pages shared "
                         "prefill→decode (zero-copy join, prefix-sharing "
                         "slots), or the dense per-slot arena (the "
                         "bit-exactness oracle)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard the paged decode engine over a (data, "
                         "model) device mesh (e.g. 2x2): slots + page "
                         "banks over data, KV-head stripes over model. "
                         "Needs D*M jax devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--device-pages", type=int, default=0,
                    help="device page-pool size (0 = sized from the decode "
                         "workers' slot budget)")
    ap.add_argument("--loop", action="store_true",
                    help="drive the always-on ServingLoop (thread-fed "
                         "arrivals, chunked prefill interleaved with decode "
                         "steps, admission backpressure) instead of the "
                         "Conductor's phase-at-a-time dispatch")
    ap.add_argument("--tbt-budget", type=float, default=None,
                    help="loop TBT budget in seconds (default: "
                         "deterministic one-chunk-per-iteration interleave)")
    ap.add_argument("--processes", type=int, default=0,
                    help="run the cluster as N real OS processes over the "
                         "wire protocol (directory service + CRC-framed "
                         "block fetches), checked bit-exact against a "
                         "single-process oracle")
    ap.add_argument("--chaos", default="none",
                    choices=("none", "kill-owner"),
                    help="with --processes: SIGKILL the block owner "
                         "mid-FETCH_BLOCK; survivors must stay bit-exact "
                         "with the degradation in fallback_reasons")
    ap.add_argument("--chaos-delay", type=float, default=0.08,
                    help="seconds after the round-2 barrier to fire the "
                         "chaos kill")
    ap.add_argument("--serve-stall", type=float, default=None,
                    help="per-LAYER serving stall in each worker's "
                         "BlockServer (widens the mid-transfer window the "
                         "chaos kill lands in; default 0, or 0.2 under "
                         "--chaos kill-owner)")
    ap.add_argument("--worker-node", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: spawned by parent
    ap.add_argument("--directory", default=None,
                    help=argparse.SUPPRESS)   # internal: host:port
    args = ap.parse_args()

    if args.worker_node is not None:
        if args.serve_stall is None:
            args.serve_stall = 0.0
        sys.exit(_worker_main(args))
    if args.processes:
        sys.exit(_parent_main(args))

    if args.global_pool and not args.ssd_blocks:
        ap.error("--global-pool needs an SSD tier (--ssd-blocks > 0)")

    cfg = get_config("smollm-360m").reduced()
    mesh = None
    mesh_d = 1
    if args.mesh:
        import dataclasses

        from repro.launch.mesh import make_decode_mesh, parse_mesh_arg
        from repro.models.transformer import paged_shard_reason
        if args.decode_substrate != "paged":
            ap.error("--mesh shards the PAGED decode substrate")
        mesh_d, mesh_m = parse_mesh_arg(args.mesh)
        if mesh_m > 1 and paged_shard_reason(cfg, mesh_m, mesh_d):
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
        mesh = make_decode_mesh(mesh_d, mesh_m)
    params = init_params(cfg, jax.random.PRNGKey(0))

    # ---- build the disaggregated cluster ----
    n_p, n_d = 2, 2
    directory = None
    if args.global_pool:
        from repro.core.directory import GlobalBlockDirectory
        directory = GlobalBlockDirectory()
    # --ssd-dir without --ssd-blocks raises in HostKVPool (a store nothing
    # can reach is a config error, not a silent flat pool)
    pools = [HostKVPool(capacity_blocks=args.dram_blocks,
                        ssd_capacity_blocks=args.ssd_blocks,
                        ssd_dir=(os.path.join(args.ssd_dir, f"p{i}")
                                 if args.ssd_dir else None),
                        directory=directory, node_id=i)
             for i in range(n_p)]
    if directory is not None:
        from repro.serving.engine import connect_pools
        connect_pools(pools)
    # ONE device page pool for the whole in-process cluster (the HBM the
    # paged substrate pages live in): prefill workers stage fresh KV into
    # it and decode workers adopt the runs — the zero-copy §3 handoff
    max_batch, max_len, page_tokens = 4, 2048, 64
    page_pool = None
    from repro.serving.engine import paged_supported
    if args.decode_substrate == "paged" and paged_supported(cfg):
        from repro.serving.paged_cache import DevicePagePool
        per_seq = (max_len + page_tokens - 1) // page_tokens
        # mesh: n_pages is the PER-BANK budget (capacity scales ×data)
        n_pages = args.device_pages or \
            1 + ((n_d * max_batch) // mesh_d + n_p) * per_seq
        page_pool = DevicePagePool(cfg, n_pages=n_pages,
                                   page_tokens=page_tokens, mesh=mesh)
        if mesh is not None:
            print(f"decode mesh {args.mesh}: {page_pool.n_banks} page "
                  f"banks × {page_pool.bank_pages} pages, KV heads / "
                  f"{mesh_m} model shards")
    pws = [PrefillWorker(params, cfg, pools[i], prefill_chunk=256,
                         ssd_mode=args.ssd_mode, page_pool=page_pool)
           for i in range(n_p)]
    dws = [DecodeWorker(params, cfg, max_batch=max_batch, max_len=max_len,
                        substrate=args.decode_substrate, page_pool=page_pool)
           for _ in range(n_d)]

    cost = lambda: CostModel(get_config("llama2-70b"), InstanceSpec())
    P = [PrefillInstance(iid=i, pool=pools[i].meta, cost=cost())
         for i in range(n_p)]
    D = [DecodeInstance(iid=100 + i, cost=cost()) for i in range(n_d)]
    msg = Messenger([p.iid for p in P] + [d.iid for d in D], bw=100e9)
    if args.ssd_blocks:
        for p in P:
            msg.add_ssd_channel(p.iid, InstanceSpec().hw.ssd_read_bw)
    conductor = Conductor(P, D, msg, ttft_slo=30.0, tbt_slo=0.1,
                          strategy=args.strategy, directory=directory)

    # ---- workload: session-structured trace, scaled to smoke size ----
    trace = generate_trace(TraceSpec(
        n_requests=args.requests, duration_ms=5_000, seed=1,
        max_input_tokens=1536, chat_turn_mu=5.5, doc_len_mu=6.8,
        frac_oneshot=0.2, frac_chat=0.6, frac_doc=0.2))[:args.requests]
    for r in trace:
        r.input_length = min(max(r.input_length, 64), 1536)
        r.hash_ids = r.hash_ids[:max(r.input_length // BLOCK_TOKENS, 1)]

    if args.loop:
        # always-on mode: ONE ServingLoop owns the page pool, a single
        # decode batch, and both prefill workers; routing (deepest pool
        # residency) and backpressure live in the loop, so the Conductor
        # is bypassed. A feeder thread plays the trace's arrival order.
        import threading

        from repro.serving.loop import ServingLoop
        print(f"serving loop: {n_p} prefill workers -> 1 decode batch "
              f"(max_batch={dws[0].max_batch}); {len(trace)} requests\n")
        loop = ServingLoop(pws, dws[0], tbt_budget_s=args.tbt_budget,
                           max_queue=max(args.requests, 8))
        payloads = [(r.req_id, realize_request_tokens(r, cfg.vocab_size),
                     min(args.max_new, max(r.output_length, 2)),
                     r.hash_ids[0] if r.hash_ids else None) for r in trace]

        def feeder():
            for rid, toks, mn, sess in payloads:
                loop.submit(ServingRequest(req_id=rid, tokens=toks,
                                           max_new=mn, session=sess))
            loop.close_intake()

        t0 = time.time()
        th = threading.Thread(target=feeder, name="repro-loop-feeder")
        th.start()
        ls = loop.run()
        th.join()
        dt = time.time() - t0
        total_tokens = sum(len(o.tokens) for o in loop.outputs.values())
        reused = sum(pw.stats()["reused_blocks"] for pw in pws)
        print(f"served {ls['completed']} requests, {total_tokens} tokens "
              f"in {dt:.1f}s — {ls['decode_steps']} decode steps, "
              f"{ls['prefill_chunks']} prefill chunks interleaved, "
              f"{ls['rejected']} rejected by backpressure, "
              f"{ls['preemptions']} preemptions")
        print(f"prefix reuse: {reused} blocks; TBT p50/p99 "
              f"{ls['tbt_p50_s'] * 1e3:.1f}/{ls['tbt_p99_s'] * 1e3:.1f} ms")
        # every component reports through the same stats() protocol —
        # one uniform snapshot of the whole serving stack
        snapshots = {"loop": ls, "decode": dws[0].stats(),
                     "pool[0]": pools[0].stats()}
        if page_pool is not None:
            snapshots["pages"] = page_pool.stats()
        for name, snap in snapshots.items():
            line = ", ".join(f"{k}={v}" for k, v in sorted(snap.items())
                             if not isinstance(v, float))
            print(f"  {name:8s} {line}")
        for pool in pools:
            pool.close()
        return

    print(f"cluster: {n_p} prefill + {n_d} decode workers; "
          f"{len(trace)} requests\n")
    t0 = time.time()
    stats = dict(reused=0, computed=0, migrations=0)
    active: dict[int, int] = {}       # req_id -> decode worker idx
    outputs: dict[int, list] = {}
    queue = list(trace)

    while queue or any(dw.n_active for dw in dws):
        # admit as many as fit
        while queue and any(dw.n_active < dw.max_batch for dw in dws):
            req = queue.pop(0)
            dec = conductor.schedule(req, now=time.time() - t0)
            if not dec.accepted:
                print(f"req {req.req_id:3d}: REJECTED ({dec.reject_reason})")
                continue
            pi = dec.prefill.iid
            di = dec.decode.iid - 100
            if dws[di].n_active >= dws[di].max_batch:
                di = next(i for i, d in enumerate(dws)
                          if d.n_active < d.max_batch)
            # hot-spot migration: copy blocks between the REAL pools
            if dec.migrated_blocks and dec.transfer_from is not None:
                src = pools[dec.transfer_from]
                dstp = pools[pi]
                hit = src.meta.prefix_len(req.hash_ids)
                if hit:
                    k, v = src.get(req.hash_ids[:hit])
                    dstp.put(req.hash_ids[:hit], k, v)
                    stats["migrations"] += 1
            tokens = realize_request_tokens(req, cfg.vocab_size)
            # session key = chain root: turns of one session extend the same
            # chain, so the incremental hasher re-hashes only the suffix
            pres = pws[pi](tokens,
                           session=req.hash_ids[0] if req.hash_ids else None)
            stats["reused"] += pres.reused_blocks
            stats["computed"] += pres.prompt_len - 512 * pres.reused_blocks
            # close the modeled-vs-measured loop: feed the store's measured
            # read EMA back into the Conductor's arm prices (CostModel) and
            # the Messenger's SSD channel bandwidth. The channel bw must be
            # in the COST MODEL's byte units (the conductor prices 70B-sized
            # blocks; the engine stores reduced-model blocks), so one
            # modeled block load costs exactly one measured read
            store = pools[pi].store
            if store is not None and store.read_s_ema is not None:
                P[pi].cost.calibrate_ssd_read(store.read_s_ema)
                msg.set_ssd_bw(P[pi].iid,
                               P[pi].cost.kv_bytes(BLOCK_TOKENS)
                               / store.read_s_ema)
            dws[di].join(ServingRequest(
                req_id=req.req_id, tokens=tokens,
                max_new=min(args.max_new, max(req.output_length, 2))), pres)
            active[req.req_id] = di
            outputs[req.req_id] = [pres.first_token]
            print(f"req {req.req_id:3d}: prefill@P{pi} "
                  f"({pres.prompt_len:5d} tok, reuse {pres.reused_blocks:2d} "
                  f"blk{', migrated' if dec.migrated_blocks else ''}) "
                  f"-> decode@D{di}")
        # one continuous-batching iteration on every decode worker
        for dw in dws:
            for rid, tok, fin in dw.step():
                outputs[rid].append(tok)
                if fin:
                    active.pop(rid, None)

    dt = time.time() - t0
    total_tokens = sum(len(v) for v in outputs.values())
    print(f"\nserved {len(outputs)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s")
    print(f"prefix reuse: {stats['reused']} blocks "
          f"({512 * stats['reused']} tokens skipped), "
          f"computed {stats['computed']} tokens, "
          f"hot-spot migrations: {stats['migrations']}")
    hashed = sum(pw.hasher.blocks_hashed for pw in pws)
    memo = sum(pw.hasher.memo_hits for pw in pws)
    print(f"prefix hashing: {hashed} blocks SHA'd, {memo} session memo hits")
    if page_pool is not None:
        ps = page_pool.stats()
        zc = sum(dw.stats()["zero_copy_joins"] for dw in dws)
        print(f"paged substrate: {page_pool.n_pages} pages "
              f"({page_pool.page_tokens} tok), {page_pool.used_pages} held, "
              f"{ps['pages_written']} written, {ps['shared_adoptions']} "
              f"shared-prefix adoptions, {ps['cow_copies']} COW copies, "
              f"{ps['registry_evictions']} registry evictions; "
              f"{zc} zero-copy joins")
    print(f"conductor migrations (metadata): {conductor.n_migrations}")
    if directory is not None:
        d = directory.stats()
        fetched = sum(p.peer_blocks_fetched for p in pools)
        failures = sum(p.peer_fetch_failures for p in pools)
        reasons: dict = {}
        for p in pools:
            for k, v in p.fallback_reasons.items():
                reasons[k] = reasons.get(k, 0) + v
        print(f"global pool: directory {d['keys']} keys "
              f"({d['dram_claims']} dram / {d['ssd_claims']} ssd claims), "
              f"conductor peer-SSD arms won {conductor.n_peer_ssd_loads}, "
              f"engine fetched {fetched} peer blocks "
              f"({failures} failures{', ' + str(reasons) if reasons else ''})")
    if args.ssd_blocks:
        print(f"conductor SSD prefix loads: {conductor.n_ssd_loads}")
        for i, pool in enumerate(pools):
            s = pool.meta.tier_stats()
            print(f"P{i} tiers: dram={s['dram_blocks']} ssd={s['ssd_blocks']} "
                  f"hits(dram/ssd)={s['dram_hits']}/{s['ssd_hits']} "
                  f"demote={s['demotions']} promote={s['promotions']} "
                  f"writebacks={s['n_writebacks']}")
            if pool.store is not None:
                st = pool.store.stats()
                print(
                    f"   store: {st['blocks']} on disk "
                    f"({st['file_bytes'] >> 10} KiB), wrote "
                    f"{st['blocks_written']} blk / {st['n_flushes']} flushes, "
                    f"read {st['layer_reads']} layers, "
                    f"{st['read_failures']} failures; engine overlapped "
                    f"{pws[i].stats()['overlapped_requests']} prefills "
                    f"({pws[i].stats()['ssd_loaded_blocks']} blocks "
                    f"prefetched)")
    for pool in pools:
        pool.close()


if __name__ == "__main__":
    main()

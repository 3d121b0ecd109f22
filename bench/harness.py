"""One run of one cell: engine, warm-up, window, metrics and the check."""
from __future__ import annotations

import gc
import glob
import importlib.util
import os
import shutil
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import flops, reference, traffic
from bench.model import load_config
from bench.spans import CompileCounter, Recorder, instrument

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BLOCK = traffic.BLOCK
PAGE = 64                       # the decode worker's page (tokens)


def _pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def _cpu_s() -> float:
    """CPU seconds this process has used, all threads."""
    t = os.times()
    return t.user + t.system


# ---- engine ----------------------------------------------------------------

def build_engine(cfg, params, eng: dict):
    """The serving engine through the program's public API: one
    ``PrefillWorker`` over a ``HostKVPool`` and one paged ``DecodeWorker``,
    sharing the decode worker's ``DevicePagePool``."""
    from repro.serving.engine import DecodeWorker, HostKVPool, PrefillWorker
    pool = HostKVPool(capacity_blocks=eng["host_pool_blocks"])
    dw = DecodeWorker(params, cfg, max_batch=eng["max_batch"],
                      max_len=eng["max_len"], substrate="paged")
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=eng["prefill_chunk"],
                       page_pool=dw.page_pool)
    return pool, pw, dw


def warm_up(arch, cfg, pool, pw, dw, work: traffic.Workload, seed: int,
            log) -> int:
    """Run every shape the window can use once: for each prompt length,
    every prefix-hit depth the traffic can produce (one chunk each: the
    hit path's updates are shaped by the depth) and, to its end, the
    deepest one (the store, the staging writes and the join), then decode
    steps at every table width. Hits are made by putting zero KV for the
    prompt's leading blocks into the host pool (``arch.host_kv``). Returns
    the prefills run."""
    from repro.serving.request import ServingRequest
    rng = np.random.default_rng((int(seed), 99))
    finished = []
    n = 0
    for S in work.prompt_lengths:
        deepest = (S - 1) // BLOCK * BLOCK
        for depth in sorted(set(work.hit_depths[S]) | {deepest}):
            toks = rng.integers(0, cfg.vocab_size, S).astype(np.int32)
            if depth:
                ids = pw.hasher.hash_ids(toks)[:depth // BLOCK]
                pool.put(ids, *arch.host_kv(cfg, depth))
            cp = pw.start(toks)
            n += 1
            if depth == deepest:
                res = cp.drain()
                if res.reused_blocks * BLOCK != depth:
                    raise RuntimeError(f"warm-up: {S}-token prompt reused "
                                       f"{res.reused_blocks} blocks, "
                                       f"expected {depth // BLOCK}")
                finished.append((S, toks, res))
            else:
                cp.advance()
                del cp
    # decode: one slot at each table width the window can reach
    max_new = max(r.max_new for r in (work.requests or
                                      [r for c in work.clients for r in c]))
    widths = set()
    lo = min(work.prompt_lengths) // PAGE + 1
    hi = (max(work.prompt_lengths) + max_new - 1) // PAGE + 1
    for need in range(lo, hi + 1):
        widths.add(min(1 << (need - 1).bit_length(), dw.max_pages))
    done_widths = set()
    for i, (S, toks, res) in enumerate(finished):
        w = min(1 << (S // PAGE).bit_length(), dw.max_pages)
        if w in done_widths:
            res.release_pages()
            continue
        dw.join(ServingRequest(req_id=-1 - i, tokens=toks, max_new=2), res)
        while dw.n_active:
            dw.step()
        done_widths.add(w)
    # widths that only a slot's growth reaches: grow the longest prompt
    missing = sorted(widths - done_widths)
    if missing:
        S, toks, _ = finished[-1]
        grow = min(dw.max_len - S, (max(missing) - 1) * PAGE + 1 - S)
        dw.join(ServingRequest(req_id=-10 ** 6, tokens=toks,
                               max_new=max(grow, 2)), pw(toks))
        while dw.n_active:
            dw.step()
    log(f"warm-up: {n} prefills over {len(work.prompt_lengths)} prompt "
        f"lengths, decode widths {sorted(widths)}")
    return n


# ---- the window ---------------------------------------------------------------

class Window:
    """Offers the workload to a ``ServingLoop`` and records what happened
    to each request on the host clock (``time.monotonic``)."""

    def __init__(self, loop, work: traffic.Workload, seconds: float,
                 drain_s: float) -> None:
        self.loop = loop
        self.work = work
        self.seconds = seconds
        self.drain_s = drain_s
        self.sent: dict = {}        # req_id -> (due, sent, accepted)
        self.done_events: dict = {}
        self.t_open = self.t_close = self.t_drained = 0.0
        self._lock = threading.Lock()

    def on_step(self, emitted) -> None:
        """Called from the decode step: wakes closed-loop clients."""
        for rid, _, fin in emitted:
            if fin:
                ev = self.done_events.get(rid)
                if ev is not None:
                    ev.set()

    def _send(self, r, due: float) -> bool:
        from repro.serving.request import ServingRequest
        req = ServingRequest(req_id=r.req_id, tokens=r.tokens,
                             max_new=r.max_new, session=r.session)
        ev = threading.Event()
        with self._lock:
            self.done_events[r.req_id] = ev
        t = time.monotonic()
        ok = self.loop.submit(req)
        with self._lock:
            self.sent[r.req_id] = (due, t, ok)
        return ok

    def _open_client(self) -> None:
        for r in self.work.requests:
            due = self.t_open + r.due_s
            if due >= self.t_close:
                break
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            try:
                self._send(r, due)
            except RuntimeError:        # intake closed: the engine failed
                return

    def _closed_client(self, script) -> None:
        for r in script:
            now = time.monotonic()
            if now >= self.t_close:
                return
            try:
                ok = self._send(r, now)
            except RuntimeError:        # intake closed: the engine failed
                return
            if ok:
                self.done_events[r.req_id].wait(
                    max(self.t_close + self.drain_s - time.monotonic(), 0))
            else:
                time.sleep(0.01)

    def run(self) -> dict:
        engine_out: dict = {}

        def engine():
            try:
                engine_out["stats"] = self.loop.run()
            except BaseException as e:      # re-raised on the main thread
                engine_out["error"] = e
                self.loop.close_intake()

        eng = threading.Thread(target=engine, name="bench-engine")
        self.t_open = time.monotonic()
        self.t_close = self.t_open + self.seconds
        if self.work.loop == "open":
            clients = [threading.Thread(target=self._open_client,
                                        name="bench-client")]
        else:
            clients = [threading.Thread(target=self._closed_client,
                                        args=(s,), name=f"bench-client-{i}")
                       for i, s in enumerate(self.work.clients)]
        eng.start()
        for c in clients:
            c.start()
        wait = self.t_close - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        for c in clients:
            c.join(self.drain_s + 5)
        self.loop.close_intake()
        eng.join(max(self.t_close + self.drain_s - time.monotonic(), 1.0))
        if eng.is_alive():
            self.loop.stop()        # abandons queued and mid-prefill work
            eng.join()
        self.t_drained = time.monotonic()
        for c in clients:
            for ev in list(self.done_events.values()):
                ev.set()
            c.join()
        if "error" in engine_out:
            raise RuntimeError("the serving loop failed") \
                from engine_out["error"]
        return engine_out["stats"]


# ---- metrics -----------------------------------------------------------------

def request_records(win: Window, outputs: dict, limits: dict) -> list:
    """One record per request sent in the window."""
    recs = []
    closed = win.work.loop == "closed"
    for rid, (due, sent, ok) in sorted(win.sent.items()):
        o = outputs.get(rid)
        ts = list(o.token_t) if (ok and o is not None) else []
        start = sent if closed else due
        ttft = ts[0] - start if ts else None
        gaps = np.diff(ts) if len(ts) > 1 else np.zeros(0)
        done = bool(ok and o is not None and o.done)
        # the request's nearest approach to a limit, as a share of it
        reach = max(ttft * 1e3 / limits["ttft_ms"] if ttft is not None
                    else np.inf,
                    _pct(gaps, 90) * 1e3 / limits["tbt_ms"] if len(gaps)
                    else 0.0)
        good = bool(done and reach <= 1.0)
        recs.append(dict(req_id=rid, due=due, sent=sent, accepted=ok,
                         done=done, ttft=ttft, token_t=ts, good=good,
                         reach=reach,
                         tokens=list(o.tokens) if o is not None else []))
    return recs


def end_to_end(recs: list, win: Window) -> dict:
    t0, t1 = win.t_open, win.t_close
    ttfts = [r["ttft"] for r in recs if r["ttft"] is not None]
    gaps, n_tok = [], 0
    for r in recs:
        ts = r["token_t"]
        n_tok += sum(t0 <= t <= t1 for t in ts)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if t0 <= b <= t1]
    m = {}
    if ttfts:
        m["ttft_p90_ms"] = {"value": _pct(ttfts, 90) * 1e3, "unit": "ms"}
    if gaps:
        m["tbt_p99_ms"] = {"value": _pct(gaps, 99) * 1e3, "unit": "ms"}
    m["output_tok_s"] = {"value": n_tok / win.seconds, "unit": "tokens/s"}
    m["goodput_rps"] = {"value": sum(r["good"] for r in recs) / win.seconds,
                        "unit": "req/s"}
    return m


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)


def counts(arch) -> SimpleNamespace:
    """``ctx.flops``: the architecture's least operations and bytes, and
    the roofline that turns them into seconds."""
    return SimpleNamespace(prefill_chunk=arch.prefill_chunk,
                           decode_step=arch.decode_step,
                           weight_bytes=arch.weight_bytes,
                           least_seconds=flops.least_seconds)


def per_layer(names: list, ctx: Context) -> dict:
    """Each metric's reader, ``bench/metrics/<name>.py``; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for name, unit in names:
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[name] = {"value": float(v), "unit": unit}
    return out


# ---- the check ---------------------------------------------------------------

def pick_sample(recs: list, work: traffic.Workload, prefills: dict,
                n: int, budget: int, seed: int) -> list:
    """Finished requests to check, at most ``n`` of them and ``budget``
    prompt and served tokens together (the reference's time grows with
    them): the one with the most served tokens always, then one served
    from host-pool hits and one computed cold where there are such, then
    others drawn from the seed. Returns (record, request, hit tokens)."""
    by_id = {r.req_id: r for r in (work.requests or
                                   [r for c in work.clients for r in c])}
    done = [r for r in recs if r["done"] and r["tokens"]]
    if not done:
        return []
    reused = {r["req_id"]: prefills.get(id(by_id[r["req_id"]].tokens),
                                        {}).get("reused_tokens", 0)
              for r in done}
    rng = np.random.default_rng((int(seed), 5))
    first = max(done, key=lambda r: (len(r["tokens"]), r["req_id"]))
    hits = [r for r in done if reused[r["req_id"]] > 0]
    cold = [r for r in done if reused[r["req_id"]] == 0]
    rest = [done[int(i)] for i in rng.permutation(len(done))]
    for g in (cold, hits):
        if g:
            rest.insert(0, g[int(rng.integers(len(g)))])
    picks, used = [first], 0
    used = len(by_id[first["req_id"]].tokens) + len(first["tokens"])
    for r in rest:
        size = len(by_id[r["req_id"]].tokens) + len(r["tokens"])
        if len(picks) >= n:
            break
        if r not in picks and used + size <= budget:
            picks.append(r)
            used += size
    return [(r, by_id[r["req_id"]], reused[r["req_id"]]) for r in picks]


def check_outputs(arch, shape, seed: int, sample: list, limit: float, log,
                  control: bool) -> tuple[bool, dict]:
    """Compare the served tokens of ``sample`` with the reference. With
    ``control`` the fp8 control stands in for the program: its tokens at
    the same positions are what is compared, by the same test."""
    if not sample:
        return False, {"checked_requests": {"value": 0, "limit": ">= 1"}}
    prompts = [np.asarray(q.tokens, np.int32) for _, q, _ in sample]
    served = [np.asarray(r["tokens"], np.int64) for r, _, _ in sample]
    short = sum(len(r["tokens"]) != q.max_new for r, q, _ in sample)
    t0 = time.monotonic()
    gaps = reference.served_gaps(arch, shape, seed, prompts, served,
                                 control=control)
    worst = {k: max(float(g.max()) for g in v) for k, v in gaps.items()}
    n_tok = sum(len(g) for g in gaps["served"])
    log(f"check: {len(sample)} requests, {n_tok} served tokens "
        f"(prompts {[len(p) for p in prompts]}, host-pool hit tokens "
        f"{[h for _, _, h in sample]}), widest logit gap {worst['served']!r}"
        f" (limit {limit!r}), reference {time.monotonic() - t0:.1f}s")
    if control:
        log(f"control: the fp8 pass stands in for the program, widest "
            f"logit gap {worst['control']!r} over {n_tok} positions")
    compared = worst["control" if control else "served"]
    ok = compared <= limit and short == 0
    check = {"checked_tokens": {"value": n_tok, "limit": ">= 1"},
             "short_requests": {"value": short, "limit": 0},
             "max_logit_gap": {"value": compared, "limit": limit}}
    return ok, check


# ---- one run -------------------------------------------------------------------

def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             device: dict, control: bool = False, t_process: float,
             log, config_path=None, fault=None) -> dict:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.loop import ServingLoop
    from bench.device import peaks
    enable_compile_cache()
    arch, shape = load_config(cell["config"], path=config_path)
    cfg = arch.program_config(shape)
    mix = traffic.load_mix(cell["traffic"])
    eng = cell["engine"]
    meminfo = Path("/proc/meminfo")
    if meminfo.exists():
        log("host " + " ".join(meminfo.read_text().split("\n")[:2]))
    work = traffic.make_workload(mix, cell, seed, seconds, shape.vocab_size)
    log(traffic.describe(work))
    params = arch.program_params(shape, seed, cfg.padded_vocab)
    jax.block_until_ready(params)
    log(f"weights made on {device['kind']}")
    pool, pw, dw = build_engine(cfg, params, eng)
    rec = Recorder()
    instrument(rec, [pw], dw, pool)
    warm_up(arch, cfg, pool, pw, dw, work, seed, log)
    gc.collect()
    if fault is not None:
        fault(pw, dw)
    loop = ServingLoop([pw], dw, chunks_per_iter=eng["chunks_per_iter"],
                       max_queue=eng["max_queue"],
                       admission=eng["admission"])
    win = Window(loop, work, seconds, cell["drain_s"])
    step = dw.step

    def step_and_notify():
        out = step()
        win.on_step(out)
        return out

    dw.step = step_and_notify
    counters0 = dict(prefill=pw.stats(), decode=dw.stats())
    compiles = CompileCounter()
    tracer = None
    if trace:
        tracer = _Tracer(rec, seconds, cell.get("trace_s", 10.0))
    rec.recording = True
    compiles.counting = True
    setup_s = time.monotonic() - t_process
    log(f"set-up {setup_s:.2f}s; window opens for {seconds}s")
    cpu0 = _cpu_s()
    if tracer is not None:
        tracer.start()
    stats = win.run()
    compiles.counting = False
    rec.recording = False
    if tracer is not None:
        tracer.join()
    counters1 = dict(prefill=pw.stats(), decode=dw.stats())
    outputs = dict(loop.outputs)
    limits = cell["limits"]
    recs = request_records(win, outputs, limits)
    late = [s - d for d, s, _ in win.sent.values()]
    attempted = len(recs)
    failed = sum(not r["done"] for r in recs)
    log(f"window: {attempted} requests sent, {sum(r['done'] for r in recs)} "
        f"done, {sum(not r['accepted'] for r in recs)} shed; generator "
        f"late by p50 {_pct(late, 50) * 1e3:.2f} ms, max "
        f"{max(late) * 1e3:.2f} ms; drained {win.t_drained - win.t_close:.1f}"
        f"s after the close; {compiles.compiles} compiles and "
        f"{compiles.cache_loads} cache loads in the window; loop {stats}")
    log("share of a limit reached, by due time: " + " ".join(
        f"{r['reach']:.2f}" for r in sorted(recs, key=lambda r: r["due"])))
    log("ttft ms by due time: " + " ".join(
        f"{r['ttft'] * 1e3:.0f}" if r["ttft"] is not None else "-"
        for r in sorted(recs, key=lambda r: r["due"])))
    log(f"host: process cpu {_cpu_s() - cpu0:.1f}s over "
        f"{win.t_drained - win.t_open:.1f}s")
    mem =jax.devices()[0].memory_stats() or {}
    dev = dict(device)
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        from bench.trace_reduce import breakdown
        red = tracer.reduce()
        ctx = Context(cfg=cfg, shape=shape, recorder=rec, window=win,
                      records=recs, counters0=counters0, counters1=counters1,
                      compiles=compiles, reduced=red,
                      peak=peaks(device["kind"]), flops=counts(arch),
                      limits=limits)
        names = [(m["name"], m["unit"]) for m in cell["per_layer"]]
        result["metrics"] = per_layer(names, ctx)
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = breakdown(red)
    else:
        m = end_to_end(recs, win)
        m["setup_s"] = {"value": setup_s, "unit": "s"}
        log("end to end, all: " + ", ".join(
            f"{k} {v['value']!r}" for k, v in m.items()))
        result["metrics"] = {k: v for k, v in m.items()
                             if k in cell.get("end_to_end", m)}
    result["device"] = dev
    prefills = rec.prefills
    # free the engine before the reference runs: a process's peak never
    # falls, and the reference needs the chip's memory
    del loop, win.loop, pw, dw, pool, params, step
    gc.collect()
    sample = pick_sample(recs, work, prefills, cell["check"]["sample"],
                         cell["check"]["tokens"], seed)
    ok, check = check_outputs(arch, shape, seed, sample,
                              cell["check"]["max_logit_gap"], log, control)
    ok = ok and attempted > 0
    result["correct"] = bool(ok)
    for k, v in check.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    result["check"] = check
    return result


class _Tracer:
    """Profiles ``trace_s`` seconds in the middle of the window, the whole
    window where ``trace_s`` is as long."""

    def __init__(self, rec: Recorder, seconds: float, trace_s: float) -> None:
        self.rec = rec
        self.trace_s = min(trace_s, seconds)
        self.lead = (seconds - self.trace_s) / 2
        self.dir = ROOT / "bench_out" / "trace"
        self._th = threading.Thread(target=self._run, name="bench-tracer")

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self._th.start()

    def _run(self) -> None:
        import jax
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        po.host_tracer_level = 1
        po.enable_hlo_proto = False
        time.sleep(self.lead)
        self.rec.annotate = True
        jax.profiler.start_trace(str(self.dir), profiler_options=po)
        time.sleep(self.trace_s)
        jax.profiler.stop_trace()
        self.rec.annotate = False

    def join(self) -> None:
        self._th.join()

    def reduce(self):
        from bench.trace_reduce import reduce_trace
        path = glob.glob(str(self.dir / "plugins/profile/*/*.xplane.pb"))[0]
        try:
            return reduce_trace(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

"""The one traffic generator; each mix is a data file that it reads.

``bench/traffic/<mix>.json`` gives the generator its parameters: the
arrival kind (``open``: requests due on a schedule, or ``closed``: each
client sends its next turn when the last one completes), the session
shapes, the length distributions and how prompts are cut and rounded.
The cell's file (``bench/cells/<cell>.json``) adds what depends on the
model and the chip: the offered rate or the client count, and the
prompt cap.

Two seeds. ``structure_seed`` (in the mix's file) draws the schedule:
every session's kind, turn lengths, output lengths, think times, start
time and client. ``--seed`` draws the tokens of every block (and, in the
harness, the weights). So every run seed offers the same sizes at the
same times with the same sharing, and the spread between seeds measures
the system, not a different amount or order of work: with some tens of
requests in a window, a p90 over a reshuffled schedule swings by tens of
percent from the order alone.

Prompts are realised from hash chains of 512-token blocks as in the
Mooncake open trace: equal block ids give equal blocks and equal
prefixes, so prefix reuse is the trace's (the session and system-prompt
structure is copied from ``repro.core.trace.generate_trace``, the block
realisation from ``repro.data.pipeline.realize_request_tokens``). An open
mix with ``"fresh_blocks": true`` draws every request's chain from new
block ids: the same schedule, and no system prompt, turn or document
shared between requests.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
BLOCK = 512          # the trace's block; the program's prefix-hash block


@dataclass
class Req:
    """One request as the client will send it."""
    req_id: int
    tokens: np.ndarray      # int32 prompt
    max_new: int
    session: int
    kind: str
    due_s: float = 0.0      # open loop: seconds after the window opens


@dataclass
class Workload:
    loop: str                       # "open" | "closed"
    requests: list                  # open: every Req, by due time
    clients: list                   # closed: one list of Req per client
    prompt_lengths: list            # every prompt length the run can send
    hit_depths: dict                # prompt length -> possible hit tokens


def load_mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _lognormal(rng, p: dict) -> float:
    return float(rng.lognormal(p["mu"], p["sigma"]))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class _Blocks:
    """Realises hash ids to token blocks for one run seed."""

    def __init__(self, seed: int, vocab: int) -> None:
        self.seed = int(seed)
        self.vocab = vocab
        self._memo: dict = {}

    def tokens(self, chain: list, n: int) -> np.ndarray:
        out = []
        for h in chain[:-(-n // BLOCK)]:
            if h not in self._memo:
                rng = np.random.default_rng((self.seed, 7, int(h)))
                self._memo[h] = rng.integers(0, self.vocab, BLOCK,
                                             dtype=np.int64).astype(np.int32)
            out.append(self._memo[h])
        flat = np.concatenate(out)
        assert len(flat) >= n, (len(flat), n)
        return flat[:n]


# ---- open loop: Mooncake-shaped sessions ---------------------------------

def _session_shapes(mix: dict, n_requests: int, seconds: float,
                    cap: int) -> list:
    """Sessions (kind, system prompt, [(prompt_len, out_len, gap_s)]) and
    their start times, all from ``structure_seed``. Lengths follow
    ``generate_trace``: chat turns extend the chain by the last output and
    a new input, document turns by the last output and a follow-up. A
    prompt is capped at ``cap`` tokens on its first turn; a later turn
    that would pass the cap ends the session."""
    rng = np.random.default_rng(mix["structure_seed"])
    align = mix["prompt_align"]
    out_p = mix["output"]
    sp = mix["system_prompts"]
    sys_blocks = [int(rng.integers(*sp["blocks"])) for _ in range(sp["count"])]
    zipf = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
    zipf /= zipf.sum()
    scale = mix["think_scale"]
    per_tok = mix["think_per_output_token_s"]

    def out_len() -> int:
        return int(np.clip(_lognormal(rng, out_p), out_p["min"], out_p["max"]))

    def fit(n: int) -> int:
        return min(_round_up(max(n, 1), align), cap)

    kinds = rng.choice(3, size=n_requests, p=[mix["mix"]["chat"],
                                              mix["mix"]["doc"],
                                              mix["mix"]["oneshot"]])
    budget = [int((kinds == k).sum()) for k in range(3)]
    sessions = []
    # chat: system prompt + turns of lognormal new input
    c = mix["chat"]
    left = budget[0]
    while left > 0:
        turns = min(int(rng.integers(*c["turns"])), left)
        left -= turns
        s = int(rng.choice(sp["count"], p=zipf))
        total = sys_blocks[s] * BLOCK
        shape = []
        for _ in range(turns):
            total += int(np.clip(_lognormal(rng, c["turn"]), *c["turn_clip"]))
            n = fit(total)
            if shape and n >= cap:
                break
            o = out_len()
            gap = (float(rng.exponential(c["think_mean_s"])) + o * per_tok)
            shape.append((n, o, gap * scale))
            total = n + o
        sessions.append(("chat", s, shape))
    # documents: system prompt + a long document, then follow-up questions
    d = mix["doc"]
    left = budget[1]
    while left > 0:
        turns = min(int(rng.integers(*d["turns"])), left)
        left -= turns
        s = int(rng.choice(sp["count"], p=zipf))
        doc = int(max(_lognormal(rng, d["len"]), d["len_min"]))
        total = sys_blocks[s] * BLOCK + doc
        shape = []
        for _ in range(turns):
            n = fit(total)
            if shape and n >= cap:
                break
            o = out_len()
            gap = (float(rng.exponential(d["think_mean_s"])) + o * per_tok)
            shape.append((n, o, gap * scale))
            total = n + o + int(_lognormal(rng, d["followup"]))
        sessions.append(("doc", s, shape))
    # one-shot requests with no shared prefix
    o1 = mix["oneshot"]
    for _ in range(budget[2]):
        n = fit(int(max(_lognormal(rng, o1["len"]), o1["len_min"])))
        sessions.append(("oneshot", None, [(n, out_len(), 0.0)]))
    starts = np.sort(rng.uniform(0, seconds * 0.97, size=len(sessions)))
    return sessions, sys_blocks, [float(t) for t in starts]


def _open_workload(mix: dict, cell: dict, seed: int, seconds: float,
                   vocab: int) -> Workload:
    cap = cell["prompt_cap"]
    n = max(int(round(cell["rate_rps"] * seconds)), 1)
    sessions, sys_blocks, starts = _session_shapes(mix, n, seconds, cap)
    order = np.random.default_rng(mix["structure_seed"]).permutation(
        len(sessions))
    blocks = _Blocks(seed, vocab)
    next_hash = [10 ** 6]

    def new_ids(k: int) -> list:
        ids = list(range(next_hash[0], next_hash[0] + k))
        next_hash[0] += k
        return ids

    sys_ids = [new_ids(b) for b in sys_blocks]
    fresh = mix.get("fresh_blocks", False)
    reqs, full = [], []
    for slot, si in enumerate(order):
        kind, s, shape = sessions[si]
        t = starts[slot]
        chain = list(sys_ids[s]) if s is not None else []
        for prompt, out, gap in shape:
            if fresh:
                chain = []
            if len(chain) * BLOCK < prompt:
                chain = chain + new_ids(-(-prompt // BLOCK) - len(chain))
            if t < seconds:
                reqs.append(Req(req_id=0, tokens=blocks.tokens(chain, prompt),
                                max_new=out, session=int(si), kind=kind,
                                due_s=t))
                full.append((t, tuple(chain[:prompt // BLOCK])))
            t += gap
    order = sorted(range(len(reqs)), key=lambda i: reqs[i].due_s)
    reqs = [reqs[i] for i in order]
    for i, r in enumerate(reqs):
        r.req_id = i
    return Workload("open", reqs, [], sorted({len(r.tokens) for r in reqs}),
                    _hit_depths([len(r.tokens) for r in reqs],
                                [full[i][1] for i in order]))


def _hit_depths(lengths: list, chains: list) -> dict:
    """Prompt length -> every prefix-hit depth (tokens) a prompt of that
    length can meet. The host pool holds every prefix of every finished
    prefill's chain (it is sized not to evict within a window), so a hit
    is as deep as the longest common prefix with some other request,
    whichever of them finished first, and at most one block short of the
    prompt (the last block is computed again for its logits)."""
    out: dict = {}
    for i, (S, a) in enumerate(zip(lengths, chains)):
        d = out.setdefault(S, {0})
        for j, b in enumerate(chains):
            if i != j:
                n = 0
                while n < min(len(a), len(b)) and a[n] == b[n]:
                    n += 1
                d.add(min(n, (S - 1) // BLOCK) * BLOCK)
    return {S: sorted(d) for S, d in out.items()}


# ---- closed loop: chat clients with a hot system prompt -----------------

def _closed_workload(mix: dict, cell: dict, seed: int, vocab: int) -> Workload:
    """Each client runs sessions one after another: a system-prompt block
    drawn from a few hot prompts, then turns that each add the last
    output and a new input, rounded up to ``prompt_align``. A session
    restarts when prompt plus output would pass ``max_len``."""
    rng = np.random.default_rng(mix["structure_seed"])
    max_len = cell["engine"]["max_len"]
    align = mix["prompt_align"]
    sp = mix["system_prompts"]
    zipf = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
    zipf /= zipf.sum()
    out_p, turn_p = mix["output"], mix["turn"]
    sessions = []
    for _ in range(mix["sessions"]):
        s = int(rng.choice(sp["count"], p=zipf))
        total, shape = sp["blocks"] * BLOCK, []
        while True:
            total += int(np.clip(_lognormal(rng, turn_p), *turn_p["clip"]))
            n = _round_up(total, align)
            o = int(np.clip(_lognormal(rng, out_p), out_p["min"], out_p["max"]))
            if n + o > max_len:
                break
            shape.append((n, o))
            total = n + o
        if shape:
            sessions.append((s, shape))
    blocks = _Blocks(seed, vocab)
    n_clients = cell["clients"]
    clients = [[] for _ in range(n_clients)]
    next_hash = 10 ** 6 + sp["count"] * sp["blocks"]
    rid = 0
    for j, (s, shape) in enumerate(sessions):
        si = j
        chain = list(range(10 ** 6 + s * sp["blocks"],
                           10 ** 6 + (s + 1) * sp["blocks"]))
        k = -(-max_len // BLOCK) - len(chain)
        chain += list(range(next_hash, next_hash + k))
        next_hash += k
        for n, o in shape:
            clients[j % n_clients].append(Req(
                req_id=rid, tokens=blocks.tokens(chain, n), max_new=o,
                session=int(si), kind="chat"))
            rid += 1
    lengths = sorted({n for _, shape in sessions for n, _ in shape})
    shared = sp["blocks"] * BLOCK
    depths = {S: sorted({0, min(shared, (S - 1) // BLOCK * BLOCK)})
              for S in lengths}
    return Workload("closed", [], clients, lengths, depths)


def make_workload(mix: dict, cell: dict, seed: int, seconds: float,
                  vocab: int) -> Workload:
    if mix["loop"] == "open":
        return _open_workload(mix, cell, seed, seconds, vocab)
    if mix["loop"] == "closed":
        return _closed_workload(mix, cell, seed, vocab)
    raise ValueError(f"unknown loop kind {mix['loop']!r}")


def describe(w: Workload) -> str:
    reqs = w.requests or [r for c in w.clients for r in c]
    p = np.array([len(r.tokens) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    return (f"{len(reqs)} requests prepared ({w.loop} loop), prompt tokens "
            f"mean {p.mean():.0f} max {p.max()}, output tokens mean "
            f"{o.mean():.0f} max {o.max()}, {len(w.prompt_lengths)} prompt "
            f"lengths {w.prompt_lengths}")

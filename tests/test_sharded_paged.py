"""Shard-invariance suite for the (data, model) mesh-sharded paged
decode path: the mesh must be a pure physical re-layout — page banks
data-parallel over decode slots, KV-head stripes model-parallel — with
every host-side logical op (alloc/refcount/COW/export) and every decoded
token bit-identical to the single-device pool.

Default lane: the mesh-free split oracle, width-bucket planning, and the
API gates. Device lane: ``run_subprocess(devices=4)`` spins up 4 virtual
CPU devices and re-checks the property end-to-end through the engine on
meshes (1,1), (2,1), (1,2) and (2,2).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.configs.base import get_config
from repro.launch.mesh import make_decode_mesh, parse_mesh_arg
from repro.models.transformer import init_params, paged_shard_reason
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker,
                                  bucket_width, plan_width_buckets)
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# mesh arg / gating


def test_parse_mesh_arg():
    assert parse_mesh_arg("2x2") == (2, 2)
    assert parse_mesh_arg("4x1") == (4, 1)
    with pytest.raises(ValueError):
        parse_mesh_arg("2")
    with pytest.raises(ValueError):
        parse_mesh_arg("0x2")


def test_shard_reason_gates_padded_heads(setup):
    """The reduced smollm arch pads query heads (16 query / 5 effective
    over 2 kv heads) — its explicit qh2kv map cannot head-stripe, so
    model-parallel sharding must be refused with a reason; grouped GQA
    (16 heads / 4 kv) shards cleanly. Data-only sharding is always open
    to paged archs."""
    cfg, _ = setup
    assert paged_shard_reason(cfg, 2) != ""
    assert paged_shard_reason(cfg, 1, 2) == ""
    grouped = dataclasses.replace(cfg, n_heads=16, n_kv_heads=4)
    assert paged_shard_reason(grouped, 2) == ""
    assert paged_shard_reason(grouped, 2, 2) == ""


def test_worker_mesh_gates(setup):
    """API contract: a meshed worker must reject a pool on a different
    mesh, non-divisible batches, unshardable archs, and width buckets
    (bucketed sub-batches would need per-bucket bank splits)."""
    cfg, params = setup
    mesh = make_decode_mesh(1, 1)
    pp_plain = DevicePagePool(cfg, n_pages=32, page_tokens=64)
    with pytest.raises(ValueError, match="mesh"):
        DecodeWorker(params, cfg, max_batch=2, max_len=256,
                     substrate="paged", page_pool=pp_plain, mesh=mesh)
    pp_mesh = DevicePagePool(cfg, n_pages=32, page_tokens=64, mesh=mesh)
    with pytest.raises(ValueError, match="width_buckets"):
        DecodeWorker(params, cfg, max_batch=2, max_len=256,
                     substrate="paged", page_pool=pp_mesh, mesh=mesh,
                     width_buckets=2)


# ---------------------------------------------------------------------------
# width buckets (satellite: per-slot page-count padding)


def test_plan_width_buckets_single_is_global_pow2():
    """One bucket must reproduce the historical padding exactly: the
    deepest slot's need rounded up to a power of two."""
    assert plan_width_buckets([3, 9, 2], 16) == [16]
    assert plan_width_buckets([1, 1], 16) == [1]
    assert plan_width_buckets([5], 16) == [8]
    assert plan_width_buckets([], 16) == [1]


def test_plan_width_buckets_multi():
    plan = plan_width_buckets([1, 2, 9, 3], 16, max_buckets=3)
    assert plan == [16, 4, 2]
    # shallower-than-plan slots merge upward into the smallest kept width
    assert bucket_width(1, plan, 16) == 2
    assert bucket_width(3, plan, 16) == 4
    assert bucket_width(9, plan, 16) == 16
    # widths are capped at max_pages even when need overflows
    assert plan_width_buckets([30], 16) == [16]
    assert bucket_width(30, [16], 16) == 16
    # more buckets than distinct widths: plan just lists them all
    assert plan_width_buckets([8, 2], 16, max_buckets=3) == [8, 2]


def test_bucketed_decode_bit_exact(setup):
    """width_buckets=2 over a depth-skewed batch must emit exactly the
    single-bucket stream — bucketing only changes padding, never math —
    while actually splitting steps into >1 jitted sub-batches."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompts = {0: rng.integers(0, cfg.vocab_size, 600),   # 10 pages
               1: rng.integers(0, cfg.vocab_size, 70),    # 2 pages
               2: rng.integers(0, cfg.vocab_size, 40)}    # 1 page

    def run(width_buckets):
        pp = DevicePagePool(cfg, n_pages=1 + 4 * 16, page_tokens=64)
        pool = HostKVPool()
        pw = PrefillWorker(params, cfg, pool, prefill_chunk=256,
                           page_pool=pp)
        dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                          substrate="paged", page_pool=pp,
                          width_buckets=width_buckets)
        outs = {}
        for rid, toks in prompts.items():
            res = pw(toks)
            dw.join(ServingRequest(req_id=rid, tokens=toks, max_new=5), res)
            outs[rid] = [res.first_token]
        steps = 0
        while dw.n_active:
            steps += 1
            for rid, tok, _ in dw.step():
                outs[rid].append(tok)
        pp.check_leaks()
        return outs, steps, dw.stats()

    base, steps, st1 = run(1)
    got, _, st2 = run(2)
    assert got == base
    assert st1["bucket_substeps"] == 0
    # depth skew (10 vs 1-2 pages) guarantees two widths per step
    assert st2["bucket_substeps"] >= 2 * steps


# ---------------------------------------------------------------------------
# mesh-free split oracle


def test_split_ref_matches_ref_bitwise():
    """The (n_data, n_model) split-and-concat decomposition is bitwise
    the plain oracle — head-local and row-local attention make the shard
    boundaries invisible."""
    from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                                  paged_attention_split_ref)
    rng = np.random.default_rng(3)
    B, H, KV, D, P, page = 4, 8, 4, 16, 9, 8
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, page, KV, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, P, (B, 4)), jnp.int32)
    lens = jnp.asarray([30, 17, 8, 25], jnp.int32)
    ref = paged_attention_ref(q, kp, vp, tbl, lens)
    for nd, nm in [(1, 1), (2, 1), (1, 2), (2, 2), (4, 4), (2, 4)]:
        got = paged_attention_split_ref(q, kp, vp, tbl, lens,
                                        n_model=nm, n_data=nd)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref)), \
            (nd, nm)


# ---------------------------------------------------------------------------
# device lane: 4 virtual CPU devices


_SUB_PRELUDE = """
import dataclasses
import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.mesh import make_decode_mesh
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker,
                                  PrefillResult, stage_run)
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest
from repro.models.transformer import init_params

assert jax.device_count() == 4, jax.devices()
"""


def test_banked_pool_host_invariants():
    """Pure host-side logical ops on a 2-bank pool: per-bank free lists
    and null pages, per-bank registry/adoption, same-bank COW, per-bank
    OOM, cross-bank export/import, and mesh-wide logical pressure()."""
    run_subprocess(_SUB_PRELUDE + """
cfg = get_config("smollm-360m").reduced()
mesh = make_decode_mesh(2, 2)               # d=2 banks, KV=2 stripes over m=2
pp = DevicePagePool(cfg, n_pages=16, mesh=mesh, page_tokens=64)

# geometry: per-bank budget, global id space, one null page per bank
assert pp.n_banks == 2 and pp.bank_pages == 16 and pp.n_pages == 32
assert pp.bank_of(1) == 0 and pp.bank_of(17) == 1
assert sorted(pp._bank_free[1]) == list(range(17, 32))   # 16 is bank-1 null
assert pp.free is pp._bank_free[0] and pp.runs is pp._bank_runs[0]

# logical capacity excludes every bank's null page; occupancy is mesh-wide
press = pp.pressure()
assert press["capacity"] == 30 and press["free"] == 30

a0 = pp.alloc(3, bank=0)
blk = pp.alloc(8, bank=1)               # one full 512-token block run
assert all(pp.bank_of(p) == 0 for p in a0)
assert all(pp.bank_of(p) == 1 for p in blk)
assert pp.free_pages == 19 and pp.pressure()["pinned"] == 11

# a bank exhausts on its own budget even while the other has room
try:
    pp.alloc(13, bank=0)                # bank 0 has 12 free, bank 1 has 7
    raise SystemExit("bank-0 over-alloc must OOM")
except MemoryError:
    pass
assert pp.free_pages == 19      # failed alloc holds nothing

# registry is per bank: the same chain registers independently
import jax.numpy as jnp
L, KV, Dh = cfg.attention_layers, cfg.n_kv_heads, cfg.head_dim
rng = np.random.default_rng(0)
dt = pp.k_pages.dtype                   # slabs quantise to the pool dtype
k = np.asarray(jnp.asarray(rng.standard_normal((L, 512, KV, Dh)), dt))
v = np.asarray(jnp.asarray(rng.standard_normal((L, 512, KV, Dh)), dt))
pp.write_run(blk, k, v)
pp.register_block(77, blk)              # registry holds its own reference
assert pp.lookup_chain([77], bank=1) == 1
assert pp.lookup_chain([77], bank=0) == 0
assert pp.best_stage_bank([77]) == 1
n, got = pp.adopt_chain([77], bank=1)
assert n == 1 and got == blk
pp.release(got)
n, got = pp.adopt_chain([77], bank=0)
assert n == 0 and got == []

# COW stays inside the owning bank
pp.retain(blk[0:1])
moved = pp.make_writable(blk[0])
assert moved != blk[0] and pp.bank_of(moved) == 1
pp.release([moved])

# export releases the caller's references (the registry keeps the run
# warm); import round-trips the bytes into a chosen bank
ek, ev = pp.export_run(blk, 512)
back = pp.import_run(ek, ev, 512, bank=0)
assert all(pp.bank_of(p) == 0 for p in back)
rk, rv = pp.read_seq(back, 512)
np.testing.assert_array_equal(np.asarray(rk), k)
np.testing.assert_array_equal(np.asarray(rv), v)
pp.release(back)
pp.release(a0)
pp.unregister(77, bank=None)
pp.check_leaks()

# check_leaks catches a page filed into the wrong bank's free list
pp._bank_free[0].append(pp._bank_free[1].pop())
try:
    pp.check_leaks()
    raise SystemExit("cross-bank free page must fail check_leaks")
except AssertionError:
    pass
pp._bank_free[1].append(pp._bank_free[0].pop())
pp.check_leaks()
print("OK")
""", devices=4)


def test_mesh_shard_invariance_bit_exact():
    """End-to-end engine property on meshes (1,1), (2,1), (1,2), (2,2):
    prefill -> bank-aware join (incl. one PrefillResult fanned into two
    slots: shared partial tail, COW on first append; and cross-bank
    stage-copy joins once the preferred bank's slots fill) -> decode.
    Every stream must be bitwise the unmeshed single-device run, and the
    banked pools must come out leak-free."""
    out = run_subprocess(_SUB_PRELUDE + """
cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                          n_heads=16, n_kv_heads=4)   # grouped GQA: stripes
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(7)
common = rng.integers(0, cfg.vocab_size, 512)         # one full shared block
prompts = [np.concatenate([common,
                           rng.integers(0, cfg.vocab_size, 88 + 37 * r)])
           for r in range(3)]

def run(mesh_dm):
    mesh = make_decode_mesh(*mesh_dm) if mesh_dm else None
    pp = DevicePagePool(cfg, n_pages=64, mesh=mesh, page_tokens=64)
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                      substrate="paged", page_pool=pp)
    press = [pw(t) for t in prompts]
    outs = {}
    # multi-join first (n-best fan-out shares the partial tail -> COW),
    # while its bank still has two free slots
    for rid, pres in [(2, press[2]), (3, press[2]),
                      (0, press[0]), (1, press[1])]:
        dw.join(ServingRequest(req_id=rid, tokens=None, max_new=6), pres)
        outs[rid] = [pres.first_token]
    while dw.n_active:
        for rid, tok, _ in dw.step():
            outs[rid].append(tok)
    assert pp.stats()["cow_copies"] >= 1, pp.stats()
    pp.check_leaks()
    return outs, dw.stats()

base, _ = run(None)
assert base[3] == base[2]
for dm in [(1, 1), (2, 1), (1, 2), (2, 2)]:
    got, st = run(dm)
    assert got == base, (dm, got, base)
    print(dm, "match:", got == base, "zero_copy:", st["zero_copy_joins"])
print("OK")
""", devices=4)
    assert out.count("match: True") == 4, out


def test_mesh_preempt_restore_bit_exact():
    """Preemption on a (2,2) mesh: a victim's export leaves the banked
    pool, and BOTH restore arms — reload (stage the spilled bytes) and
    recompute (re-prefill prompt + emitted prefix) — resume the stream
    bitwise against the unmeshed never-preempted oracle."""
    out = run_subprocess(_SUB_PRELUDE + """
cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                          n_heads=16, n_kv_heads=4)
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(9)
toks = rng.integers(0, cfg.vocab_size, 600)
max_new = 8

def mk(mesh):
    pp = DevicePagePool(cfg, n_pages=64, mesh=mesh, page_tokens=64)
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=2, max_len=1024,
                      substrate="paged", page_pool=pp)
    return pp, pw, dw

# unmeshed never-preempted oracle
pp, pw, dw = mk(None)
res = pw(toks)
dw.join(ServingRequest(req_id=0, tokens=toks, max_new=max_new), res)
oracle = [res.first_token]
while dw.n_active:
    for _, tok, _ in dw.step():
        oracle.append(tok)
pp.check_leaks()

for arm in ("reload", "recompute"):
    pp, pw, dw = mk(make_decode_mesh(2, 2))
    res = pw(toks)
    slot = dw.join(ServingRequest(req_id=0, tokens=toks, max_new=max_new),
                   res)
    emitted = [res.first_token]
    for _ in range(3):
        for _, tok, _ in dw.step():
            emitted.append(tok)
    run = dw.preempt(slot)
    assert dw.n_active == 0
    assert run.n_tokens == len(toks) + len(run.emitted) - 1
    if arm == "reload":
        ids = pw.hasher.hash_ids(np.concatenate(
            [toks, np.asarray(run.emitted[:-1], toks.dtype)]))
        pages = stage_run(pp, ids, run.k, run.v, run.n_tokens)
        assert pages is not None
        banks = {pp.bank_of(p) for p in pages if p}
        assert len(banks) == 1, banks          # a run lives in ONE bank
        pres = PrefillResult(
            first_token=run.emitted[-1], kv_k=run.k, kv_v=run.v,
            prompt_len=run.n_tokens, reused_blocks=0, new_blocks=0,
            hash_ids=ids, pages=pages, page_pool=pp,
            page_gens=pp.gens_of(pages))
    else:
        pres = pw(np.concatenate(
            [toks, np.asarray(run.emitted[:-1], toks.dtype)]))
    dw.join(run.request, pres, resume_emitted=run.emitted)
    while dw.n_active:
        for _, tok, _ in dw.step():
            emitted.append(tok)
    assert emitted == oracle, (arm, emitted, oracle)
    pp.check_leaks()
    print(arm, "match:", emitted == oracle)
print("OK")
""", devices=4)
    assert out.count("match: True") == 2, out


def test_mesh_device_staged_run_lands_in_its_bank():
    """On a (2,2) mesh, a prefill staged straight from its device dense
    cache lands in ONE bank, the one with the deepest registered prefix
    (else the one with the most free pages), holds the bytes a host-side
    prefill computes, and leaves both slabs on their mesh sharding; the
    one-pass scatter form of the page write stores the same bytes."""
    run_subprocess(_SUB_PRELUDE + """
from repro.serving.paged_cache import page_write
cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                          n_heads=16, n_kv_heads=4)
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(15)
warm, cold = (rng.integers(0, cfg.vocab_size, n) for n in (700, 600))
oracle = PrefillWorker(params, cfg, HostKVPool(), prefill_chunk=256)
want = {id(t): oracle(t) for t in (warm, cold)}

pp = DevicePagePool(cfg, n_pages=32, mesh=make_decode_mesh(2, 2),
                    page_tokens=64)
# bank 1 holds the warm prompt's first block: its run goes there
rh = want[id(warm)]
blk = pp.alloc(pp.pages_per_block, bank=1)
pp.write_run(blk, rh.kv_k[:, :512], rh.kv_v[:, :512])
pp.register_block(rh.hash_ids[0], blk)
pp.release(blk)
pw = PrefillWorker(params, cfg, HostKVPool(), prefill_chunk=256,
                   page_pool=pp)
for t, bank in ((warm, 1), (cold, 0)):
    r = pw(t)
    assert r.kv_k is None and r.pages, r
    assert {pp.bank_of(p) for p in r.pages} == {bank}, (bank, r.pages)
    for got, ref in zip(r.host_kv(), want[id(t)].host_kv()):
        np.testing.assert_array_equal(got, ref)
    assert pp.k_pages.sharding == pp._sharding
    assert pp.v_pages.sharding == pp._sharding
    r.release_pages()
st = pw.stats()
assert (st["device_stages"], st["host_stages"]) == (2, 0), st

# the two forms of the write agree on the mesh
idx = np.asarray([3, 20, 9], np.int32)
k, v = rh.kv_k[:, :150], rh.kv_v[:, :150]
out = []
for per_page in (True, False):
    w = page_write(64, per_page=per_page, sharding=pp._sharding)
    kp, vp = w(pp.k_pages.copy(), pp.v_pages.copy(), idx, k, v)
    assert kp.sharding == pp._sharding
    out.append((np.asarray(kp), np.asarray(vp)))
for a, b in zip(*out):
    np.testing.assert_array_equal(a, b)
pp.check_leaks()
print("OK")
""", devices=4)

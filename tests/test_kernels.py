"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # sandboxed env: vendored shim (seeded random)
    from _hypothesis_shim import given, settings, strategies as st

from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.flash_prefill.ref import flash_prefill_ref
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_naive_ref, ssd_scan_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- flash ----
FLASH_CASES = [
    # B, Sq, Sk, H, KV, D, offset, window
    (2, 128, 256, 4, 2, 64, 128, 0),
    (1, 256, 256, 8, 8, 128, 0, 0),      # MHA
    (2, 128, 512, 4, 1, 32, 384, 128),   # MQA + sliding window + offset
    (1, 128, 128, 16, 4, 128, 0, 0),     # GQA 4:1
    (1, 64, 192, 2, 2, 64, 128, 0),      # small blocks
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_prefill_matches_ref(case, dtype):
    B, Sq, Sk, H, KV, D, off, win = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, D), dtype)
    blk = lambda s: next(b for b in (128, 64, 32, 16) if s % b == 0)
    out = flash_prefill(q, k, v, q_offset=off, window=win,
                        bq=blk(Sq), bk=blk(Sk), interpret=True)
    ref = flash_prefill_ref(q, k, v, q_offset=off, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_prefill_is_causal():
    """Output at position i must not depend on keys at positions > i."""
    B, S, H, D = 1, 128, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    out1 = flash_prefill(q, k, v, interpret=True, bq=64, bk=64)
    k2 = k.at[:, 100:].set(99.0)     # corrupt the future
    v2 = v.at[:, 100:].set(-99.0)
    out2 = flash_prefill(q, k2, v2, interpret=True, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(out1[:, :100]),
                               np.asarray(out2[:, :100]), atol=1e-5)
    assert not np.allclose(np.asarray(out1[:, 101:]),
                           np.asarray(out2[:, 101:]))


# ---------------------------------------------------------------- paged ----
PAGED_CASES = [
    # B, H, KV, D, P, page, max_pages
    (4, 8, 2, 64, 32, 64, 4),
    (2, 4, 4, 128, 16, 128, 2),
    (3, 15, 5, 32, 64, 64, 8),      # smollm-style GQA 3:1
    (1, 2, 1, 128, 8, 64, 3),       # MQA
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_attention_matches_ref(case):
    B, H, KV, D, P, page, mp = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P, page, KV, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (P, page, KV, D), jnp.bfloat16)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(1, P, (B, mp)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, mp * page + 1, (B,)), jnp.int32)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_paged_attention_respects_seq_lens():
    """Tokens past seq_len must not contribute."""
    B, H, KV, D, P, page, mp = 1, 2, 2, 64, 8, 64, 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, page, KV, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, page, KV, D), jnp.float32)
    table = jnp.asarray([[1, 2]], jnp.int32)
    out1 = paged_attention(q, kp, vp, table,
                           jnp.asarray([70], jnp.int32), interpret=True)
    kp2 = kp.at[2, 10:].set(50.0)    # corrupt beyond token 70 (page 2 at 64+)
    vp2 = vp.at[2, 10:].set(-50.0)
    out2 = paged_attention(q, kp2, vp2, table,
                           jnp.asarray([70], jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


# ------------------------------------------------------------------ ssd ----
SSD_CASES = [
    # b, s, h, p, n, chunk, with_h0
    (2, 128, 4, 32, 64, 32, False),
    (1, 256, 2, 64, 128, 64, True),
    (2, 64, 8, 16, 32, 64, False),
    (1, 64, 1, 8, 16, 16, True),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_refs(case):
    b, s, h, p, n, chunk, with_h0 = case
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n), jnp.bfloat16)
    C = jax.random.normal(ks[4], (b, s, n), jnp.bfloat16)
    h0 = jax.random.normal(ks[5], (b, h, p, n), jnp.float32) if with_h0 else None
    y_k, hT_k = ssd_scan(x, dt, A, B, C, h0, chunk=chunk, interpret=True)
    y_r, hT_r = ssd_scan_ref(x, dt, A, B, C, chunk=chunk, h0=h0)
    y_n, hT_n = ssd_naive_ref(x, dt, A, B, C, h0=h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=0.1, rtol=0.1)
    np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_r),
                               atol=0.1, rtol=0.1)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_n),
                               atol=0.1, rtol=0.1)


@given(st.integers(1, 3), st.sampled_from([32, 64]), st.integers(1, 4),
       st.sampled_from([8, 16]), st.sampled_from([16, 32]))
@settings(max_examples=10, deadline=None)
def test_ssd_scan_property_sweep(b, s, h, p, n):
    """Kernel ≡ naive recurrence across random small shapes."""
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + s + h), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    y_k, hT_k = ssd_scan(x, dt, A, B, C, chunk=min(32, s), interpret=True)
    y_n, hT_n = ssd_naive_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_n),
                               atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_n),
                               atol=5e-3, rtol=5e-3)


def test_model_mamba_block_consistency():
    """The model's ssd_chunked (used by mamba2/jamba) agrees with the
    kernel across a chunk-boundary continuation."""
    b, s, h, p, n, chunk = 1, 64, 2, 16, 32, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    # full scan vs two halves with state carry
    y_full, hT = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y1, h1 = ssd_scan(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                      chunk=chunk, interpret=True)
    y2, h2 = ssd_scan(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:],
                      h1, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hT),
                               atol=5e-3, rtol=5e-3)


def test_model_prefill_via_pallas_matches_default(tmp_path):
    """End-to-end: the model's prefill with the Pallas flash kernel routed
    in (REPRO_USE_PALLAS=interpret, the Pallas interpreter) equals the jnp
    path."""
    import subprocess, sys, os
    code = '''
import os, sys
os.environ["REPRO_USE_PALLAS"] = sys.argv[1]
import jax, numpy as np
from repro.configs.base import get_config
from repro.models.transformer import init_params, prefill
cfg = get_config("qwen2.5-3b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab_size)
logits, _ = jax.jit(lambda p, t: prefill(p, t, cfg))(params, tokens)
np.save(sys.argv[2], np.asarray(logits, np.float32))
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = {flag: str(tmp_path / f"pallas_model_{flag}.npy")
           for flag in ("0", "interpret")}
    for flag, path in out.items():
        subprocess.run([sys.executable, "-c", code, flag, path], env=env,
                       check=True, timeout=600)
    a = np.load(out["0"])
    b = np.load(out["interpret"])
    np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)
    assert int(a[0].argmax()) == int(b[0].argmax())

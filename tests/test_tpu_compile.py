"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (``test_kernels.py``, ``test_paged_decode.py``) checks the
kernels' arithmetic but not their tiling: Mosaic refuses block shapes
whose last two dims are neither (8, 128)-aligned nor the array's own,
which the interpreter never sees. These tests compile each kernel for a
described (not attached) ``v5e:2x2`` chip with the TPU compiler that is
installed alongside jax, and check the program really holds the kernel.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  paged_attention_layers)
from repro.serving.paged_cache import page_write

# (H, KV, D): qwen2.5-3b's grouped heads, and smollm-360m's unpadded ones
WIDTHS = {"qwen2.5-3b": (16, 2, 128), "smollm-360m": (15, 5, 64)}
B, PAGE, N_PAGES, MAX_PAGES, L = 8, 64, 289, 32, 36
SQ, SK = 256, 1024          # one prefill chunk over its 1k-token prefix
# (L, pages, KV, D) of the benchmark's page slabs
SLABS = {"qwen2.5-3b": (36, 1153, 2, 128), "phi3-mini": (32, 113, 32, 96)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_attention_compiles(arch, one_chip):
    H, KV, D = WIDTHS[arch]
    text = _compiled_text(paged_attention, [
        ((B, H, D), jnp.bfloat16),
        ((N_PAGES, PAGE, KV, D), jnp.bfloat16),
        ((N_PAGES, PAGE, KV, D), jnp.bfloat16),
        ((B, MAX_PAGES), jnp.int32), ((B,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_attention_layers_compiles(arch, one_chip):
    H, KV, D = WIDTHS[arch]
    text = _compiled_text(paged_attention_layers, [
        ((L, B, H, D), jnp.bfloat16),
        ((L, N_PAGES, PAGE, KV, D), jnp.bfloat16),
        ((L, N_PAGES, PAGE, KV, D), jnp.bfloat16),
        ((B, MAX_PAGES), jnp.int32), ((B,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_flash_prefill_compiles(arch, one_chip):
    H, KV, D = WIDTHS[arch]
    fn = jax.jit(lambda q, k, v: flash_prefill(q, k, v, q_offset=SK - SQ))
    text = _compiled_text(fn, [
        ((1, SQ, H, D), jnp.bfloat16),
        ((1, SK, KV, D), jnp.bfloat16),
        ((1, SK, KV, D), jnp.bfloat16)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", sorted(SLABS))
def test_page_write_compiles_in_place(arch, one_chip):
    """The page write takes the form ``DevicePagePool`` picks from the
    slab's device layout (phi3-mini's puts the page axis minor-most), and
    compiles with both slabs aliased to its outputs; per page, at qwen's
    widths, with no slab-sized temporary either."""
    L, P, KV, D = SLABS[arch]
    slab = (L, P, PAGE, KV, D)
    zeros = jax.jit(lambda: jnp.zeros(slab, jnp.bfloat16),
                    out_shardings=one_chip).lower().compile()
    pages_minor = zeros.output_formats.layout.major_to_minor[-1] == 1
    assert pages_minor == (arch == "phi3-mini")
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        (slab, jnp.bfloat16), (slab, jnp.bfloat16), ((8,), jnp.int32),
        ((L, 512, KV, D), jnp.bfloat16), ((L, 512, KV, D), jnp.bfloat16)]]
    compiled = page_write(PAGE, per_page=not pages_minor).lower(
        *args).compile()
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
        "{1}: (1, {}, may-alias) }" in compiled.as_text()
    if not pages_minor:
        slab_bytes = L * P * PAGE * KV * D * 2
        assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes / 100


def _moonlight():
    """Moonlight-16B-A3B at its published widths, as one chip's eighth of
    an EP8 deployment: experts 0-7 of 64 held in each expert layer."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    return ModelConfig(
        name="moonlight-16b-a3b", kind="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=11264, vocab_size=163840,
        rope_theta=5e4, norm_eps=1e-5, remat=False,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                      routed_scale=2.446, d_ff_shared=2816, first_dense=1,
                      n_held=8))


def test_latent_expert_step_and_chunk_compile(one_chip):
    """Moonlight's paged decode step (8 slots, the benchmark's 1,153-page
    latent slabs, a 128-page table) and one 256-token prefill chunk over
    an 8k latent arena compile for one v5e chip; the step writes both
    slabs in place and makes no copy of them (its temporaries, 0.55 GB,
    stay under a third of the 2.3 GB pair)."""
    from repro.models.transformer import (Caches, KVCache, decode_step,
                                          decode_step_paged, init_params)
    cfg = _moonlight()

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    (wk,), (wv,) = cfg.kv_widths
    slabs = [spec((27, 1153, PAGE, wk)), spec((27, 1153, PAGE, wv))]
    step = jax.jit(lambda p, t, kp, vp, tbl, lens: decode_step_paged(
        p, t, kp, vp, tbl, lens, cfg), donate_argnums=(2, 3))
    compiled = step.lower(params, spec((8, 1), jnp.int32), *slabs,
                          spec((8, 128), jnp.int32),
                          spec((8,), jnp.int32)).compile()
    n = len(jax.tree.leaves(params))
    assert f"input_output_alias={{ {{1}}: ({n + 1}, {{}}, may-alias), " \
        f"{{2}}: ({n + 2}, {{}}, may-alias) }}" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.alias_size_in_bytes / 3
    chunk = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg,
                                                held_pairs=True))
    arena = Caches(kv=KVCache(spec((27, 1, 8192, wk)),
                              spec((27, 1, 8192, wv))),
                   ssm=None, enc_kv=None, length=spec((), jnp.int32))
    chunk.lower(params, spec((1, 256), jnp.int32), arena).compile()

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
import math
import re

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
# (L, pages, KV, D) of the benchmark's page slabs, stored lane-dense as
# (L, pages, PAGE, KV·D)
SLABS = {"qwen2.5-3b": (36, 1153, 2, 128), "phi3-mini": (32, 113, 32, 96)}
# (slots, block-table width) of the benchmark's decode steps at their
# deepest: 8 slots to 8,192 tokens, 6 to 1,024
STEPS = {"qwen2.5-3b": (8, 128), "phi3-mini": (6, 16)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        # and the compiler's first warning opens a log file there mid-test
        mp.setenv("TPU_MIN_LOG_LEVEL", "3")
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


def _slab_moves(text: str, slabs, whole: bool = True) -> list[str]:
    """The compiled program's copies, dynamic slices and dynamic updates
    that hold as many elements as one layer of one of ``slabs`` (stacked
    shapes), or with ``whole`` the slab itself, in whatever layout or
    rank: each is a pass over a layer slab or a slab that an in-place
    step does not make."""
    sizes = {math.prod(s[1:]) for s in slabs}
    if whole:
        sizes |= {math.prod(s) for s in slabs}
    op = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} "
                    r"(copy|dynamic-slice|dynamic-update-slice)\(")
    found = []
    for line in text.splitlines():
        m = op.search(line)
        if m and math.prod(int(d) for d in m.group(1).split(",")
                           if d) in sizes:
            found.append(line.strip()[:160])
    return found


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
    """The pool's lane-dense slab puts the page axis major at both
    benchmark widths (phi3-mini's 5-D slab put it minor-most), so the
    page write ``DevicePagePool`` picks from that layout is the per-page
    form; given a block of lane-dense rows, as ``write_run`` hands it,
    it compiles with both slabs aliased to its outputs and temporaries
    under 1% of a slab."""
    L, P, KV, D = SLABS[arch]
    slab = (L, P, PAGE, KV * D)
    zeros = jax.jit(lambda: jnp.zeros(slab, jnp.bfloat16),
                    out_shardings=one_chip).lower().compile()
    pages_minor = zeros.output_formats.layout.major_to_minor[-1] == 1
    assert not pages_minor
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        (slab, jnp.bfloat16), (slab, jnp.bfloat16), ((8,), jnp.int32),
        ((L, 512, KV * D), jnp.bfloat16), ((L, 512, KV * D), jnp.bfloat16)]]
    compiled = page_write(PAGE, per_page=True).lower(*args).compile()
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
        "{1}: (1, {}, may-alias) }" in compiled.as_text()
    slab_bytes = math.prod(slab) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes / 100


def _dense(arch):
    """The benchmark's dense configurations at their published widths."""
    from repro.configs.base import ModelConfig
    if arch == "qwen2.5-3b":
        return ModelConfig(
            name=arch, kind="dense", n_layers=36, d_model=2048, n_heads=16,
            n_kv_heads=2, head_dim=128, d_ff=11008, vocab_size=151936,
            attn_bias=True, rope_theta=1e6, norm_eps=1e-6,
            tie_embeddings=False, remat=False)
    return ModelConfig(
        name=arch, kind="dense", n_layers=32, d_model=3072, n_heads=32,
        n_kv_heads=32, head_dim=96, d_ff=8192, vocab_size=32064,
        rope_theta=1e4, norm_eps=1e-5, tie_embeddings=False, remat=False)


def _params_spec(cfg, sharding):
    from repro.models.transformer import init_params
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("arch", sorted(SLABS))
def test_paged_step_writes_rows_in_place(arch, one_chip):
    """The engine's donated paged decode step, at the benchmark's slabs,
    slots and deepest table, aliases both slabs to its outputs, holds
    temporaries under 2% of the pair, and makes no copy, dynamic slice
    or dynamic update of a slab or a layer slab: each layer writes its B
    rows and gathers its table's pages in place."""
    from repro.models.transformer import decode_step_paged
    cfg = _dense(arch)
    L, P, KV, D = SLABS[arch]
    B, width = STEPS[arch]
    slab = (L, P, PAGE, KV * D)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _params_spec(cfg, one_chip)
    step = jax.jit(lambda p, t, kp, vp, tbl, lens: decode_step_paged(
        p, t, kp, vp, tbl, lens, cfg), donate_argnums=(2, 3))
    compiled = step.lower(params, spec((B, 1), jnp.int32), spec(slab),
                          spec(slab), spec((B, width), jnp.int32),
                          spec((B,), jnp.int32)).compile()
    text = compiled.as_text()
    n = len(jax.tree.leaves(params))
    assert f"input_output_alias={{ {{1}}: ({n + 1}, {{}}, may-alias), " \
        f"{{2}}: ({n + 2}, {{}}, may-alias) }}" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.02 * 2 * math.prod(slab) * 2
    assert _slab_moves(text, [slab]) == []


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
    stay under a third of the 2.3 GB pair), nor a copy, dynamic slice or
    dynamic update of a slab or a layer slab."""
    from repro.models.transformer import Caches, KVCache, decode_step
    from repro.models.transformer import decode_step_paged
    cfg = _moonlight()

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _params_spec(cfg, one_chip)
    (wk,), (wv,) = cfg.kv_widths
    shapes = [(27, 1153, PAGE, wk), (27, 1153, PAGE, wv)]
    slabs = [spec(s) for s in shapes]
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
    assert _slab_moves(compiled.as_text(), shapes, whole=False) == []
    chunk = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg,
                                                held_pairs=True))
    arena = Caches(kv=KVCache(spec((27, 1, 8192, wk)),
                              spec((27, 1, 8192, wv))),
                   ssm=None, enc_kv=None, length=spec((), jnp.int32))
    chunk.lower(params, spec((1, 256), jnp.int32), arena).compile()

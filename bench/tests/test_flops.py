"""Operations and bytes from shapes, against counts made by hand."""
from bench import flops
from bench.model import load_config


def test_qwen_counts():
    dense, s = load_config("qwen2_5_3b")
    # q,k,v: 2048 x (16 + 2*2) x 128; o: 16*128 x 2048; SwiGLU 3 x 2048 x 11008
    assert dense.layer_weight_elems(s) == 5_242_880 + 4_194_304 + 67_633_152
    assert s.kv_bytes_per_token == 36 * 1024          # 36 KiB per token
    # 36 layers + a 2048 x 151936 head, bf16
    assert dense.weight_bytes(s) == 2 * (36 * 77_070_336 + 311_164_928)
    f, b = dense.decode_step(s, [100])
    # 2 flops per weight, and QK^T + PV over 101 positions in 36 layers
    assert f == 6_171_394_048 + 4 * 36 * 16 * 128 * 101
    # weights + 100 cached tokens read + 1 written + the token's row in/out
    assert b == 6_171_394_048 + 100 * 36_864 + 36_864 + 2 * 2 * 2048
    f, b = dense.prefill_chunk(s, 256, 512, last=True)
    # 256 queries see 256 cached positions and a causal triangle of 256
    visible = 256 * 256 + 256 * 257 // 2
    assert f == (2 * 256 * 36 * 77_070_336 + 4 * 36 * 16 * 128 * visible
                 + 2 * 2048 * 151_936)
    assert b == (2 * 36 * 77_070_336 + 2 * 256 * 36_864 + 4 * 256 * 2048
                 + 2 * 2048 * 151_936)


def test_phi3_counts():
    dense, s = load_config("phi3_mini_4k")
    # q,k,v: 3072 x (32 + 2*32) x 96; o: 32*96 x 3072; SwiGLU 3 x 3072 x 8192
    assert dense.layer_weight_elems(s) == 28_311_552 + 9_437_184 + 75_497_472
    assert s.kv_bytes_per_token == 384 * 1024         # 384 KiB per token
    f, b = dense.decode_step(s, [640, 699])
    assert f == 2 * 2 * (32 * 113_246_208 + 3072 * 32_064) \
        + 4 * 32 * 32 * 96 * (641 + 700)
    assert b == 2 * (32 * 113_246_208 + 3072 * 32_064) \
        + (640 + 699) * 393_216 + 2 * 393_216 + 2 * 2 * 2 * 3072


def test_roofline_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000, 10, peak) == 10.0     # compute bound
    assert flops.least_seconds(10, 1000, peak) == 100.0    # memory bound

"""FLOPs and bytes of both configurations against numbers worked by hand."""
import flops
import model as bmodel


def test_matmul_params_by_hand():
    s = bmodel.load_config("stablelm-1.6b")
    # per layer: q, k, v, o 4 x 2048^2 = 16,777,216; MLP 3 x 2048 x 5632 =
    # 34,603,008; 24 layers; head 2048 x 100,352
    assert flops.layer_matmul_params(s) == 16_777_216 + 34_603_008
    assert flops.matmul_params(s) == 24 * 51_380_224 + 205_520_896
    assert flops.matmul_params(s) == 1_438_646_272
    q = bmodel.load_config("qwen3-8b-l6")
    # q 4096^2, k and v 4096 x 1024 (8 kv heads x 128), o 4096^2, MLP
    # 3 x 4096 x 12288; 6 layers; head 4096 x 151,936 (the real vocabulary)
    per_layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 150_994_944
    assert flops.layer_matmul_params(q) == per_layer == 192_937_984
    assert flops.matmul_params(q) == 6 * per_layer + 622_329_856


def test_train_step_flops_by_hand():
    s = bmodel.load_config("stablelm-1.6b")
    got = flops.train_step_flops(s, sequences=8, seq=256, rank=64,
                                 targets=["q", "v"])
    tokens = 8 * 256
    base = 4 * 1_438_646_272 * tokens            # forward + activation grads
    attn = 3 * 4 * 32 * 64 * (256 * 257 // 2) * 24 * 8   # causal, fwd + bwd
    lora = 3 * 2 * (64 * (2048 + 2048) * 2) * 24 * tokens
    assert got == base + attn + lora == 12_095_231_885_312


def test_decode_step_work_by_hand():
    q = bmodel.load_config("qwen3-8b-l6")
    f, b = flops.decode_step_work(q, positions=[255], tenants=1, rank=64,
                                  targets=["q", "v"])
    params = 1_779_957_760
    # adapters per layer: q A 64x4096 + B 4096x64, v A 64x4096 + B 1024x64
    lora = (64 * (4096 + 4096) + 64 * (4096 + 1024)) * 6
    assert f == 2 * params + 4 * 32 * 128 * 256 * 6 + 2 * lora
    # 2-byte operands: weights, 256 keys and values of 8 x 128, adapters
    assert b == 2 * params + 2 * 8 * 128 * 2 * 6 * 256 + 2 * lora
    t, bound = flops.least_time_s(f, b, {"flops_bf16": 197e12,
                                         "hbm_bw": 819e9})
    assert bound == "hbm" and abs(t - b / 819e9) < 1e-15

"""FLOPs, bytes, MFU and roofline arithmetic against numbers worked by
hand for GPT-2 124M as this repository runs it (d 768, 12 layers, 12
heads x 64, swiglu width 2048, vocab 50304)."""

import pytest

from benchmark.harness import common
from benchmark.reduce import costs

CFG = common.load_json(common.BENCH_DIR + "/configs/gpt2-124m.json")
LARGE = common.load_json(common.BENCH_DIR + "/configs/gpt2-large.json")


def test_dims():
    assert costs.model_dims(CFG) == {"d": 768, "L": 12, "H": 12, "hd": 64,
                                     "f": 2048, "ffn_matrices": 3,
                                     "V": 50304}
    assert costs.model_dims(LARGE)["f"] == 3328
    assert costs.model_dims(LARGE)["hd"] == 64


def test_ffn_as_written_is_the_programs():
    # the files state the FFN as run; the program's GPTConfig decides it
    from ray_tpu.models.gpt import GPTConfig
    for conf in (CFG, LARGE):
        cfg = getattr(GPTConfig, conf["model"]["preset"])()
        assert conf["model"]["ffn"]["width"] == cfg.ff_dim
        assert (cfg.d_model, cfg.n_layers, cfg.n_heads) == (
            conf["n_embd"], conf["n_layer"], conf["n_head"])


def test_train_flops_per_token_by_hand():
    # per layer, forward: qkv 3*2*768*768 = 3,538,944; scores+values
    # causal 2*1024*768 = 1,572,864; out 2*768*768 = 1,179,648; swiglu
    # 3*2*768*2048 = 9,437,184  -> 15,728,640
    # head 2*768*50304 = 77,266,944; forward = 12*15,728,640 + head
    fwd = 12 * 15_728_640 + 77_266_944
    assert fwd == 266_010_624
    assert costs.train_flops_per_token(CFG, 1024) == 3 * fwd


def test_flops_agree_with_the_programs_count():
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.telemetry import flops
    cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024, dtype=jnp.bfloat16,
                         ce_chunk=-1)
    assert costs.train_flops_per_token(CFG, 1024) == \
        flops.gpt_train_flops_per_token(cfg, 1024, ce_recompute=False)


def test_mfu_by_hand():
    # 130,000 tokens/s/chip * 798,031,872 FLOPs/token / 197e12 = 52.66 %
    assert costs.mfu_percent(130_000, CFG, 1024, "TPU v5 lite") == \
        pytest.approx(52.6620, abs=1e-3)


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError):
        costs.chip_peaks("TPU v9 mega")
    assert costs.chip_peaks("TPU v5 lite") == {"bf16_tflops": 197.0,
                                               "hbm_gbps": 819.0}


def test_train_attention_cost_by_hand():
    c = costs.train_attention_cost(CFG, 24, 1024)
    # forward per layer: 4 * 24 * 12 * 1024^2 * 64 / 2 = 38,654,705,664
    assert c["flops"] == 12 * 38_654_705_664 * 3.5
    # one [24,1024,12,64] bf16 tensor is 37,748,736 B; 12 of them a layer
    assert c["bytes"] == 12 * 37_748_736 * 12
    # compute-bound: 1.6235e12 / 197e12 = 8.241 ms; 47.4 ms -> 17.39 %
    assert costs.roofline_percent(c, 0.0474, "TPU v5 lite") == \
        pytest.approx(17.386, abs=1e-2)


def test_decode_attention_is_bandwidth_bound():
    c = costs.decode_attention_cost(LARGE, context_tokens=30_000,
                                    sequences=50)
    kv = 30_000 * 20 * 64
    assert c["flops"] == 36 * 4 * kv
    assert c["bytes"] == 36 * (4 * kv + 4 * 50 * 20 * 64)
    least_s = c["bytes"] / 819e9
    assert c["flops"] / 197e12 < least_s
    assert costs.roofline_percent(c, least_s * 4, "TPU v5 lite") == \
        pytest.approx(25.0)

"""Operations and bytes of one decode step and one prefill, counted by
hand for each configuration."""

import json
from pathlib import Path

import pytest

from chipbench import costs, families

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def arch(name):
    return families.arch(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_parameter_counts():
    # internlm2: per layer q,o 2*2048*16*128 + k,v 2*2048*8*128
    # + SwiGLU 3*2048*8192 = 62,914,560; x24, + head and embedding
    # 2*2048*92544, + 49 norms of 2048
    a = arch("internlm2-1.8b")
    assert a.layer_matmul_params == 62_914_560
    assert a.matmul_params == 1_699_479_552
    assert a.params == 1_889_110_016
    # stablelm, 16 layers: q,o,k,v 4*2560*32*80 + 3*2560*6912 = 79,298,560
    b = arch("stablelm-3b")
    assert b.layer_matmul_params == 79_298_560
    assert b.params == 1_526_417_920


def test_decode_step_internlm2_by_hand():
    a = arch("internlm2-1.8b")
    c = a.decode_cost(batch=1, pos=0, window=0, kv_dtype="bfloat16")
    # 2 * matmul params + attention 24 layers * (qk + pv) 2*2*16*128 * 1 key
    assert c.flops == 2 * 1_699_479_552 + 24 * 4 * 16 * 128
    weights = 2 * (1_699_479_552 + 49 * 2048) + 2048 * 2
    kv = 24 * 8 * 2 * 128 * 2 * 2        # one key read, one written
    logits = 92544 * 2
    acts = 2048 * 2 * 24 * 4
    assert c.bytes == weights + kv + logits + acts
    # memory-bound: bytes at 819 GB/s take far longer than ops at 197 TF/s
    assert c.least_s({"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}) == c.bytes / 819e9


def test_decode_step_window_and_int8():
    a = arch("internlm2-1.8b")
    full = a.decode_cost(batch=8, pos=1087, window=0, kv_dtype="bfloat16")
    fast = a.decode_cost(batch=8, pos=1087, window=256, kv_dtype="int8")
    # 1088 keys at 2 bytes against 256 at 1 byte plus two fp32 scales
    per_key_full = 24 * 8 * 8 * 2 * 128 * 2
    per_key_fast = 24 * 8 * 8 * (2 * 128 + 2 * 4)
    assert full.bytes - fast.bytes == (per_key_full * 1089
                                       - per_key_fast * 257)
    assert full.flops - fast.flops == 8 * 24 * 4 * 16 * 128 * (1088 - 256)


def test_decode_step_stablelm_by_hand():
    b = arch("stablelm-3b")
    c = b.decode_cost(batch=2, pos=9, window=0, kv_dtype="bfloat16")
    matmul = 16 * 79_298_560 + 2560 * 50304
    assert c.flops == 2 * (2 * matmul + 16 * 4 * 32 * 80 * 10)
    kv = 16 * 2 * 32 * 2 * 80 * 2 * 11
    assert c.bytes == (2 * (matmul + 33 * 2560) + 2 * 2560 * 2 + kv
                       + 2 * 50304 * 2 + 2 * 2560 * 2 * 16 * 4)


def test_prefill_by_hand():
    a = arch("internlm2-1.8b")
    c = a.prefill_cost(batch=1, prompt_len=4, window=0, kv_dtype="bfloat16")
    # keys attended 1+2+3+4 = 10; logits of the last position only
    assert c.flops == (2 * 4 * 24 * 62_914_560 + 24 * 4 * 16 * 128 * 10
                       + 2 * 2048 * 92544)
    w = a.prefill_cost(batch=1, prompt_len=4, window=2, kv_dtype="int8")
    # window 2: keys 1+2+2+2 = 7
    assert c.flops - w.flops == 24 * 4 * 16 * 128 * 3


def test_served_flops_counts_accurate_prefill_and_served_steps():
    a = arch("internlm2-1.8b")
    f = costs.served_flops(a, batch=8, prompt_len=16,
                           positions=[(16, 0), (17, 256)])
    assert f == (a.prefill_cost(batch=8, prompt_len=16, window=0,
                                kv_dtype="bfloat16").flops
                 + a.decode_cost(batch=8, pos=16, window=0,
                                 kv_dtype="bfloat16").flops
                 + a.decode_cost(batch=8, pos=17, window=256,
                                 kv_dtype="bfloat16").flops)


def test_peaks_by_device_kind():
    assert costs.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.load_peaks("cpu")

"""FLOP and byte functions against hand counts at the published widths,
and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "bench")))

import flops as FL  # noqa: E402
import spec  # noqa: E402

YI = spec.load_config("yi6b")["model"]
MC = spec.load_config("minicpm2b")["model"]


def test_yi6b_hand_counts():
    # q 4096x4096, k and v 4096x512, o 4096x4096, three 4096x11008.
    layer = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    assert FL.layer_matmul_params(YI) == layer == 173015040
    assert FL.head_flops(YI) == 2 * 4096 * 64000
    # 32 layers x (k, v) x 4 kv heads x 128 x 2 bytes.
    assert FL.kv_bytes_per_token(YI) == 65536
    # Embedding, head, final norm, and per layer its matmuls and 2 norms;
    # the program's abstract parameters hold the same 12122071040 bytes.
    assert FL.weight_bytes(YI) == 2 * (2 * 64000 * 4096 + 4096
                                       + 32 * (layer + 2 * 4096))
    assert FL.weight_bytes(YI) == 12122071040
    # Decode at position 99: matmuls, attention over 100 keys, the head.
    assert FL.decode_flops(YI, 99) == (2 * 32 * layer + 4 * 32 * 32 * 128
                                       * 100 + 2 * 4096 * 64000)
    # A 3-token prefill after 2 cached ones: contexts 3, 4 and 5.
    assert FL.prefill_flops(YI, 2, 5) == (3 * 2 * 32 * layer + 4 * 32 * 32
                                          * 128 * 12 + 2 * 4096 * 64000)
    assert FL.decode_bytes(YI, 1000) == (FL.weight_bytes(YI)
                                         - 2 * 64000 * 4096 + 1000 * 65536)


def test_minicpm2b_hand_counts():
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert FL.layer_matmul_params(MC) == layer == 61046784
    # 40 layers x (k, v) x 36 kv heads x 64 x 2 bytes.
    assert FL.kv_bytes_per_token(MC) == 368640
    # Tied: the embedding table is also the head, counted once.
    assert FL.weight_bytes(MC) == 2 * (122753 * 2304 + 2304
                                       + 40 * (layer + 2 * 2304))
    assert FL.weight_bytes(MC) == 5449761792
    assert FL.decode_bytes(MC, 0) == FL.weight_bytes(MC)
    assert FL.decode_flops(MC, 0) == (2 * 40 * layer + 4 * 40 * 36 * 64
                                      + 2 * 2304 * 122753)


def test_peak_table():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")

"""The FLOPs and bytes functions against hand counts at a tiny shape, and
the roofs that bind at the real ones."""

import json
import os

import pytest

from benchmarks import work
from benchmarks.common import ROOT
from benchmarks.peaks import DEVICE_PEAKS, peaks_for

TINY = {"n_layer": 2, "n_embd": 8, "n_inner": 32, "vocab_size": 10, "n_head": 2}


def test_matmul_flops_by_hand():
    # per layer: q, k, v, o = 4 x 8 x 8 = 256 MACs; FFN 2 x 8 x 32 = 512
    # -> 768 MACs = 1536 FLOPs; two layers 3072; head 8 x 10 MACs = 160 FLOPs
    assert work.matmul_flops_per_token(TINY, head=False) == 3072
    assert work.matmul_flops_per_token(TINY) == 3072 + 160


def test_attention_and_train_flops_by_hand():
    # one query over 3 keys: q.K^T 3 x 8 MACs + p.V 3 x 8 MACs = 96 FLOPs a layer
    assert work.attn_flops_token(TINY, 3) == 2 * 96
    # T = 4: contexts 1, 2, 3, 4 -> mean 2.5
    fwd = 3232 + work.attn_flops_token(TINY, 2.5)
    assert work.train_flops_per_token(TINY, 4) == 3 * fwd
    # flash core of one row: 6 causal matmuls of 10 pairs x 8 wide, 2 layers
    assert work.flash_train_flops_per_row(TINY, 4) == 2 * 6 * 2 * 10 * 8
    assert work.flash_train_bytes_per_row(TINY, 4) == 2 * 12 * 4 * 8 * 2


def test_serving_counts_by_hand():
    # prefill of 3 tokens: 3 x 3072 + attention over 1 + 2 + 3 keys + one head row
    assert work.prefill_flops(TINY, 3) == 3 * 3072 + 2 * 32 * 6 + 160
    assert work.decode_flops(TINY, 5) == 3232 + 2 * 32 * 5
    # decode step, 7 cached tokens over 2 slots: K and V 2 x 7 x 8 x 2 B, q and
    # out 2 x 2 x 8 x 2 B, two layers
    assert work.paged_decode_bytes(TINY, 7, 2) == 2 * (224 + 64)
    assert work.paged_decode_flops(TINY, 7) == 2 * 32 * 7


@pytest.mark.parametrize("name,roof", [("cgpt-1.3b-train", "compute"),
                                       ("cgpt-1.3b", "hbm")])
def test_the_declared_roof_binds_at_the_real_shapes(name, roof):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        cfg = json.load(f)
    peaks = peaks_for("TPU v5 lite")
    if roof == "compute":
        got = work.roofline_seconds(work.flash_train_flops_per_row(cfg, 2048),
                                    work.flash_train_bytes_per_row(cfg, 2048), peaks)
    else:
        got = work.roofline_seconds(work.paged_decode_flops(cfg, 14000),
                                    work.paged_decode_bytes(cfg, 14000, 32), peaks)
    assert got[1] == roof and got[0] > 0


def test_an_unknown_device_is_an_error():
    assert DEVICE_PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")

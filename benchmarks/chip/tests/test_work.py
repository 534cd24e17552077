"""Operation and byte counts against hand calculations, and the peaks
table's refusal of an unknown chip."""

import pytest

from chip import work
from chip.arch import dense
from chip.peaks import PEAKS, peak_for

V5E = peak_for("TPU v5 lite")


def test_mlp_out_site_job_by_hand():
    # qwen3-32b mlp_out: K = 25600 -> N = 5120 at M = 8, every tile live
    m, k, n = 8, 25600, 5120
    job = work.reuse_site_job(m, k, n, live=1.0)
    assert job.flops == 2 * 8 * 25600 * 5120 == 2_097_152_000
    # x bf16 409,600 + prev_q 204,800 + codes 204,800 + W bf16 262,144,000
    # + prev_out f32 163,840 + out f32 163,840
    assert job.bytes == 409_600 + 204_800 + 204_800 + 262_144_000 \
        + 163_840 + 163_840 == 263_290_880
    # bandwidth-bound on v5e: 263.29 MB at 819 GB/s
    assert job.least_seconds(V5E) == pytest.approx(263_290_880 / 819e9)


def test_skipped_tiles_are_not_work():
    full = work.reuse_gemm_job(8, 25600, 5120, live=1.0)
    half = work.reuse_gemm_job(8, 25600, 5120, live=0.5)
    assert half.flops == full.flops / 2
    assert full.bytes - half.bytes == 2 * 25600 * 5120 * 0.5


def test_model_flops_by_hand():
    shape = dense.DenseShape(layers=8, d_model=5120, heads=64, kv_heads=8,
                             head_dim=128, ffn=25600, vocab=151936,
                             gated=True)
    per_layer = 5120 * (8192 + 2048) + 8192 * 5120 + 3 * 5120 * 25600
    assert shape.linear_params() == 8 * per_layer + 5120 * 151936
    # one decode row at position 511 attends over 512 keys in 8 layers
    assert shape.decode_flops(1, 511) == \
        2 * shape.linear_params() + 4 * 8 * 64 * 128 * 512
    # a causal prefill of 3 tokens attends over 1 + 2 + 3 keys and takes
    # logits at its last position only
    assert shape.prefill_flops(1, 3) == 2 * 8 * per_layer * 3 \
        + 2 * 5120 * 151936 + 4 * 8 * 64 * 128 * 6


def test_unknown_device_kind_raises():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        peak_for("TPU v9 imaginary")

"""Shape-derived operations, bytes, roofline and mfu, by hand."""
import json

import pytest

from harness import spec, work

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_nm_gemm_counts_kept_values_and_two_bit_indices():
    g = work.Gemm(8, 4096, 4096, (2, 4))
    assert g.kept == 8_388_608                       # half of 4096 x 4096
    assert g.flops == 2 * 8 * 8_388_608              # 134,217,728
    # values 2 B + index 2 bits = 2.25 B per kept value; x and y once
    assert g.bytes == 8_388_608 * 2.25 + 8 * 4096 * 2 + 8 * 4096 * 2
    assert g.bytes == 19_005_440
    t = work.floor_seconds([g], V5E)
    assert t == pytest.approx(19_005_440 / 819e9)    # bound by bytes


def test_dense_and_compute_bound():
    g = work.Gemm(4096, 4096, 4096)
    assert g.flops == 2 * 4096 ** 3
    assert g.bytes == 3 * 4096 * 4096 * 2
    assert work.floor_seconds([g], V5E) == pytest.approx(2 * 4096 ** 3 / 197e12)
    # 1:8 keeps an eighth with 3-bit indices
    h = work.Gemm(1, 64, 8, (1, 8), in_bytes=0, out_bytes=0)
    assert h.bytes == 64 * 8 / 8 * (2 + 3 / 8)


def test_roofline_and_mfu_percent():
    g = work.Gemm(8, 4096, 4096, (2, 4))
    floor = 19_005_440 / 819e9
    assert work.roofline_percent([g] * 10, 10 * floor * 4, V5E) == \
        pytest.approx(25.0)
    assert work.roofline_percent([g], 0.0, V5E) is None
    assert work.mfu_percent(1.97e12, 1.0, V5E) == pytest.approx(1.0)


def test_yi9b_decode_step_and_token():
    cfg = json.loads((spec.BENCH_DIR / "configs" / "yi-9b-2to4.json")
                     .read_text())
    gemms = work.lm_sparse_step_gemms(cfg, 8)
    assert len(gemms) == 7 * 48
    per_layer_dense = 4096 * 4096 * 2 + 4096 * 512 * 2 + 4096 * 11008 * 3
    assert sum(g.kept for g in gemms) == 48 * per_layer_dense / 2
    # 2:4 kept values at 2.25 bytes: 9.34 GB of weight floor per step
    w = sum(g.kept for g in gemms) * 2.25
    assert w == pytest.approx(9.342e9, rel=1e-3)
    # a token at context c: 2 per kept MAC, 4*H*D*c attention, dense head
    c = 600
    want = 48 * (2 * per_layer_dense / 2 + 4 * 32 * 128 * c) + 2 * 4096 * 64000
    assert work.lm_token_flops(cfg, c) == want


def test_resnet50_per_image():

    cfg = json.loads((spec.BENCH_DIR / "configs" / "resnet50-2to4.json")
                     .read_text())
    ref = spec.load_module(spec.BENCH_DIR / "reference" / "resnet.py")
    convs = ref.convs(cfg)
    assert len(convs) == 53 and sum(c["sparse"] for c in convs) == 52
    stem = convs[0]
    assert (stem["h_out"], stem["sparse"]) == (112, False)
    # stem: 112*112 rows, K = 147, 64 out, dense
    g = work.cnn_conv_gemms(cfg, convs, 1, False)
    assert g[0].flops == 2 * 112 * 112 * 147 * 64
    # the first 3x3 of stage 2: 56x56 rows, K = 576, 64 out, half kept;
    # its input floor is the 56x56x64 activation, not the 9x patches
    s = {c["name"]: c for c in convs}["s2b1_3x3"]
    g3 = [x for x in work.cnn_conv_gemms(cfg, [s], 2, True)][0]
    assert g3.flops == 2 * 2 * 56 * 56 * 576 * 64 / 2
    assert g3.in_bytes == 2 * 56 * 56 * 64 * 2
    assert work.cnn_image_flops(cfg, convs) == pytest.approx(3.978e9, rel=1e-3)

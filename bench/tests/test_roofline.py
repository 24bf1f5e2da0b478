"""Operations and bytes of the kernels at this benchmark's shapes, against
hand-computed values, and the peak table."""
import pytest

from bench import model as bm, roofline
from bench.costs import flash_decode_paged, w4a8_matmul


def test_w4a8_decode_shape_opt_6_7b():
    # wq of OPT-6.7B in a decode step of 16 rows: x (16, 4096) bf16, the
    # activation scale (16, 1) f32, codes (2048, 4096) u8, scale and zero
    # point (64, 4096) f32, result (16, 4096) f32
    ops_, res = [("bf16", (16, 4096)), ("f32", (16, 1)),
                 ("u8", (2048, 4096)), ("f32", (64, 4096)),
                 ("f32", (64, 4096))], [("f32", (16, 4096))]
    ops, nb = w4a8_matmul.cost(ops_, res)
    assert ops == 2 * 16 * 4096 * 4096 == 536_870_912
    assert nb == 131_072 + 64 + 8_388_608 + 2 * 1_048_576 + 262_144
    pk = roofline.peaks("TPU v5 lite")
    t, side = roofline.bound_s(ops, nb, pk["int8_ops"], pk["hbm_bytes_s"])
    assert side == "memory"
    assert t == pytest.approx(10_879_040 / 819e9)


def test_w4a8_chunk_shape_opt_13b_is_compute_bound():
    # w_up of OPT-13B in a (16, 128) chunk step: M = 2048, K 5120, N 20480
    ops_, res = [("bf16", (2048, 5120)), ("f32", (2048, 1)),
                 ("u8", (2560, 20480)), ("f32", (80, 20480)),
                 ("f32", (80, 20480))], [("f32", (2048, 20480))]
    ops, nb = w4a8_matmul.cost(ops_, res)
    assert ops == 2 * 2048 * 5120 * 20480
    assert nb == (2048 * 5120 * 2 + 2048 * 4 + 2560 * 20480
                  + 2 * 80 * 20480 * 4 + 2048 * 20480 * 4)
    pk = roofline.peaks("TPU v5 lite")
    assert roofline.bound_s(ops, nb, pk["int8_ops"],
                            pk["hbm_bytes_s"])[1] == "compute"


def test_flash_decode_paged_kv8():
    # two rows attending 1000 and 500 positions, 32 heads of 128, pages of
    # 16: whole pages of int8 codes and f32 scales for K and V
    ops, nb = flash_decode_paged.cost([1000, 500], 32, 128, 16)
    assert ops == 4 * 32 * 128 * 1500
    assert nb == 2 * (1008 + 512) * 32 * (128 + 4) + 2 * 2 * 32 * 128 * 4


def test_token_ops_opt_6_7b():
    m = bm.dims(bm.load_config("opt-6.7b"))
    lin = 2 * 32 * (4 * 4096 ** 2 + 2 * 4096 * 16384)
    assert roofline.token_ops(m, 0, False) == lin
    assert roofline.token_ops(m, 100, True) == \
        lin + 4 * 32 * 4096 * 100 + 2 * 4096 * 50272


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert roofline.peaks("TPU v5 lite")["int8_ops"] == 393e12

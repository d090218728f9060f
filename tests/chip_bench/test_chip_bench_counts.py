"""Operations, bytes and peaks kept with the benchmark."""
import pytest

from benchmarks.chip import flops, peaks, readers, spec


def test_danube_forward_flops_by_hand():
    cfg = spec.load_cell("danube-chat-shadow").config
    d, f, v = 2560, 6912, 32000
    layer = (2 * d * d            # q: 32 heads x 80
             + 2 * 2 * d * 640    # k, v: 8 heads x 80
             + 2 * d * d          # o
             + 2 * d * 2 * f      # gate and up
             + 2 * f * d)         # down
    assert layer == 138_936_320
    assert flops.matmul_flops_per_token(cfg) == 24 * layer + 2 * d * v
    assert flops.matmul_flops_per_token(cfg) == 3_498_311_680
    # attention: 24 layers x (QK + PV) x 32 heads x 80 per key
    assert flops.attention_flops(cfg, 100) == 24 * 4 * 32 * 80 * 100
    assert flops.attention_flops(cfg, 5000) == 24 * 4 * 32 * 80 * 4096
    assert flops.sequence_flops(cfg, 2, 3) == 2 * (
        3 * 3_498_311_680 + 24 * 4 * 32 * 80 * (1 + 2 + 3))


def test_dscoder_forward_flops_by_hand():
    cfg = spec.load_cell("dscoder-memtrace").config
    d, f, v = 7168, 19200, 32256
    layer = (2 * d * d            # q: 56 heads x 128
             + 2 * 2 * d * 1024   # k, v: 8 heads x 128
             + 2 * d * d          # o
             + 2 * d * 2 * f      # gate and up
             + 2 * f * d)         # down
    assert layer == 1_060_634_624
    assert flops.matmul_flops_per_token(cfg) == layer + 2 * d * v
    assert flops.matmul_flops_per_token(cfg) == 1_523_056_640
    # no window: causal attention over every earlier key, one layer
    assert flops.attention_flops(cfg, 2048) == 4 * 56 * 128 * 2048
    assert flops.sequence_flops(cfg, 4, 2048) == 4 * (
        2048 * 1_523_056_640 + 4 * 56 * 128 * 2048 * 2049 // 2)


def test_hlo_bytes_reads_operands_and_result():
    text = ("%quantize_em.50 = f32[216,1024]{1,0:T(8,128)S(1)} custom-call("
            "f32[216,1024]{1,0:T(8,128)S(1)} %reshape.847), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_"
            "constraints={f32[216,1024]{1,0}}")
    assert flops.hlo_bytes(text) == 2 * 216 * 1024 * 4
    dyn = ("%quantize_em_dynamic.3 = f32[8,1024]{1,0} custom-call(s32[4]{0} "
           "%p, f32[8,1024]{1,0} %x), custom_call_target=\"tpu_custom_call\"")
    assert flops.hlo_bytes(dyn) == 2 * 8 * 1024 * 4 + 16


def test_peaks_by_device_kind():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


class _Trace:
    """Two quantize events of 1 MiB in and out, 4 us each, in a 1 ms
    window with 0.25 ms busy."""
    ops = {0: []}
    window_s = 1e-3

    def events(self, prefix):
        text = ("%quantize_em.1 = f32[256,1024]{1,0} custom-call(f32[256,1024]"
                "{1,0} %x), custom_call_target=\"tpu_custom_call\"")
        return [(4e-6, text), (4e-6, text)] if prefix == "quantize_em" else []

    def busy_s(self):
        return 2.5e-4


def test_shares_use_the_peaks_of_the_device_kind():
    ctx = {"trace": _Trace(), "device_kind": "TPU v5 lite",
           "model_flops": 197e12 * 1e-3 / 2}
    _Trace.ops = {0: [(0, 1, "x")]}
    least = 2 * 2 * 256 * 1024 * 4 / 819e9
    assert readers.kernel_roofline(ctx, "quantize_em") == pytest.approx(
        100 * least / 8e-6)
    assert readers.step_mfu(ctx) == pytest.approx(50.0)
    assert readers.device_idle(ctx) == pytest.approx(75.0)
    with pytest.raises(KeyError):
        readers.step_mfu(dict(ctx, device_kind="cpu"))


def test_nothing_to_read_reports_nothing():
    assert readers.kernel_roofline({"trace": None}, "quantize_em") is None
    assert readers.step_mfu({"trace": None, "model_flops": 1.0}) is None
    assert readers.device_idle({"trace": None}) is None

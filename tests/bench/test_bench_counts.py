"""FLOP and byte counts of the benchmark against hand counts at the two
configurations' shapes, and the peaks table."""
import json
import os

import pytest

import counts
import spec

BENCH = os.path.dirname(os.path.abspath(counts.__file__))
dense = spec.load_layout("dense_decoder", BENCH)

KERNEL_TEXT = (
    "%fused_rk_update.1 = bf16[8,10240,128]{2,1,0:T(8,128)(2,1)S(1)} "
    "custom-call(f32[8]{0:T(128)S(1)} %copy-done.1, f32[8]{0:T(128)S(1)} "
    "%integer_pow.1, s32[8]{0:T(128)S(1)} %copy-done.2, "
    "bf16[8,10240,128]{2,1,0:T(8,128)(2,1)S(1)} %reshape.8, "
    "bf16[8,10240,128]{2,1,0:T(8,128)(2,1)} %reshape.10, "
    "bf16[8,10240,128]{2,1,0:T(8,128)(2,1)S(1)} %reshape.12), "
    'custom_call_target="tpu_custom_call"')


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# per token at S = 512: q/k/v/o projections 2*d*(H*hd + 2*KV*hd) +
# 2*H*hd*d, causal core 4*H*hd*(S+1)/2, SwiGLU 6*d*d_ff, readout 2*d*V
@pytest.mark.parametrize("name, proj, ffn, readout", [
    ("qwen3_4b", 2 * 2560 * 6144 + 2 * 4096 * 2560, 6 * 2560 * 9728,
     2 * 2560 * 151936),
    ("mistral_nemo_12b", 2 * 5120 * 6144 + 2 * 4096 * 5120,
     6 * 5120 * 14336, 2 * 5120 * 131072),
])
def test_flops_match_hand_counts(name, proj, ffn, readout):
    m, S = _config(name), 512
    core = 4 * 32 * 128 * (S + 1) / 2
    assert dense.field_eval_flops(m, S) == S * (proj + core + ffn)
    assert counts.readout_flops(m, S) == S * readout
    assert counts.request_flops(dense, m, S, 9) == \
        9 * S * (proj + core + ffn) + S * readout


def test_qwen3_4b_field_eval_is_206_mflop_a_token():
    assert dense.field_eval_flops(_config("qwen3_4b"), 512) / 512 \
        == 206_053_376


def test_kernel_traffic_by_memory_space():
    read, written = counts.custom_call_traffic(KERNEL_TEXT)
    plane = 8 * 10240 * 128 * 2
    assert read == {1: 2 * plane + 8 * 4 * 3, 0: plane}
    assert written == {1: plane}
    # z + eps*dz + eps^2*g: two multiply-adds per element
    assert counts.rk_update_flops(KERNEL_TEXT) == 4 * 8 * 10240 * 128


def test_kernel_floor_takes_the_binding_bound():
    peak = counts.peaks("TPU v5 lite")
    s, which = counts.kernel_floor_s(KERNEL_TEXT, peak)
    plane = 8 * 10240 * 128 * 2
    assert which == "hbm"
    assert s == pytest.approx(plane / 819e9)


def test_peaks_refuse_an_unknown_device():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")

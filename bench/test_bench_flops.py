"""Model FLOPs from shapes, and the table of device peaks."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pytest  # noqa: E402

import harness as H  # noqa: E402
import step_flops as F  # noqa: E402


@pytest.mark.parametrize("config,per_token", [
    # hand count at seq 4096, causal: 8 layers x (2 x layer matmul params
    # + 4 x 3072 x 2048.5 attention) + 2 x 3072 x vocab for the head
    ("phi3-mini-3.8b-8l", 2.210e9),
    ("starcoder2-3b-8l", 2.038e9),
])
def test_forward_flops_per_token(config, per_token):
    model = H.config(config)["model"]
    assert F.forward_flops_per_token(model, 4096) == pytest.approx(
        per_token, rel=5e-4)


def test_step_flops_passes():
    model = H.config("phi3-mini-3.8b-8l")["model"]
    fwd = F.forward_flops_per_token(model, 4096) * 32768
    assert F.step_flops(model, 4096, 32768, "fo") == pytest.approx(3 * fwd)
    assert F.step_flops(model, 4096, 32768, "zo") == pytest.approx(2 * fwd)


def test_window_shortens_attention():
    model = dict(H.config("phi3-mini-3.8b-8l")["model"], window=1024)
    full = F.forward_flops_per_token(H.config("phi3-mini-3.8b-8l")["model"],
                                     4096)
    assert F.forward_flops_per_token(model, 4096) < full


def test_peaks_by_device_kind():
    assert F.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert F.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        F.peaks("TPU v4")
    with pytest.raises(KeyError):
        F.peaks("cpu")

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


def dense_count_before_the_split(model, seq):
    """The dense count as it stood with attention inline: one window for
    every layer."""
    D, H, KV, hd, Fd = (model["d_model"], model["n_heads"],
                        model["n_kv_heads"], model["head_dim"], model["d_ff"])
    mlp_mats = 3 if model["activation"] == "swiglu" else 2
    per_layer_params = D * H * hd * 2 + D * KV * hd * 2 + mlp_mats * D * Fd
    window = model.get("window") or seq
    keys = sum(min(i + 1, window) for i in range(seq)) / seq
    attn = 2 * 2 * H * hd * keys
    return model["n_layers"] * (2 * per_layer_params + attn) \
        + 2 * D * model["vocab_size"]


def test_attention_split_out_keeps_the_count():
    """``attention_flops`` plus the rest is the count before the split, to
    the FLOP: phi3 as run, and at a small size with per-layer windows,
    where it is the mean of the one-window counts."""
    phi3 = H.config("phi3-mini-3.8b-8l")["model"]
    assert F.forward_flops_per_token(phi3, 4096) == \
        dense_count_before_the_split(phi3, 4096)
    assert F.attention_flops(phi3, 4096) == 8 * 4 * 32 * 96 * 2048.5
    small = dict(phi3, n_layers=4, d_model=256, n_heads=4, n_kv_heads=2,
                 head_dim=64, d_ff=512, vocab_size=512)
    mixed = dict(small, window=[8, None, 8, None])
    assert F.forward_flops_per_token(mixed, 64) == (
        dense_count_before_the_split(dict(small, window=8), 64)
        + dense_count_before_the_split(dict(small, window=None), 64)) / 2
    assert F.layer_windows(mixed) == [8, None, 8, None]
    assert F.layer_windows(dict(small, window=8)) == [8] * 4


def test_flop_counts_from_the_reference_module(monkeypatch):
    """A configuration's reference module that defines its own counts gives
    them; ``dense_lm`` defines none, so the dense counts stand."""
    from types import SimpleNamespace as NS
    cfg = H.config("phi3-mini-3.8b-8l")
    assert H.flop_counts(cfg) == (F.forward_flops_per_token,
                                  F.attention_flops)

    def own_forward(model, seq):
        return 1e9

    def own_attention(model, seq):
        return 2e8
    monkeypatch.setattr(H, "reference_module", lambda c: NS(
        forward_flops_per_token=own_forward, attention_flops=own_attention))
    assert H.flop_counts(cfg) == (own_forward, own_attention)
    assert F.step_flops(cfg["model"], 4096, 10, "fo", own_forward) == 3e10

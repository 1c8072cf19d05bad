"""Model FLOPs of one training step, from a configuration's shapes, and the
device peaks they are divided by.

Counted: every matmul of the forward pass (2 FLOPs per multiply-add),
attention's two sequence matmuls over the causal half (position ``i`` sees
``i + 1`` keys), and the head.  Not counted: the embedding gather, norms,
activations, softmax, recomputation (remat) and the ZO direction algebra.
An FO step costs forward + backward = 3 forwards; a ZO step two forwards.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
PASSES = {"fo": 3, "zo": 2}


def forward_flops_per_token(model: dict, seq: int) -> float:
    D, H, KV, hd, F = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                       model["head_dim"], model["d_ff"])
    mlp_mats = 3 if model["activation"] == "swiglu" else 2
    per_layer_params = D * H * hd * 2 + D * KV * hd * 2 + mlp_mats * D * F
    window = model.get("window") or seq
    # mean number of keys a query attends to under a causal (windowed) mask
    keys = sum(min(i + 1, window) for i in range(seq)) / seq
    attn = 2 * 2 * H * hd * keys
    per_layer = 2 * per_layer_params + attn
    return model["n_layers"] * per_layer + 2 * D * model["vocab_size"]


def step_flops(model: dict, seq: int, tokens: int, kind: str) -> float:
    return PASSES[kind] * forward_flops_per_token(model, seq) * tokens


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"{PEAKS_FILE} has {sorted(table)}")
    return table[device_kind]

"""Model FLOPs of one training step, from a configuration's shapes, and the
device peaks they are divided by.

Counted: every matmul of the forward pass (2 FLOPs per multiply-add),
attention's two sequence matmuls over the causal half (position ``i`` sees
``i + 1`` keys, or its window's), and the head.  Not counted: the embedding
gather, norms, activations, softmax, recomputation (remat) and the ZO
direction algebra.  An FO step costs forward + backward = 3 forwards; a ZO
step two forwards.

These are the dense decoder's counts.  A configuration whose layers differ
(experts, latent attention, a leading dense layer) gives its own
``forward_flops_per_token`` and ``attention_flops`` in its reference module;
the harness takes those where they are defined (``harness.flop_counts``).
"""
from __future__ import annotations

import json
import os
from typing import List, Optional

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
PASSES = {"fo": 3, "zo": 2}


def layer_windows(model: dict) -> List[Optional[int]]:
    """Per layer, its attention window (None: full causal attention): the
    file's ``window`` is one value for every layer or a list, one a layer."""
    w = model.get("window")
    return list(w) if isinstance(w, list) else [w] * model["n_layers"]


def attention_flops(model: dict, seq: int) -> float:
    """FLOPs per token of attention's two sequence matmuls (q·kᵀ and P·v)
    in one forward, summed over the layers: the mean number of keys a
    query attends to under each layer's causal (windowed) mask."""
    H, hd = model["n_heads"], model["head_dim"]
    total = 0.0
    for window in layer_windows(model):
        keys = sum(min(i + 1, window or seq) for i in range(seq)) / seq
        total += 2 * 2 * H * hd * keys
    return total


def forward_flops_per_token(model: dict, seq: int) -> float:
    D, H, KV, hd, F = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                       model["head_dim"], model["d_ff"])
    mlp_mats = 3 if model["activation"] == "swiglu" else 2
    per_layer_params = D * H * hd * 2 + D * KV * hd * 2 + mlp_mats * D * F
    return (model["n_layers"] * 2 * per_layer_params
            + attention_flops(model, seq) + 2 * D * model["vocab_size"])


def step_flops(model: dict, seq: int, tokens: int, kind: str,
               forward=forward_flops_per_token) -> float:
    """FLOPs of one step of ``kind`` over ``tokens``, from ``forward``'s
    count per token (a configuration's own, where it has one)."""
    return PASSES[kind] * forward(model, seq) * tokens


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"{PEAKS_FILE} has {sorted(table)}")
    return table[device_kind]

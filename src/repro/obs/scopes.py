"""The named scopes of the step programs, and the map from a compiled
program's instructions to them.

The FO and ZO step programs name their parts with ``jax.named_scope`` where
the work is lowered (``core/distributed.py``, ``core/engine.py``,
``models/transformer.py``), so every op of the optimized HLO carries its
scope in the ``op_name`` of its metadata, e.g.
``jit(fo_step)/while/body/fo.grad/transpose(jvp(model.attn))/dot_general``.
A profiler trace names each device op by its HLO instruction name:
``op_names`` reads ``{instruction name: op_name}`` from a compiled program's
text (``jitted.lower(...).compile().as_text()``), and ``classify`` puts an
``op_name`` down to the part of the step and the model layer it belongs to.

Under ``fo.grad`` the phase is told by name alone: backward ops carry
``transpose(``, and the recompute of a rematerialized layer carries
``rematted_computation`` inside the transpose.  Inside ``model.attn`` the
attention itself runs under ``attn.flash`` (the blocked kernels) or
``attn.dense`` (the dense fallback), and the layer is named with its path
(``model.attn/attn.flash``).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

#: parts of a step, by program
STEP = ("fo.grad", "fo.accumulate", "fo.update",
        "zo.norm", "zo.perturb", "zo.forward", "zo.exchange",
        "zo.reconstruct", "zo.update")
#: layers of the model, inside ``fo.grad`` and ``zo.forward``
MODEL = ("model.embed", "model.attn", "model.mlp", "model.head")
SCOPES = STEP + MODEL
#: the paths of attention inside ``model.attn``
ATTN_PATHS = ("attn.flash", "attn.dense")

_SCOPE = re.compile(r"(?<![\w.])(%s)(?![\w.])"
                    % "|".join(re.escape(s) for s in SCOPES))
# one instruction per line: "%name = <shape> opcode(...), ...,
# metadata={op_name="..." ...}" (ROOT-prefixed in a computation's last line)
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*metadata=\{op_name="([^"]*)"',
                    re.M)
_ATTN_PATH = re.compile(r"(?<![\w.])(%s)(?![\w.])"
                        % "|".join(re.escape(s) for s in ATTN_PATHS))
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)


def module_name(hlo_text: str) -> str:
    """The module's name, as a trace's ``XLA Modules`` line shows it
    (``jit_fo_step``)."""
    m = _MODULE.search(hlo_text)
    if m is None:
        raise ValueError("not the text of an HLO module")
    return m.group(1)


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every instruction, in every
    computation, that has an ``op_name``."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def scopes_of(op_name: str) -> List[str]:
    """The known scopes in ``op_name``, outer to inner."""
    return _SCOPE.findall(op_name)


def classify(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """``(part, layer)``: the innermost step scope, ``fo.grad`` split into
    ``fo.grad.forward``, ``fo.grad.backward`` and ``fo.grad.recompute``;
    and the innermost model scope, ``model.attn`` with its attention path
    where one is named.  Either is None where none is named."""
    found = scopes_of(op_name)
    part = next((s for s in reversed(found) if s in STEP), None)
    layer = next((s for s in reversed(found) if s in MODEL), None)
    if layer == "model.attn":
        path = _ATTN_PATH.findall(op_name)
        if path:
            layer += "/" + path[-1]
    if part == "fo.grad":
        if "rematted_computation" in op_name:
            part += ".recompute"
        elif "transpose(" in op_name:
            part += ".backward"
        else:
            part += ".forward"
    return part, layer

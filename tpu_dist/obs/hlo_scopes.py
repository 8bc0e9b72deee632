"""Which ops of the compiled train step belong to which named scope.

The model wraps its kernel-like regions in ``jax.named_scope`` (``ssm/scan``,
``moe/experts``, ``attn/causal``, ...). The scope reaches every HLO
instruction's ``op_name`` metadata, forward and transposed
(``.../transpose(jvp(ssm/scan))/...``), but not the device trace: a v5e
capture names an op event by its HLO text without the metadata and attaches
no stat that holds it (read off a capture, PR 33). So the program keeps the
join itself: where the trainer compiles its step for the cost capture
(``obs/costmodel.analyze_jitted``, the first dispatch of a run on a TPU), the
executable's text is parsed once into ``instruction name -> op_name``, and a
reader of a trace asks :func:`ops_in` for the instruction names of a scope.

Host-side, once a run, no device work. A fusion carries the ``op_name`` of
its root instruction, so an op that XLA fused across a scope's edge counts
on one side of it; an instruction XLA made itself (a copy, a rewritten
custom call) may carry none.
"""

from __future__ import annotations

import re
from typing import Dict, FrozenSet

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?(?P<op>[^\s=]+) = .*?metadata=\{[^}]*?op_name="(?P<name>[^"]*)"'
)
_OP_NAMES: Dict[str, str] = {}


def record(hlo_text: str) -> int:
    """Replace the table by the instructions of ``hlo_text`` (a compiled
    executable's ``as_text()``); returns how many carry an ``op_name``."""
    _OP_NAMES.clear()
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            _OP_NAMES[m.group("op")] = m.group("name")
    return len(_OP_NAMES)


def ops_in(scope: str) -> FrozenSet[str]:
    """Names of the recorded instructions whose ``op_name`` holds ``scope``."""
    return frozenset(op for op, name in _OP_NAMES.items() if scope in name)


def recorded() -> int:
    return len(_OP_NAMES)

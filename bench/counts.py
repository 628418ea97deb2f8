"""Operations and bytes of one served instruction, from the shapes.

One ``MESCServer.step()`` runs one instruction for one request: the
prefill of its whole prompt, or one decode step.  What the algorithm
needs for it, and no more, is counted by the configuration's family
(``bench/families/<family>.py``, whose docstring says what it counts).
Every family counts only what the algorithm needs, so a share of a
peak computed from these counts can only be an underestimate of what
the chip did.
"""
from __future__ import annotations

from pathlib import Path

from bench import spec

COMPUTE_BYTES = 2          # bfloat16


def flops(conf: dict, kind: str, tokens: int, kv_len: int,
          root: Path = spec.ROOT) -> float:
    """Operations of one instruction: ``kind`` is ``prefill`` (``tokens``
    prompt tokens) or ``decode`` (one token attending ``kv_len`` keys)."""
    return spec.family(conf, root).flops(conf, kind, tokens, kv_len)


def hbm_bytes(conf: dict, kind: str, tokens: int, kv_len: int,
              root: Path = spec.ROOT) -> float:
    """Bytes one instruction must move to or from device memory."""
    return spec.family(conf, root).hbm_bytes(conf, kind, tokens, kv_len)

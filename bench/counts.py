"""Operations and bytes of one served instruction, from the shapes.

One ``MESCServer.step()`` runs one instruction for one request: the
prefill of its whole prompt, or one decode step.  What the algorithm
needs for it, and no more:

* Matrix products: 2 operations per multiply-add of the projections
  (``q``, ``k``, ``v``, ``o``, the three MLP matrices) for every token
  in the instruction, and of the output head at the last position only.
* Attention: causal.  A prefill of ``S`` tokens scores and mixes
  ``S (S + 1) / 2`` query-key pairs per head; a decode step attends to
  the ``kv_len`` keys up to and including its own position, not to the
  cache's full ``max_len``.
* Bytes: every weight matrix read once per instruction, at the compute
  dtype (bfloat16), whatever dtype it is stored in; the embedding rows
  of the instruction's tokens (where the head is not the embedding);
  the keys and values written for its tokens; for a decode step, the
  keys and values read up to ``kv_len``.

Norms, rotary and softmax arithmetic are left out (a few operations
per element, far below the products).  So a share of a peak computed
from these counts can only be an underestimate of what the chip did.
"""
from __future__ import annotations

from bench.weights import dims

COMPUTE_BYTES = 2          # bfloat16


def _matrix_params(conf: dict) -> tuple:
    n = dims(conf)
    d, H, Hkv, dh, f = n["d"], n["H"], n["Hkv"], n["dh"], n["f"]
    layer = d * H * dh + 2 * d * Hkv * dh + H * dh * d + 3 * d * f
    return n["L"] * layer, d * n["V"]


def _kv_bytes_per_token(conf: dict) -> int:
    n = dims(conf)
    return 2 * n["L"] * n["Hkv"] * n["dh"] * COMPUTE_BYTES


def flops(conf: dict, kind: str, tokens: int, kv_len: int) -> float:
    """Operations of one instruction: ``kind`` is ``prefill`` (``tokens``
    prompt tokens) or ``decode`` (one token attending ``kv_len`` keys)."""
    n = dims(conf)
    body, head = _matrix_params(conf)
    per_pair = 4 * n["H"] * n["dh"] * n["L"]      # q.k and p.v
    if kind == "prefill":
        pairs = tokens * (tokens + 1) / 2
        return 2.0 * body * tokens + per_pair * pairs + 2.0 * head
    return 2.0 * body + per_pair * kv_len + 2.0 * head


def hbm_bytes(conf: dict, kind: str, tokens: int, kv_len: int) -> float:
    """Bytes one instruction must move to or from device memory."""
    n = dims(conf)
    body, head = _matrix_params(conf)
    weights = (body + head) * COMPUTE_BYTES
    # a tied head reads the whole embedding already
    embed_rows = 0 if n["tied"] else tokens * n["d"] * COMPUTE_BYTES
    kv = _kv_bytes_per_token(conf)
    if kind == "prefill":
        return weights + embed_rows + kv * tokens
    return weights + embed_rows + kv * kv_len

"""Sharding rules: DP (+pod) x FSDP('data') x TP/EP('model').

A thread-local :class:`AxisRules` context maps logical roles to mesh axes.
Outside any context (unit tests on one device) every constraint is a no-op,
so model code is portable.

Conventions:
  * batch dims           -> ('pod','data') / ('data',)
  * up-proj weights      -> (in='data' [FSDP], out='model' [TP])
  * down-proj weights    -> (in='model', out='data')
  * MoE expert weights   -> (E='model' [EP], in='data', out=None)
  * vocab dim            -> 'model'
  * residual stream S    -> 'model' when sequence_parallel
  * KV-cache S dim       -> 'model' (flash-decoding via GSPMD reductions)

Every spec is *sanitized* against the actual shape: axes that do not divide
the dimension are dropped (replicated) — this is what makes odd head counts
(40, 56, 10 heads on a 16-way axis) compile cleanly; GSPMD then propagates a
legal layout from the surrounding annotated ops.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_TLS = threading.local()


def logical_device_mesh(n: int, axis_name: str = "dev") -> Mesh:
    """1-D mesh over the first ``n`` local devices.

    The sim dispatcher's shard axis (``core.simulator_jit``): simulation
    points are independent, so the mesh carries no collectives — it only
    names the axis ``shard_map`` splits the point dimension over.  The
    logical CPU devices themselves come from ``runtime.device_config``
    (``--xla_force_host_platform_device_count``).
    """
    devs = jax.devices()
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"logical_device_mesh: need 1 <= n <= {len(devs)} "
            f"available devices, got n={n} (configure the pool first — "
            "see repro.runtime.device_config)")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def current_rules() -> Optional["AxisRules"]:
    return getattr(_TLS, "rules", None)


class AxisRules:
    """mode='sp': Megatron-SP+TP (weights stay model-sharded; sequence is
    gathered at block entry and reduce-scattered at exit).  mode='2d':
    batch sharded over data x model (ZeRO-3-style full weight gathers) —
    right for small models where replicating a layer's weights is cheap."""

    def __init__(self, mesh: Mesh, *, sequence_parallel: bool = False,
                 mode: str = "sp", fsdp_over_pod: bool = False):
        self.mesh = mesh
        names = mesh.axis_names
        self.dp: Tuple[str, ...] = tuple(n for n in names if n in ("pod", "data"))
        self.tp: Optional[str] = "model" if "model" in names else None
        self.sp = sequence_parallel
        self.mode = mode
        # ZeRO across pods: shard params over ('pod','data') so 400B-class
        # state halves per added pod (gathers cross slow links -> pair with
        # int8 gather compression, see optim.compression)
        self.fsdp: Tuple[str, ...] = (
            tuple(n for n in names if n in ("pod", "data"))
            if fsdp_over_pod else ("data",) if "data" in names else ())

    def axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return int(np.prod([self.mesh.shape[a] for a in axes]))

    def _resolve(self, ax):
        if ax == "data":                    # alias: the FSDP shard axes
            if len(self.fsdp) == 0:
                return None
            return self.fsdp if len(self.fsdp) > 1 else self.fsdp[0]
        return ax

    def sanitize(self, spec: Tuple, shape: Tuple[int, ...]) -> P:
        out = []
        for d, ax in enumerate(spec[:len(shape)]):
            ax = self._resolve(ax)
            if ax is None or shape[d] % self.axis_size(ax) != 0:
                out.append(None)
            else:
                out.append(ax)
        out += [None] * (len(shape) - len(out))
        return P(*out)

    def named(self, spec: Tuple, shape: Tuple[int, ...]) -> NamedSharding:
        return NamedSharding(self.mesh, self.sanitize(spec, shape))


@contextmanager
def axis_rules(rules: Optional[AxisRules]):
    prev = getattr(_TLS, "rules", None)
    _TLS.rules = rules
    try:
        yield rules
    finally:
        _TLS.rules = prev


# ---------------------------------------------------------------------------
# Activation constraints (called from model code)
# ---------------------------------------------------------------------------

def shard_activation(x, kind: str, rc=None):
    r = current_rules()
    if r is None or (rc is not None and not rc.logical_axes):
        return x
    dp = r.dp if len(r.dp) != 1 else r.dp[0]
    full = r.dp + ((r.tp,) if r.tp else ())
    is2d = r.mode == "2d" and x.shape[0] % r.axis_size(full) == 0
    if kind == "residual":
        if is2d:
            spec: Tuple = (full, None, None)
        else:
            seq = r.tp if (r.sp and (rc is None or rc.sequence_parallel)) \
                else None
            spec = (dp, seq, None)
    elif kind == "logits":
        spec = (dp,) + (None,) * (x.ndim - 2) + (r.tp,)
    elif kind == "batch":
        spec = (dp,) + (None,) * (x.ndim - 1)
    elif kind == "attn_in":
        # q/k/v (B, S, H, dh): keep the flash loops collective-free.
        # 2d: batch-local attention; sp: head-sharded TP when heads divide,
        # else replicated across 'model' (documented redundancy; §Perf lever).
        spec = _attn_spec(r, x.shape[0], x.shape[2])
    elif kind == "attn_out":
        # o (B, S, H*dh) before the output projection
        if is2d:
            spec = (full, None, None)
        else:
            spec = (dp, None, r.tp)
    elif kind == "ffn_in":
        # block input x (B, S, D): sequence gathered (Megatron-SP boundary)
        spec = (full, None, None) if is2d else (dp, None, None)
    elif kind == "ffn_hidden":
        # up-projection output (B, S, F): F model-sharded in sp mode so the
        # FFN weights are never replicated across 'model'
        spec = (full, None, None) if is2d else (dp, None, r.tp)
    elif kind == "moe_tokens":
        # (R, N, D) routing rows: train routes per sequence (R = batch),
        # decode routes over batch (R = 1, N = batch)
        spec = (dp, None, None) if x.shape[0] > 1 else (None, dp, None)
    elif kind == "moe_buf":
        # expert buffers (R, E, C, *): expert dim over 'model' (EP)
        spec = (dp if x.shape[0] > 1 else None, r.tp)             + (None,) * (x.ndim - 2)
    else:
        return x
    return jax.lax.with_sharding_constraint(
        x, r.named(spec, x.shape))


def _attn_spec(r: "AxisRules", B: int, H: int) -> Tuple:
    dp = r.dp if len(r.dp) != 1 else r.dp[0]
    full = r.dp + ((r.tp,) if r.tp else ())
    if r.mode == "2d" and B % r.axis_size(full) == 0:
        return (full, None, None, None)
    if r.tp and H % r.axis_size(r.tp) == 0:
        return (dp, None, r.tp, None)
    return (dp, None, None, None)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

_UP = {"wq", "wk", "wv", "w1", "w3", "w_q", "w_dkv", "w_uk", "w_uv", "w_in",
       "w_up", "w_y", "w_xb", "w_if", "w_k"}
_DOWN = {"wo", "w2", "w_o", "w_down", "w_out"}
_REPL3 = {"w_a", "w_x", "r"}          # small block-diagonal weights

# (core_rank, core_spec); leading stack dims are padded with None
_PARAM_RULES = {
    **{n: (2, ("data", "model")) for n in _UP},
    **{n: (2, ("model", "data")) for n in _DOWN},
    **{n: (3, (None, None, None)) for n in _REPL3},
    # embed: vocab replicated, D sharded over the whole mesh -> token
    # gathers are fully local (a vocab-sharded table makes GSPMD emit
    # per-shard masked gathers with replicated batch)
    "embed": (2, (None, ("data", "model"))),
    "lm_head": (2, ("data", "model")),
    "router": (2, ("data", None)),
    "conv_w": (2, (None, "model")),
    "lam": (1, ("model",)),
}
_MOE_RULES = {
    "w1": (3, ("model", "data", None)),
    "w3": (3, ("model", "data", None)),
    "w2": (3, ("model", None, "data")),
}


def _param_spec(path, arr, rules: AxisRules, tied: bool = False) -> NamedSharding:
    keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    name = keys[-1]
    in_moe = len(keys) >= 2 and keys[-2] == "moe"
    if name == "embed" and tied:
        # tied embeddings serve as lm_head too: keep vocab on 'model' so
        # the logits matmul stays vocab-parallel (the input-side gather
        # cost is acceptable at tied-arch vocab sizes)
        rule = (2, ("model", "data"))
    else:
        rule = (_MOE_RULES.get(name) if in_moe else None) \
            or _PARAM_RULES.get(name)
    if rule is None:
        return rules.named((None,) * arr.ndim, arr.shape)
    core_rank, core = rule
    lead = arr.ndim - core_rank
    if lead < 0:
        return rules.named((None,) * arr.ndim, arr.shape)
    return rules.named((None,) * lead + tuple(core), arr.shape)


def param_specs(params, rules: AxisRules):
    """PyTree of NamedSharding for a parameter tree."""
    tied = isinstance(params, dict) and "lm_head" not in params
    return jax.tree_util.tree_map_with_path(
        lambda p, a: _param_spec(p, a, rules, tied=tied), params)


# ---------------------------------------------------------------------------
# Cache / optimizer / batch specs
# ---------------------------------------------------------------------------

_CACHE_RULES = {
    # core spec counted from the END of the shape
    "ck": ("batch", "model", None, None), "cv": ("batch", "model", None, None),
    "cka": ("batch", "model", None, None), "cva": ("batch", "model", None, None),
    "ckb": ("batch", "model", None, None), "cvb": ("batch", "model", None, None),
    "cc": ("batch", "model", None), "ckr": ("batch", "model", None),
    "wk": ("batch", "model", None, None), "wv": ("batch", "model", None, None),
    "rh": ("batch", "model"), "rconv": ("batch", None, "model"),
    "mC": ("batch", None, None, None), "mn": ("batch", None, None),
    "mm": ("batch", None), "mconv": ("batch", None, "model"),
    "sc": ("batch", "model"), "sn": ("batch", "model"),
    "sh": ("batch", "model"), "sm": ("batch", "model"),
    "pos": (),
}


def _cache_spec(path, arr, rules: AxisRules) -> NamedSharding:
    keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    name = keys[-1]
    rule = _CACHE_RULES.get(name) or _CACHE_RULES.get(name.rstrip("0123456789"))
    if rule is None:
        return rules.named((None,) * arr.ndim, arr.shape)
    dp = rules.dp if len(rules.dp) != 1 else rules.dp[0]
    core = tuple(dp if ax == "batch" else ax for ax in rule)
    lead = arr.ndim - len(core)
    if lead < 0:
        return rules.named((None,) * arr.ndim, arr.shape)
    return rules.named((None,) * lead + core, arr.shape)


def cache_specs(cache, rules: AxisRules):
    return jax.tree_util.tree_map_with_path(
        lambda p, a: _cache_spec(p, a, rules), cache)


def kv_seq_sharded(shape: Tuple[int, ...]) -> bool:
    """Whether ``cache_specs`` under the current rules splits the S dim
    of a KV cache of ``shape`` (..., B, S, Hkv, Dh) over devices."""
    r = current_rules()
    if r is None or r.tp is None:
        return False
    seq = r.sanitize(_CACHE_RULES["ck"][1:2], shape[-3:-2])[0]
    return seq is not None and r.axis_size(seq) > 1


def batch_specs(batch, rules: AxisRules):
    dp = rules.dp if len(rules.dp) != 1 else rules.dp[0]
    return jax.tree_util.tree_map(
        lambda a: rules.named((dp,) + (None,) * (a.ndim - 1), a.shape), batch)


def replicated(tree, rules: AxisRules):
    return jax.tree_util.tree_map(
        lambda a: rules.named((None,) * getattr(a, "ndim", 0),
                              getattr(a, "shape", ())), tree)

"""Jaxpr/HLO extraction behind the graph-lint rules.

Everything here answers one question: *what did the compiler actually
build* for a representative engine configuration?  The functions trace
the jit lockstep engine exactly the way production does — same
``_compiled_run`` memo, same table/carry construction, same x64
context — then walk the resulting ClosedJaxpr (recursing into
``while``/``cond``/``scan``/``jit``/``shard_map`` sub-jaxprs) and the
optimized HLO text to extract the measurable surface the budget
manifests pin:

* while-body kernel count (via the engine's own
  :func:`repro.core.simulator_jit.while_body_kernels` so the manifest
  and ``BENCH_sim.json`` can never disagree about what a kernel is);
* the recursive primitive histogram;
* the loop-carry tensor inventory (count, per-tensor dtype, global
  bytes) against the engine's ``_CARRY_KEYS`` contract;
* buffer-donation evidence (``input_output_alias`` pairs in the HLO
  header, donation-dropped warnings during compile);
* dtype discipline (float32 values anywhere in an x64 graph,
  f64->f32 ``convert_element_type`` demotions);
* CRN purity (callback / transfer / threefry primitives that AST
  linting cannot see through closures);
* the retrace surface of the span planner over the shared corpora;
* the serving virtual path's zero-XLA-compilation contract.

All jax / repro imports are deferred into the functions: importing
this module must stay safe from the stdlib-only lint job (the IR rules
are non-default there; see ``tools/graphlint/rules.py``).  Tracing
always runs against the real checkout this file lives in — a
``--root`` pointing at a throwaway manifest tree changes which
``budgets.json`` is read, never which engine is traced.
"""
from __future__ import annotations

import math
import re
import sys
import warnings
from collections import Counter
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional

#: the checkout that owns the traced engines (NOT the lint --root)
REPO_ROOT = Path(__file__).resolve().parents[2]

#: primitives that must never appear in a compiled engine graph: host
#: callbacks and transfers break the pure-loop contract, threefry /
#: random_* primitives break the counter-based CRN contract (every
#: draw must come from the hash-based per-point streams, never from a
#: traced jax.random key)
BANNED_EXACT = frozenset({
    "pure_callback", "io_callback", "debug_callback", "outside_call",
    "infeed", "outfeed", "device_put",
})
BANNED_PREFIXES = ("threefry", "random_")

#: sub-jaxpr-owning primitives whose own eqn is compiler plumbing, not
#: a budgetable operation (their contents are recursed into instead)
WRAPPER_PRIMS = frozenset({"jit", "closed_call", "custom_jvp_call",
                           "custom_vjp_call", "remat", "shard_map"})


def _ensure_paths() -> None:
    """Make ``repro`` and the test harness importable the way pytest
    arranges them (src/ on the path, tests/ as top-level modules)."""
    for p in (REPO_ROOT / "src", REPO_ROOT / "tests"):
        s = str(p)
        if s not in sys.path:
            sys.path.insert(0, s)


def _harness():
    _ensure_paths()
    import harness
    return harness


def prepare_device_pool(n: int) -> None:
    """Widen the logical host device pool to ``n`` before the first
    backend init, so the manifest's sharded cases can trace.  A no-op
    once XLA is live (pytest's conftest already forces a >= 4-way
    pool; the CLI arrives here first and configures its own)."""
    _ensure_paths()
    from repro.runtime.device_config import (configure_host_devices,
                                             jax_initialized)
    if n > 1 and not jax_initialized():
        configure_host_devices(n)


# ----------------------------------------------------------------------
# Jaxpr walking (raw Jaxpr and ClosedJaxpr handled uniformly)
# ----------------------------------------------------------------------

def _inner(jaxpr_like):
    """The raw eqn-bearing jaxpr: ``while``/``cond``/``jit`` params
    hold ClosedJaxpr (unwrap ``.jaxpr``), ``shard_map`` params hold
    raw Jaxpr already — normalize both to the raw form, which has
    ``.eqns`` *and* ``.invars``."""
    return getattr(jaxpr_like, "jaxpr", jaxpr_like)


def sub_jaxprs(eqn) -> Iterable[Any]:
    """Every jaxpr-valued param of one eqn (lists included)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                if hasattr(_inner(v), "eqns"):
                    yield _inner(v)


def walk_eqns(jaxpr_like) -> Iterable[Any]:
    """Depth-first over every eqn, recursing into sub-jaxprs."""
    for eqn in _inner(jaxpr_like).eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from walk_eqns(sub)


def find_while(jaxpr_like):
    """The (first) lockstep ``while`` eqn, at any nesting depth —
    devices=1 traces to jit->while, sharded to
    jit->shard_map->while."""
    for eqn in walk_eqns(jaxpr_like):
        if eqn.primitive.name == "while":
            return eqn
    raise ValueError("no while eqn in traced computation — the "
                     "lockstep engine no longer lowers to while_loop?")


def primitive_histogram(jaxpr_like) -> Dict[str, int]:
    """Recursive primitive counts, skipping pure wrapper eqns (their
    names churn across jax versions; their contents are counted)."""
    hist: Counter = Counter()
    for eqn in walk_eqns(jaxpr_like):
        name = eqn.primitive.name
        if name not in WRAPPER_PRIMS:
            hist[name] += 1
    return dict(sorted(hist.items()))


def banned_primitives(jaxpr_like) -> Dict[str, int]:
    """Counts of contract-banned primitives anywhere in the graph."""
    out: Counter = Counter()
    for eqn in walk_eqns(jaxpr_like):
        name = eqn.primitive.name
        if name in BANNED_EXACT or name.startswith(BANNED_PREFIXES):
            out[name] += 1
    return dict(sorted(out.items()))


def dtype_summary(jaxpr_like) -> Dict[str, int]:
    """Dtype-discipline counters over the whole graph.

    ``float32_ops`` counts eqns producing any float32 value — the
    engine runs entirely under x64, so a single f32 aval means XLA
    silently demoted event times somewhere.  ``f64_to_f32_demotions``
    counts explicit f64->f32 ``convert_element_type`` eqns (the int32
    <-> int64 widenings along the step counter are legitimate and are
    pinned by the primitive histogram instead).
    """
    f32_ops = demotions = 0
    for eqn in walk_eqns(jaxpr_like):
        outs = [v.aval for v in eqn.outvars if hasattr(v, "aval")]
        if any(getattr(a, "dtype", None) is not None
               and str(a.dtype) == "float32" for a in outs):
            f32_ops += 1
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0].aval if eqn.invars else None
            dst = outs[0] if outs else None
            if src is not None and dst is not None \
                    and str(getattr(src, "dtype", "")) == "float64" \
                    and str(getattr(dst, "dtype", "")) == "float32":
                demotions += 1
    return {"float32_ops": f32_ops, "f64_to_f32_demotions": demotions}


def carry_summary(while_eqn, devices: int) -> Dict[str, Any]:
    """The loop-carry tensor inventory from the while body's
    signature: the invars after the ``body_nconsts`` closed-over
    constants are exactly the carry, in pytree (sorted-key) order.
    ``total_bytes`` is the global carry footprint (per-shard bytes
    times the device count — every carry tensor shards along the point
    axis, the step counter contributes one lane per device)."""
    _ensure_paths()
    from repro.core.simulator_jit import _CARRY_KEYS
    body = _inner(while_eqn.params["body_jaxpr"])
    n_const = while_eqn.params["body_nconsts"]
    avals = [v.aval for v in body.invars[n_const:]]
    names = sorted(_CARRY_KEYS)
    if len(names) != len(avals):
        names = [f"tensor{i:02d}" for i in range(len(avals))]
    dtypes = {n: str(a.dtype) for n, a in zip(names, avals)}
    per_shard = sum(
        int(a.dtype.itemsize) * int(math.prod(a.shape) if a.shape
                                    else 1)
        for a in avals)
    return {"tensors": len(avals), "dtypes": dtypes,
            "total_bytes": per_shard * max(devices, 1)}


def donation_summary(hlo_text: str,
                     caught: List[warnings.WarningMessage]) \
        -> Dict[str, int]:
    """Donation evidence from one compiled module: ``donated`` counts
    the input/output alias pairs XLA committed to in the module header
    (one per carry leaf when donation worked), ``dropped`` counts
    donation-related warnings jax raised while lowering/compiling
    (nonzero means ``donate_argnums`` silently degraded to a copy)."""
    header = ""
    for line in hlo_text.splitlines():
        if "input_output_alias" in line:
            header = line
            break
    donated = len(re.findall(r"(?:may|must)-alias", header))
    dropped = sum(1 for w in caught
                  if "donat" in str(w.message).lower())
    return {"donated": donated, "dropped": dropped}


# ----------------------------------------------------------------------
# Case tracing
# ----------------------------------------------------------------------

def trace_jit_case(config: Dict[str, Any],
                   spec: Dict[str, Any]) -> Dict[str, Any]:
    """Trace + compile one jit-engine configuration at the manifest's
    canonical corpus shape and return its live budget dict.

    ``config`` mirrors a ``budgets.json`` case entry: ``policy``
    ("mesc" | "non_preemptive"), ``demand_profile``, ``scenario``
    (None or a ``get_scenario`` spec) and ``devices``.  The compile
    goes through the production ``_compiled_run`` memo, so a second
    case that is graph-identical (the neutral-scenario contract) hits
    the same jitted callable.
    """
    _ensure_paths()
    import jax
    import jax.numpy as jnp

    from repro.core import Policy
    from repro.core import simulator_jit as sj
    from repro.scenarios import get_scenario

    h = _harness()
    policy = {"mesc": Policy.mesc,
              "non_preemptive": Policy.non_preemptive}[
        config.get("policy", "mesc")]()
    devices = int(config.get("devices") or 1)
    nominal = config.get("demand_profile", "sampled") == "nominal"
    scenario = get_scenario(config.get("scenario"))
    loop_scen = scenario if scenario is not None \
        and scenario.affects_demand else None   # as simulate_jbatch
    tasksets, seeds = h.fig8_corpus(tuple(spec["utils"]),
                                    int(spec["n_seeds"]),
                                    int(spec["n_tasks"]))
    duration = float(spec["duration"])
    K = int(spec["table_width"])
    b = sj._VecBatch(tasksets, h.LIB, policy,
                     seeds=[int(s) for s in seeds], duration=duration,
                     overrun_prob=float(spec["overrun_prob"]),
                     cf=float(spec["cf"]), scenario=scenario)
    run = sj._compiled_run(policy.use_banks, policy.drop_lo_in_hi,
                           policy.preemption, nominal, sj._PRUNE_STALE,
                           loop_scen, devices)
    with jax.enable_x64(True):
        tb = sj._tables(b, seeds)
        sc = {"t_sr": jnp.float64(policy.t_sr),
              "overrun_prob": jnp.float64(float(spec["overrun_prob"])),
              "cf": jnp.float64(float(spec["cf"])),
              "duration": jnp.float64(duration),
              "max_steps": jnp.int64(sj._max_steps(b, duration))}
        c0 = sj._carry0(b, seeds, K, devices=devices)
        closed = jax.make_jaxpr(run)(tb, sc, c0)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            hlo = run.lower(tb, sc, c0).compile().as_text()
    while_eqn = find_while(closed.jaxpr)
    budget: Dict[str, Any] = {
        "while_body_kernels": sj.while_body_kernels(hlo),
        "primitive_histogram": primitive_histogram(closed.jaxpr),
        "carry": carry_summary(while_eqn, devices),
        "donation": donation_summary(hlo, list(caught)),
        "banned_primitives": banned_primitives(closed.jaxpr),
    }
    budget.update(dtype_summary(closed.jaxpr))
    return budget


def serving_compilations() -> Optional[int]:
    """XLA backend compilations triggered by one tiny virtual-clock
    serving case.

    The fig12 stack is modelless (virtual clocks, CRN service draws,
    no weights); its only jax traffic is the eager transfer/convert
    executables behind the context-save/restore model
    (``device_put``/``device_get``/``asarray`` — the
    ``step_wise_mvin``/``mvout`` cost accounting).  Those compile a
    fixed handful of trivial kernels; the pinned count is the ceiling
    that catches a jitted model call (or any other real computation)
    sneaking into the virtual path.  Counted via jax's monitoring
    events; returns None when a backend is already live in this
    process — eager kernels are cached process-wide, so only a fresh
    process (the CLI, CI) measures authoritatively."""
    _ensure_paths()
    from repro.runtime.device_config import jax_initialized
    if jax_initialized():
        return None
    try:
        from jax._src import monitoring
    except ImportError:      # jax-internal API drift: unmeasurable
        return None
    count = [0]

    def _listener(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    monitoring.register_event_duration_secs_listener(_listener)
    h = _harness()
    case = h.ServingCase(name="graphlint-probe", n_lo=4, n_hi=2)
    h.run_serving_case(case)
    return count[0]


def retrace_surface(spec: Dict[str, Any]) -> Dict[str, Dict[str, int]]:
    """Distinct traced signatures the span planner produces over the
    two shared corpora, per device count.

    A signature is the static shape key jax specializes on —
    (devices, per-device chunk, padded task count, table width).  The
    planner buckets points into devices x chunk rectangles, so the
    signature count must stay O(1) in the corpus size; a count equal
    to ``n_points`` means some axis retraces per point, which is the
    exact anti-precondition for the ROADMAP's mega-batching item.
    Computed statically from ``_plan_spans`` — no compilation.
    """
    _ensure_paths()
    from repro.core.simulator_jit import _plan_spans
    h = _harness()
    K = int(spec["table_width"])
    chunk = int(spec.get("chunk", 64))
    corpora = {
        "fig8": [int(spec["n_tasks"])]
        * (len(spec["utils"]) * int(spec["n_seeds"])),
        "mixed": list(h.MIXED_SIZES),
    }
    out: Dict[str, Dict[str, int]] = {}
    for name, sizes in corpora.items():
        for devices in (1, 2):
            sigs = set()
            for idxs, real, d in _plan_spans(len(sizes), chunk,
                                             devices):
                t_max = max((sizes[i] for i in idxs
                             if i < len(sizes)), default=0)
                sigs.add((d, len(idxs) // max(d, 1), t_max, K))
            out[f"{name}-d{devices}"] = {
                "n_points": len(sizes), "signatures": len(sigs)}
    return out

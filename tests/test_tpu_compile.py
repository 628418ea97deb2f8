"""Compile the device paths for a described TPU v5e, with no chip.

The TPU compiler refuses here what it would refuse on the chip: a
Pallas lowering it does not implement, a block shape off the (8, 128)
tiling, a program that does not fit the chip's memory.  Nothing runs,
so these tests say nothing about results or times.  The topology is
described inside a fixture (never at import: only one process at a
time may load the TPU library), and every test of this kind lives in
this one file so that a single test worker loads it.
"""
import os

import pytest

import jax
import jax.numpy as jnp

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # repro-lint: disable=except-breadth (any failure to describe the chip means these tests cannot run here)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_tinyllama_decode_step_full_width(one_chip):
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.common import DEFAULT_RC
    cfg = get_config("tinyllama-1.1b")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) == (22, 2048, 5632)
    params = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0), DEFAULT_RC))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 1, 512, DEFAULT_RC))
    compiled = jax.jit(
        lambda p, t, c: lm.decode_step(cfg, p, t, c, DEFAULT_RC)).lower(
        _shapes(params, one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        _shapes(cache, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 4e9          # f32 1.1B params
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_tinyllama_decode_on_step_params_converts_no_weight(one_chip):
    """Fed ``lm.step_params``' bf16 copy, the compiled decode step holds
    no conversion of the stacked layer weights and no per-step copy of
    them; fed the f32 tree, it converts them all on each call.  (The
    head's f32 multiply inside its matrix-vector fusion is XLA's own
    precision handling of a bf16 product, on either tree.)"""
    import re
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.common import DEFAULT_RC
    cfg = get_config("tinyllama-1.1b")
    params = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0), DEFAULT_RC))
    cast = jax.eval_shape(lambda p: lm.step_params(cfg, p, DEFAULT_RC),
                          params)
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 1, 512, DEFAULT_RC))
    weights = {",".join(map(str, a.shape)) for a in
               jax.tree_util.tree_leaves(cast["blocks"])
               if a.dtype == jnp.bfloat16}

    def compile_for(tree):
        return jax.jit(
            lambda p, t, c: lm.decode_step(cfg, p, t, c, DEFAULT_RC)).lower(
            _shapes(tree, one_chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
            _shapes(cache, one_chip)).compile()

    def weight_converts(compiled):
        return [s for s in re.findall(r"= bf16\[([0-9,]+)\]\S* convert\(",
                                      compiled.as_text()) if s in weights]

    before, after = compile_for(params), compile_for(cast)
    assert len(weight_converts(before)) >= 7         # wq wk wv wo w1 w2 w3
    assert before.memory_analysis().temp_size_in_bytes > 1e9
    assert weight_converts(after) == []
    assert after.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def _decode_attention():
    from repro.kernels.decode_attention import decode_attention_tpu
    return decode_attention_tpu, [((1, 32, 64), jnp.bfloat16),
                                  ((1, 4, 512, 64), jnp.bfloat16),
                                  ((1, 4, 512, 64), jnp.bfloat16),
                                  ((), jnp.int32)], dict(block_s=512)


def _flash_attention():
    from repro.kernels.flash_attention import flash_attention_tpu
    return flash_attention_tpu, [((1, 32, 2048, 64), jnp.bfloat16),
                                 ((1, 4, 2048, 64), jnp.bfloat16),
                                 ((1, 4, 2048, 64), jnp.bfloat16)], {}


def _rglru_scan():
    from repro.kernels.rglru_scan import rglru_scan_tpu
    return rglru_scan_tpu, [((2, 512, 2560), jnp.float32),
                            ((2, 512, 2560), jnp.float32),
                            ((2, 2560), jnp.float32)], {}


def _systolic_gemm():
    from repro.kernels.systolic_gemm import systolic_gemm
    return systolic_gemm, [((2048, 2048), jnp.bfloat16),
                           ((2048, 5632), jnp.bfloat16)], {}


@pytest.mark.parametrize("make", [_decode_attention, _flash_attention,
                                  _rglru_scan, _systolic_gemm],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_pallas_kernel_compiles_to_a_tpu_kernel(one_chip, make):
    kernel, args, kw = make()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in args]
    compiled = jax.jit(lambda *a: kernel(*a, **kw)).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jit_engine_lockstep_compiles_under_x64(one_chip):
    from repro.core import Policy
    from repro.core import simulator_jit as sj
    from harness import LIB, fig8_corpus
    tasksets, seeds = fig8_corpus((0.7,), 2, 4)
    policy = Policy.mesc()
    duration = 2e5
    b = sj._VecBatch(tasksets, LIB, policy, seeds=seeds, duration=duration,
                     overrun_prob=0.3, cf=2.0)
    run = sj._compiled_run(policy.use_banks, policy.drop_lo_in_hi,
                           policy.preemption, True, sj._PRUNE_STALE)
    with jax.enable_x64(True):
        tb = sj._tables(b, seeds)
        sc = {"t_sr": jnp.float64(policy.t_sr),
              "overrun_prob": jnp.float64(0.3), "cf": jnp.float64(2.0),
              "duration": jnp.float64(duration),
              "max_steps": jnp.int64(sj._max_steps(b, duration))}
        c0 = sj._carry0(b, seeds, 16)
        compiled = run.lower(_shapes(tb, one_chip), _shapes(sc, one_chip),
                             _shapes(c0, one_chip)).compile()
    text = compiled.as_text()
    assert "while" in text
    assert sj.while_body_kernels(text) > 0


def _hlo_ops(text):
    """``{name: (dtype, dims, opcode, operands, called)}`` for every
    array-valued instruction, and ``{computation: root name}``."""
    import re
    ops, roots, comp = {}, {}, None
    head = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
    inst = re.compile(r"^\s*(ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                      r"([\w-]+)\(([^)]*)\)(.*)$")
    for ln in text.splitlines():
        m = head.match(ln)
        if m:
            comp = m.group(1)
            continue
        m = inst.match(ln)
        if m:
            root, name, dt, dims, op, args, rest = m.groups()
            dims = tuple(int(d) for d in dims.split(",") if d)
            called = re.search(r"calls=%(\S+?)[,\s]", rest + " ")
            ops[name] = (dt, dims, op, re.findall(r"%([^\s,]+)", args),
                         called and called.group(1))
            if root:
                roots[comp] = name
    return ops, roots


@pytest.mark.parametrize("arch,max_len,param_dtype",
                         [("olmo-1b", 2048, jnp.float32),
                          ("phi4-mini-3.8b", 8192, jnp.bfloat16)],
                         ids=["olmo-1b", "phi4-mini-3.8b"])
def test_mesc_decode_writes_its_rows_in_place(one_chip, arch, max_len,
                                              param_dtype):
    """``jit_mesc_decode`` as ``_programs`` builds it, at the benchmark's
    program shapes, on a cache in the stored order the compiler chose:
    the donated cache is aliased whole to the output, no op but the row
    update makes an array of the whole cache's size (no copy, no
    relayout), every such update writes one row, no select runs over a
    layer's slice of the cache, and the cache goes in and out in its
    plain layout (a non-default one is lost when the executable comes
    back from JAX's persistent compilation cache)."""
    import math
    from repro.configs import get_config
    from repro.core.serving import _programs
    from repro.models import lm
    from repro.models.common import RuntimeConfig
    cfg = get_config(arch)
    rc = RuntimeConfig(param_dtype=param_dtype)
    params = jax.eval_shape(lambda: lm.step_params(
        cfg, lm.init_params(cfg, jax.random.PRNGKey(0), rc), rc))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 1, max_len, rc))
    decode, _ = _programs(cfg, rc, max_len)
    decode.bind(_shapes(params, one_chip), _shapes(cache, one_chip))
    compiled = decode.lower(
        _shapes(params, one_chip), jax.ShapeDtypeStruct((1,), jnp.int32),
        _shapes(jax.eval_shape(decode.stored, cache), one_chip)).compile()
    L, B, S, H, D = cache["ck"].shape
    whole, layer, row = L * B * S * H * D, B * S * H * D, B * H * D
    ops, roots = _hlo_ops(compiled.as_text())
    size = {n: math.prod(o[1]) for n, o in ops.items()}

    def row_update(name):
        dt, _, op, args, called = ops[name]
        if op == "fusion":
            return row_update(roots[called])
        return op == "dynamic-update-slice" and size[args[1]] == row

    big = {n: o[2] for n, o in ops.items()
           if o[0] == "bf16" and size[n] == whole}
    moved = {n: op for n, op in big.items()
             if op not in ("parameter", "get-tuple-element", "bitcast")
             and not row_update(n)}
    assert sum(row_update(n) for n in big) >= 2            # K and V
    assert moved == {}
    assert [n for n, o in ops.items()
            if o[2] == "select" and size[n] == layer] == []
    formats = [compiled.input_formats[0][2], compiled.output_formats[1]]
    assert all(f.layout.major_to_minor == tuple(range(len(a.shape)))
               for c in formats for f, a in zip(
                   jax.tree.leaves(c), jax.tree.leaves(cache)))
    mem = compiled.memory_analysis()
    kv = 2 * whole * 2
    assert kv <= mem.alias_size_in_bytes < kv + 1024       # + pos
    assert mem.temp_size_in_bytes < 64 * 2 ** 20

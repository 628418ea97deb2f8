"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward + one train step on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.data import batch_for_arch
from repro.models import lm
from repro.models.common import CPU_RC
from repro.optim import OptConfig, init_opt_state
from repro.runtime.trainer import make_train_step

ALL_ARCHS = sorted(ARCHS)


def _smoke_batch(cfg, B=2, S=16, seed=0):
    return {k: jnp.asarray(v)
            for k, v in batch_for_arch(cfg, S, B, step=0, seed=seed).items()}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch + "-smoke")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), CPU_RC)
    batch = _smoke_batch(cfg)
    logits, _ = lm.forward(cfg, params, batch, CPU_RC)
    S = 16
    if cfg.family == "audio":
        assert logits.shape == (2, S, cfg.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (2, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_runs_and_finite(arch):
    cfg = get_config(arch + "-smoke")
    opt_cfg = OptConfig(warmup_steps=2, decay_steps=10)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), CPU_RC)
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, CPU_RC, opt_cfg))
    params, opt, metrics = step(params, opt, _smoke_batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    leaves = jax.tree_util.tree_leaves(params)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_metadata(arch):
    cfg = ARCHS[arch]
    assert cfg.param_count() > 0
    assert cfg.active_param_count() <= cfg.param_count()

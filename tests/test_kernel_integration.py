"""Kernels wired into the system: the Pallas paths must agree with the
XLA/jnp paths inside the actual models."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

KEY = jax.random.PRNGKey(0)


def test_lm_forward_pallas_attention_matches_xla():
    from repro.configs import get_arch
    from repro.models import transformer_lm as M
    from repro.models.params import init_params
    arch = get_arch("llama3_2_1b", reduced=True)
    cfg = dataclasses.replace(arch.cfg, remat=False)
    params = init_params(KEY, M.param_specs(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab,
                              jnp.int32)
    lx, _, _ = M.forward(params, cfg, toks)
    cfg_p = dataclasses.replace(cfg, attention_impl="pallas")
    lp, _, _ = M.forward(params, cfg_p, toks)
    a = np.asarray(jax.nn.softmax(lx, -1), np.float32)
    b = np.asarray(jax.nn.softmax(lp, -1), np.float32)
    np.testing.assert_allclose(a, b, atol=0.05)


def test_swa_pallas_matches_xla():
    from repro.configs import get_arch
    from repro.models import transformer_lm as M
    from repro.models.params import init_params
    arch = get_arch("mixtral_8x22b", reduced=True)
    cfg = dataclasses.replace(arch.cfg, remat=False)
    params = init_params(KEY, M.param_specs(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, cfg.vocab,
                              jnp.int32)
    lx, _, _ = M.forward(params, cfg, toks)
    cfg_p = dataclasses.replace(cfg, attention_impl="pallas")
    lp, _, _ = M.forward(params, cfg_p, toks)
    a = np.asarray(jax.nn.softmax(lx, -1), np.float32)
    b = np.asarray(jax.nn.softmax(lp, -1), np.float32)
    np.testing.assert_allclose(a, b, atol=0.05)


def test_quality_transfer_kernel_path_in_core():
    """Kernel and jnp paths agree on the whole frame: both edge-pad the
    anchor, so border blocks that reach past the frame match too."""
    from repro.core.quality_transfer import transfer_frame
    H, W = 64, 96
    ks = jax.random.split(KEY, 3)
    anchor = jax.random.uniform(ks[0], (H, W), jnp.float32) * 255
    mv = jax.random.randint(ks[1], (H // 16, W // 16, 2), -8, 9, jnp.int32)
    resid = jax.random.normal(ks[2], (H, W), jnp.float32) * 4
    a = transfer_frame(anchor, mv, resid, use_kernel=False)
    b = transfer_frame(anchor, mv, resid, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)

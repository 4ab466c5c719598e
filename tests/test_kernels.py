"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash import flash_attention
from repro.kernels.linattn import rwkv_linattn_pallas, rwkv_linattn_ref
from repro.kernels.sdca import sdca_epoch_pallas, sdca_epoch_ref
from repro.kernels.svrg import svrg_inner_pallas, svrg_inner_ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("n_p,m_q,steps", [(8, 8, 8), (24, 16, 50),
                                           (64, 128, 64), (17, 9, 33)])
@pytest.mark.parametrize("loss", ["hinge", "squared"])
@pytest.mark.parametrize("beta", [None, "m_q"])
def test_sdca_kernel(n_p, m_q, steps, loss, beta):
    x = jnp.asarray(RNG.normal(size=(n_p, m_q)), jnp.float32)
    y = jnp.asarray(np.sign(RNG.normal(size=n_p)) + 0.0, jnp.float32)
    y = jnp.where(y == 0, 1.0, y)
    mask = jnp.ones((n_p,)).at[-2:].set(0.0)
    a0 = jnp.asarray(RNG.uniform(0, 0.5, n_p), jnp.float32) * (y > 0)
    w0 = jnp.asarray(RNG.normal(size=m_q) * 0.1, jnp.float32)
    idx = jnp.asarray(RNG.integers(0, n_p, steps), jnp.int32)
    # beta ~ ||x_i||^2 keeps the step-size-variant recursion contractive
    kw = dict(lam=0.2, n=200, Q=3, loss=loss,
              beta=float(m_q) if beta else None)
    da_r, w_r = sdca_epoch_ref(x, y, mask, a0, w0, idx, **kw)
    da_p, w_p = sdca_epoch_pallas(x, y, mask, a0, w0, idx, **kw)
    np.testing.assert_allclose(np.asarray(da_p), np.asarray(da_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_p,m_sub,L", [(16, 8, 20), (40, 32, 64),
                                         (13, 5, 11)])
@pytest.mark.parametrize("loss", ["hinge", "squared"])
def test_svrg_kernel(n_p, m_sub, L, loss):
    x = jnp.asarray(RNG.normal(size=(n_p, m_sub)), jnp.float32)
    y = jnp.asarray(np.sign(RNG.normal(size=n_p)), jnp.float32)
    y = jnp.where(y == 0, 1.0, y)
    mask = jnp.ones((n_p,))
    wa = jnp.asarray(RNG.normal(size=m_sub) * 0.2, jnp.float32)
    za = x @ wa + jnp.asarray(RNG.normal(size=n_p) * 0.1, jnp.float32)
    mu = jnp.asarray(RNG.normal(size=m_sub) * 0.05, jnp.float32)
    idx = jnp.asarray(RNG.integers(0, n_p, L), jnp.int32)
    kw = dict(lam=0.1, eta=0.03, loss=loss)
    w_r = svrg_inner_ref(x, y, mask, za, wa, mu, idx, **kw)
    w_p = svrg_inner_pallas(x, y, mask, za, wa, mu, idx, **kw)
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_r),
                               rtol=1e-5, atol=1e-5)


def _solver_kernel_calls():
    """Each solver kernel at a tiny size: (its name, fn, args)."""
    from repro.kernels.sdca import sdca_epoch_sparse_pallas
    from repro.kernels.svrg import svrg_inner_sparse_pallas
    n_p, m_q, k, steps = 16, 8, 8, 4
    f32 = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    cols = jnp.zeros((n_p, k), jnp.int32)
    idx = jnp.arange(steps, dtype=jnp.int32)
    return {
        "sdca_dense": (lambda *a: sdca_epoch_pallas(*a, lam=0.2, n=64, Q=2),
                       (f32(n_p, m_q), f32(n_p), f32(n_p), f32(n_p),
                        f32(m_q), idx)),
        "sdca_sparse": (lambda *a: sdca_epoch_sparse_pallas(
            *a, lam=0.2, n=64, Q=2),
            (cols, f32(n_p, k), f32(n_p), f32(n_p), f32(n_p), f32(m_q),
             idx)),
        "svrg_dense": (lambda *a: svrg_inner_pallas(*a, lam=0.1, eta=0.03),
                       (f32(n_p, m_q), f32(n_p), f32(n_p), f32(n_p),
                        f32(m_q), f32(m_q), idx)),
        "svrg_sparse": (lambda *a: svrg_inner_sparse_pallas(
            *a, lam=0.1, eta=0.03),
            (cols, f32(n_p, k), f32(n_p), f32(n_p), f32(n_p), f32(m_q),
             f32(m_q), idx)),
    }


@pytest.mark.parametrize("name", ["sdca_dense", "sdca_sparse",
                                  "svrg_dense", "svrg_sparse"])
def test_solver_kernel_name_in_lowered_program(name):
    """Each solver kernel carries its own name into the program it is
    lowered into (the op names of its HLO), so a profile can find it."""
    fn, args = _solver_kernel_calls()[name]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert f"/{name}/pallas_call" in text
    others = {"sdca_dense", "sdca_sparse", "svrg_dense", "svrg_sparse"}
    assert not any(f"/{o}/" in text for o in others - {name})


@pytest.mark.parametrize("B,S,H,KV,D", [(2, 128, 4, 2, 32), (1, 256, 2, 2, 64),
                                        (2, 64, 8, 1, 16)])
@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel(B, S, H, KV, D, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, KV, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, KV, D)), dtype)
    o_ref = flash_attention(q, k, v, causal=True, window=window,
                            backend="ref")
    o_pal = flash_attention(q, k, v, causal=True, window=window,
                            backend="pallas", block_q=64, block_k=64)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("BH,S,D,chunk", [(2, 64, 16, 16), (3, 128, 32, 32),
                                          (1, 256, 64, 64), (2, 96, 16, 32)])
def test_linattn_kernel(BH, S, D, chunk):
    r = jnp.asarray(RNG.normal(size=(BH, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(BH, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(BH, S, D)), jnp.float32)
    logw = -jnp.exp(jnp.asarray(RNG.normal(size=(BH, S, D)), jnp.float32))
    u = jnp.asarray(RNG.normal(size=(D,)), jnp.float32)
    o_r, s_r = rwkv_linattn_ref(r, k, v, logw, u)
    o_p, s_p = rwkv_linattn_pallas(r, k, v, logw, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                               rtol=2e-4, atol=2e-4)


def test_linattn_extreme_decay_no_overflow():
    """All-negative exponent formulation: no NaN/Inf even at w -> 0."""
    BH, S, D = 1, 64, 16
    r = jnp.ones((BH, S, D)) * 0.5
    k = jnp.ones((BH, S, D)) * 0.5
    v = jnp.ones((BH, S, D))
    logw = jnp.full((BH, S, D), -50.0)   # decay ~ e^-50 per step
    u = jnp.ones((D,))
    o_p, s_p = rwkv_linattn_pallas(r, k, v, logw, u, chunk=16)
    assert bool(jnp.all(jnp.isfinite(o_p))) and bool(
        jnp.all(jnp.isfinite(s_p)))

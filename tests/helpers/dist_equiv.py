"""Run shard_map engines on 8 forced host devices and compare to simulated.

Executed as a subprocess by tests (device count must be set before jax init).
Prints max-abs diffs as `name diff` lines; exits nonzero on failure.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import *
from repro.data import make_svm_data
from repro.launch.mesh import make_mesh

def main():
    P_, Q_ = 4, 2
    X, y = make_svm_data(400, 120, seed=1)
    lam = 1.0
    data = partition(X, y, P=P_, Q=Q_)
    mesh = make_mesh((P_, Q_), ("data", "model"))

    Xd, yd = np.asarray(data.dense()[0]), np.asarray(data.dense()[1])
    n_pad, m_pad = P_ * data.n_p, Q_ * data.m_q
    Xp = np.zeros((n_pad, m_pad), np.float32); Xp[:400, :120] = Xd
    yp = np.zeros((n_pad,), np.float32); yp[:400] = yd
    maskp = np.zeros((n_pad,), np.float32); maskp[:400] = 1.0
    Xp, yp, maskp = jnp.array(Xp), jnp.array(yp), jnp.array(maskp)

    fails = 0
    def check(name, a, b, tol=2e-4):
        nonlocal fails
        d = float(jnp.abs(a - b).max())
        print(f"{name} {d:.3e}")
        if not d < tol:
            fails += 1

    cfg = D3CAConfig(lam=lam, outer_iters=3)
    w_sim, a_sim = d3ca_simulated("hinge", data, cfg)
    w_dist, a_dist = d3ca_distributed("hinge", mesh, Xp, yp, maskp, cfg)
    check("d3ca_w", w_sim, w_dist[:120]); check("d3ca_alpha", a_sim, a_dist[:400])

    rcfg = RADiSAConfig(lam=lam, gamma=0.02, outer_iters=3)
    check("radisa_w", radisa_simulated("hinge", data, rcfg),
          radisa_distributed("hinge", mesh, Xp, yp, maskp, rcfg)[:120])

    rcfg = RADiSAConfig(lam=lam, gamma=0.02, outer_iters=3, variant="avg")
    check("radisa_avg_w", radisa_simulated("hinge", data, rcfg),
          radisa_distributed("hinge", mesh, Xp, yp, maskp, rcfg)[:120])

    acfg = ADMMConfig(lam=lam, rho=lam, outer_iters=5)
    check("admm_w", admm_simulated("hinge", data, acfg),
          admm_distributed("hinge", mesh, Xp, yp, maskp, acfg)[:120])

    # multi-pod: the same P=4 observation split expressed as a collapsed
    # ("pod","data") tuple axis on a (2,2,2) mesh must reproduce the flat
    # (4,2) mesh result bit-for-bit (same grid, same fold_in indices)
    from jax.sharding import NamedSharding, PartitionSpec as SP
    from repro.core.losses import get_loss
    from repro.core.d3ca import make_d3ca_step
    from repro.core.radisa import make_radisa_step
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    daxes = ("pod", "data")
    loss = get_loss("hinge")
    key0 = jax.random.PRNGKey(0)

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh3, spec))

    x3 = put(Xp, SP(daxes, "model"))
    y3, m3 = put(yp, SP(daxes)), put(maskp, SP(daxes))

    cfg = D3CAConfig(lam=lam, outer_iters=3)
    step2 = make_d3ca_step(loss, mesh, cfg, n=n_pad, n_p=data.n_p)
    step3 = make_d3ca_step(loss, mesh3, cfg, n=n_pad, n_p=data.n_p,
                           data_axis=daxes)
    a2, w2 = jnp.zeros((n_pad,)), jnp.zeros((m_pad,))
    a3 = put(jnp.zeros((n_pad,)), SP(daxes))
    w3 = put(jnp.zeros((m_pad,)), SP("model"))
    for t in range(1, 4):
        a2, w2 = step2(t, key0, Xp, yp, maskp, a2, w2)
        a3, w3 = step3(t, key0, x3, y3, m3, a3, w3)
    check("d3ca_multipod_w", w2, w3, tol=1e-6)
    check("d3ca_multipod_alpha", a2, a3, tol=1e-6)

    rcfg = RADiSAConfig(lam=lam, gamma=0.02, outer_iters=3)
    rstep2 = make_radisa_step(loss, mesh, rcfg, n=n_pad, n_p=data.n_p,
                              m_q=data.m_q)
    rstep3 = make_radisa_step(loss, mesh3, rcfg, n=n_pad, n_p=data.n_p,
                              m_q=data.m_q, data_axis=daxes)
    rw2 = jnp.zeros((m_pad,))
    rw3 = put(jnp.zeros((m_pad,)), SP("model"))
    for t in range(1, 4):
        rw2 = rstep2(t, key0, Xp, yp, maskp, rw2)
        rw3 = rstep3(t, key0, x3, y3, m3, rw3)
    check("radisa_multipod_w", rw2, rw3, tol=1e-6)

    raise SystemExit(fails)

if __name__ == "__main__":
    main()

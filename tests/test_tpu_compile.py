"""Compile the solver kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jax, and it compiles for a chip that
is described and not attached: each test lowers a kernel (or a whole
simulated-engine step) at the paper's sizes with its arguments placed on
one device of a described ``v5e:2x2`` host, compiles it, and checks that
the program holds the Pallas kernel (``tpu_custom_call``); the solve
loop's objectives on the dense blocks are checked for float32
throughout.  It catches what interpret mode cannot: block shapes the
(8, 128) tiling refuses, and kernels that overflow on-chip memory; and
that each kernel carries its name into the compiled program (a lone
kernel's op is ``%sdca_dense.1 = ... custom-call``).  Nothing runs, so
nothing here says anything about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
pytest-xdist workers import every test file.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]

#: paper Part 1 "4x2": 2,000 x 3,000 blocks; RADiSA's sub-block is m_q / P
PART1 = dict(n=8000, P=4, Q=2, n_p=2000, m_q=3000)
#: real-sim-shaped cell on a 4x2 grid (72,309 x 20,958 at 2.4e-3), as
#: ``partition_sparse`` cuts it: n_p = 72,312 / 4 is not a multiple of 8,
#: so the last (8, k) ELL tile and the last dense tile run past the
#: array; k = 56 is the widest row's nonzeros in one feature block at
#: seed 0; m_q = 20,958 / 2 for D3CA, and 20,960 / 2 for RADiSA, which
#: pads the features to a multiple of P * Q
REALSIM = dict(n=72309, n_p=18078, k=56, m_q=10479, m_q_radisa=10480)
#: the paper's weak-scaling deployment on a 2x2 mesh (80,000 x 10,000 at
#: 1%, ``chipbench/configs/weak_1pct_2x2_mesh.json``): one 40,000 x 5,000
#: block a chip; k = 88 is the widest row's nonzeros in one feature block
#: of the configuration's sparsity pattern, rounded up to 8
WEAK = dict(n=80000, P=2, Q=2, n_p=40000, m_q=5000, k=88)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_sdca_dense_compiles(spec):
    from repro.kernels.sdca import sdca_epoch_pallas
    n_p, m_q = PART1["n_p"], PART1["m_q"]

    def epoch(x, y, mask, a0, w0, idx, beta):
        return sdca_epoch_pallas(x, y, mask, a0, w0, idx, lam=1e-2,
                                 n=PART1["n"], Q=PART1["Q"], beta=beta,
                                 interpret=False)

    text = compiled_text(epoch, spec((n_p, m_q)), spec((n_p,)),
                         spec((n_p,)), spec((n_p,)), spec((m_q,)),
                         spec((n_p,), jnp.int32), spec(()))
    assert "tpu_custom_call" in text
    assert "%sdca_dense" in text          # the kernel's op carries its name


def test_svrg_dense_compiles(spec):
    from repro.kernels.svrg import svrg_inner_pallas
    n_p, m_sub = PART1["n_p"], PART1["m_q"] // PART1["P"]

    def inner(x, y, mask, z, wa, mu, idx, eta):
        return svrg_inner_pallas(x, y, mask, z, wa, mu, idx, lam=1e-2,
                                 eta=eta, interpret=False)

    text = compiled_text(inner, spec((n_p, m_sub)), spec((n_p,)),
                         spec((n_p,)), spec((n_p,)), spec((m_sub,)),
                         spec((m_sub,)), spec((n_p,), jnp.int32), spec(()))
    assert "tpu_custom_call" in text
    assert "%svrg_dense" in text          # the kernel's op carries its name


def test_sdca_sparse_compiles(spec):
    from repro.kernels.sdca import sdca_epoch_sparse_pallas
    n_p, k, m_q = REALSIM["n_p"], REALSIM["k"], REALSIM["m_q"]

    def epoch(cols, vals, y, mask, a0, w0, idx):
        return sdca_epoch_sparse_pallas(cols, vals, y, mask, a0, w0, idx,
                                        lam=1e-4, n=REALSIM["n"], Q=2,
                                        interpret=False)

    text = compiled_text(epoch, spec((n_p, k), jnp.int32), spec((n_p, k)),
                         spec((n_p,)), spec((n_p,)), spec((n_p,)),
                         spec((m_q,)), spec((n_p,), jnp.int32))
    assert "tpu_custom_call" in text
    assert "%sdca_sparse" in text          # the kernel's op carries its name


def test_sdca_sparse_compiles_at_weak_scaling_size(spec):
    """The sparse kernel on one chip's block of the weak-scaling mesh: a
    40,000-step coordinate order in scalar memory, 88-wide ELL rows."""
    from repro.kernels.sdca import sdca_epoch_sparse_pallas
    n_p, k, m_q = WEAK["n_p"], WEAK["k"], WEAK["m_q"]

    def epoch(cols, vals, y, mask, a0, w0, idx):
        return sdca_epoch_sparse_pallas(cols, vals, y, mask, a0, w0, idx,
                                        lam=1.0, n=WEAK["n"], Q=WEAK["Q"],
                                        interpret=False)

    text = compiled_text(epoch, spec((n_p, k), jnp.int32), spec((n_p, k)),
                         spec((n_p,)), spec((n_p,)), spec((n_p,)),
                         spec((m_q,)), spec((n_p,), jnp.int32))
    assert "%sdca_sparse" in text


def test_d3ca_shard_map_step_compiles_on_four_chips(topo, monkeypatch):
    """One whole outer step of the shard_map engine on the described 2x2
    mesh at the weak-scaling size: the sparse kernel on every chip, the
    two declared collectives as all-reduces over the chips, each op under
    its named scope."""
    import re

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import repro.kernels
    from repro.core.d3ca import D3CAConfig, d3ca_cell_program
    from repro.core.engines import mesh_program
    from repro.core.losses import get_loss
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    n, Pn, Qn = WEAK["n"], WEAK["P"], WEAK["Q"]
    n_p, m_q, k = WEAK["n_p"], WEAK["m_q"], WEAK["k"]
    mesh = Mesh(np.array(topo.devices).reshape(Pn, Qn), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def placed(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    cell = d3ca_cell_program(get_loss("hinge"), D3CAConfig(lam=1.0), n=n,
                             n_p=n_p, m_q=m_q, sparse=True,
                             local_backend="pallas")
    data = (placed((2,), jnp.uint32),
            placed((Pn * n_p, Qn * k), jnp.int32, "data", "model"),
            placed((Pn * n_p, Qn * k), jnp.float32, "data", "model"),
            placed((Pn * n_p,), jnp.float32, "data"),
            placed((Pn * n_p,), jnp.float32, "data"))
    state = (placed((Pn * n_p,), jnp.float32, "data"),
             placed((Qn * m_q,), jnp.float32, "model"))
    step, comm0, acct = mesh_program(cell, mesh, data, state)
    assert acct["bytes_per_step"] == 4 * (n_p + m_q) * 4
    text = step.lower(jax.ShapeDtypeStruct((), jnp.int32), data,
                      (state, comm0)).compile().as_text()
    assert "%sdca_sparse" in text
    reduces = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    scopes = sorted(re.search(r'op_name="[^"]*/(repro\.comm\.\w+)/',
                              line).group(1) for line in reduces)
    assert scopes == ["repro.comm.dalpha", "repro.comm.w_contrib"]
    # dalpha over each row's two chips (model), w_contrib over each
    # column's (data)
    assert any("f32[40000]" in line and "{{0,1},{2,3}}" in line
               for line in reduces)
    assert any("f32[5000]" in line and "{{0,2},{1,3}}" in line
               for line in reduces)
    assert "repro.d3ca.map/repro.comm.w_contrib" in text


def test_svrg_sparse_compiles(spec):
    from repro.kernels.svrg import svrg_inner_sparse_pallas
    n_p, k, m_sub = REALSIM["n_p"], REALSIM["k"], REALSIM["m_q_radisa"] // 4

    def inner(cols, vals, y, mask, z, wa, mu, idx, eta, lo):
        return svrg_inner_sparse_pallas(cols, vals, y, mask, z, wa, mu, idx,
                                        lam=1e-4, eta=eta, lo=lo,
                                        interpret=False)

    text = compiled_text(inner, spec((n_p, k), jnp.int32), spec((n_p, k)),
                         spec((n_p,)), spec((n_p,)), spec((n_p,)),
                         spec((m_sub,)), spec((m_sub,)),
                         spec((n_p,), jnp.int32), spec(()),
                         spec((), jnp.int32))
    assert "tpu_custom_call" in text
    assert "%svrg_sparse" in text          # the kernel's op carries its name


def test_d3ca_simulated_step_compiles(spec, monkeypatch):
    """One whole outer step of the simulated engine (the vmapped 4x2
    grid) with the Pallas local solver, at Part-1 size."""
    import repro.kernels
    from repro.core.d3ca import D3CAConfig, d3ca_cell_program
    from repro.core.engines import grid_program
    from repro.core.losses import get_loss
    # the program asks the backend, which is the CPU here
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    P, Q, n_p, m_q = PART1["P"], PART1["Q"], PART1["n_p"], PART1["m_q"]
    cell = d3ca_cell_program(get_loss("hinge"), D3CAConfig(lam=1e-2),
                             n=PART1["n"], n_p=n_p, m_q=m_q,
                             local_backend="pallas")
    step = grid_program(cell, P, Q)
    data = (spec((2,), jnp.uint32), spec((P, Q, n_p, m_q)), spec((P, n_p)),
            spec((P, n_p)))
    state = (spec((P, n_p)), spec((Q, m_q)))
    text = step.lower(spec((), jnp.int32), data, state).compile().as_text()
    assert "tpu_custom_call" in text
    # under the grid's vmap the op keeps a call's name; the kernel's
    # name is in its op_name, vmap(vmap(sdca_dense))
    assert "(sdca_dense)" in text


def test_chip_smoke_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("which", ["primal", "dual"])
def test_block_objectives_compile_in_float32(spec, which):
    """The solve loop's objectives on Part 1's dense blocks (4, 2, 2000,
    3000) compile in float32 throughout (no bfloat16 product) and read
    the blocks where they lie (no copy of them in the program)."""
    from repro.core.losses import get_loss
    from repro.core.partition import block_dual_objective, block_objective
    P, Q, n_p, m_q = PART1["P"], PART1["Q"], PART1["n_p"], PART1["m_q"]
    evaluate, size = ((block_objective, Q * m_q) if which == "primal"
                      else (block_dual_objective, PART1["n"]))
    text = evaluate.lower(get_loss("hinge"), spec((P, Q, n_p, m_q)),
                          spec((P, n_p)), spec((P, n_p)), spec((size,)),
                          lam=1e-2, n=PART1["n"]).compile().as_text()
    assert "bf16" not in text
    entry = text[text.index("ENTRY"):]
    blocks = [line for line in entry.splitlines()
              if "= f32[4,2,2000,3000]" in line]
    assert len(blocks) == 1 and "parameter(0)" in blocks[0]

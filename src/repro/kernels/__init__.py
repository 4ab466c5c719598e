"""Pallas TPU kernels for the perf-critical hot spots.

  sdca/    local dual coordinate ascent epoch (paper Algorithm 2)
  svrg/    RADiSA inner loop (paper Algorithm 3 steps 7-10)
  flash/   blockwise causal/windowed attention (LM stack)
  linattn/ chunked RWKV6 data-dependent-decay linear attention

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle).  ``lanes.py`` holds the tile and
lane-dense layouts the solver kernels share.  Every kernel takes
``interpret=None``, which :func:`resolve_interpret` turns into
:func:`default_interpret`: compiled on a TPU, the Pallas interpreter on
any other backend (the CPU test suite).
"""
import jax as _jax


def default_interpret() -> bool:
    """Interpret mode everywhere but real TPUs (where kernels compile).
    The single source of truth for every kernel call site."""
    return _jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``interpret`` if given, else :func:`default_interpret` (looked up
    at call time, so a test can patch it to compile for a described
    TPU from a CPU process)."""
    return default_interpret() if interpret is None else bool(interpret)

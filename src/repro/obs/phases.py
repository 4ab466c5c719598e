"""Per-phase wall-clock attribution for engine programs, for a solve
given a metrics registry.

An outer iteration's wall-clock decomposes into

  * ``local_s`` -- the cell-local solve (the Pallas/ref kernel work),
  * ``comm_s``  -- the declared collectives (wire + codec encode/decode),
  * ``host_s``  -- host bookkeeping (objective/gap eval, scheduling).

Nothing inside a jitted step can be timed from the host, so the split
is measured *differentially*: every :class:`~repro.core.engines`
program built since the telemetry PR also carries ``local_step`` -- the
SAME cell program with every collective executed cell-locally
(:class:`~repro.core.comm.LocalComm`: psum/pmean return the cell's own
contribution, allgather broadcasts it) -- which costs the local math
without the reductions.  ``comm_s = step_s - local_step_s`` is then the
communication share.  Per-collective device times come from a profile
of the step, where each collective is its own op.

:func:`calibrate_phases` measures the split once per program (a few
timed steps of each variant); :meth:`PhaseSplit.attribute` then prices
every subsequent iteration from its measured ``step_s`` alone, so the
steady-state tracing overhead stays at host-timer resolution.

Overlap-aware attribution: programs built by the overlap engine
(``EngineProgram.overlap`` with ``staleness = tau > 0``) consume each
reduction tau steps after dispatch, so up to tau steps of local solve
can hide the wire.  For those programs :meth:`PhaseSplit.attribute`
further splits ``comm_s`` into ``comm_hidden_s`` (overlapped with the
local solve, up to ``tau * local_s``) and ``comm_exposed_s`` (the
remainder that extends the critical path) via
:func:`repro.core.comm_model.overlap_split`.

:func:`bench_codecs` microbenchmarks each compressed collective's
encode/decode path on a representative payload (per-codec cost the
fig_compress sweep reports next to the byte savings).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional


def _timeit(fn, reps: int) -> float:
    import jax
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)      # min: calibration wants the noise floor


@dataclasses.dataclass(frozen=True)
class PhaseSplit:
    """Calibrated local/comm split of one engine program."""

    #: fraction of a step spent in the cell-local solve (0..1)
    local_frac: float
    #: calibration measurements, for provenance
    step_s: float
    local_s: float
    #: reduction delay tau of the program (0 = synchronous)
    staleness: int = 0
    #: True for overlap-engine programs: comm_s further splits into
    #: hidden (overlapped with local solve) and exposed shares
    overlap: bool = False

    def attribute(self, step_s: float) -> dict:
        """Split one measured step duration into phases::

            {"local_s": ..., "comm_s": ...,
             ["comm_hidden_s": ..., "comm_exposed_s": ...]}
        """
        local = step_s * self.local_frac
        comm = max(step_s - local, 0.0)
        out = {"local_s": local, "comm_s": comm}
        if self.overlap and self.staleness > 0:
            from repro.core.comm_model import overlap_split
            out.update(overlap_split(comm, local, self.staleness))
        return out


def calibrate_phases(prog, *, reps: int = 3) -> Optional[PhaseSplit]:
    """Measure a program's local/comm split (see module docstring).

    Returns None when the program carries no ``local_step`` (legacy
    programs built outside the generic executors) -- callers then
    record only the undivided ``step_s``.  Warmup compiles both variants;
    the calibration steps are pure (engine state is functional), so a
    calibrated solve returns bit-identical iterates.
    """
    local_step = getattr(prog, "local_step", None)
    if local_step is None:
        return None
    state = prog.state
    import jax
    donated = bool(getattr(prog, "donated", False))
    if donated:
        # the overlap engine's jitted step donates its state operand on
        # accelerators; re-stepping from the saved state0 would read
        # freed buffers, so every calibration call gets its own copy
        # (made outside the timed region)
        import jax.numpy as jnp
        copies = [jax.tree_util.tree_map(jnp.copy, state)
                  for _ in range(reps + 1)]
        pool = iter(copies)
        jax.block_until_ready(prog.step(1, next(pool)))   # compile + warm
        step_s = _timeit(lambda: prog.step(1, next(pool)), reps)
    else:
        jax.block_until_ready(prog.step(1, state))        # compile + warm
        step_s = _timeit(lambda: prog.step(1, state), reps)
    jax.block_until_ready(local_step(1, state))
    local_s = _timeit(lambda: local_step(1, state), reps)
    local_frac = min(local_s / step_s, 1.0) if step_s > 0 else 1.0
    return PhaseSplit(local_frac=local_frac, step_s=step_s, local_s=local_s,
                      staleness=int(getattr(prog, "staleness", 0)),
                      overlap=bool(getattr(prog, "overlap", False)))


def bench_codecs(policy, acct: dict, *, reps: int = 3) -> Dict[str, float]:
    """Seconds per encode/decode of each *compressed* collective.

    ``policy`` is a CompressionPolicy (duck-typed: ``codec_for(name)``),
    ``acct`` the program's wire accounting, whose per-collective entries
    carry the payload aval (``payload_shape`` / ``payload_dtype``).
    Identity-codec collectives are skipped (their apply is free).
    """
    import jax
    import jax.numpy as jnp
    out: Dict[str, float] = {}
    for name, cell in acct.get("collectives", {}).items():
        codec = policy.codec_for(name)
        if codec.name == "identity" or "payload_shape" not in cell:
            continue
        x = jnp.zeros(tuple(cell["payload_shape"]),
                      jnp.dtype(cell["payload_dtype"]))
        if codec.stateful:
            err = jnp.zeros(x.shape, jnp.float32)
            fn = jax.jit(lambda v, e, c=codec: c.apply(v, e))
            jax.block_until_ready(fn(x, err))
            out[name] = _timeit(lambda: fn(x, err), reps)
        else:
            fn = jax.jit(lambda v, c=codec: c.apply(v))
            jax.block_until_ready(fn(x))
            out[name] = _timeit(lambda: fn(x), reps)
    return out

"""Two-level topology spec and its exact wire accounting.

``Topology`` describes a two-level machine: ``pods`` groups along one
logical axis (default ``"data"``) and an optional cross-pod codec.  A
collective over the pod-split axis is executed hierarchically
(full-precision reduce within the pod, codec-compressed across pods --
what the hierarchical executors in :mod:`repro.core.comm` do), and
:func:`hierarchical_accounting` splits its exact bytes per step into
the two tiers.  How long the exchanges take is read from a device
profile (the ``repro.comm.<name>`` scopes), not modelled here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

__all__ = ["Topology", "as_topology", "hierarchical_accounting"]


@dataclasses.dataclass(frozen=True)
class Topology:
    """Two-level machine model for the hierarchical executors.

    ``pods`` groups along logical ``axis`` (the leading mesh/vmap axis
    of the two-level split); ``codec`` names the cross-pod payload
    codec ("identity" disables compression); ``algo`` ("ring" or
    "tree") is the spec's third field, carried in ``spec`` only.
    ``pods == 1`` is the flat machine (the executors then take the
    ordinary single-psum path).
    """

    pods: int = 1
    codec: str = "identity"
    algo: str = "ring"
    axis: str = "data"

    def __post_init__(self):
        if self.pods < 1:
            raise ValueError(f"pods must be >= 1, got {self.pods}")
        if self.algo not in ("ring", "tree"):
            raise ValueError(f"algo must be 'ring' or 'tree', "
                             f"got {self.algo!r}")

    @classmethod
    def from_spec(cls, spec) -> "Topology":
        """Parse ``"pods=2"``, ``"pods=4:int8"``, ``"pods=2:int8:tree"``
        (codec and algo optional, in that order)."""
        if isinstance(spec, Topology):
            return spec
        if not isinstance(spec, str) or not spec.strip():
            raise ValueError(f"bad topology spec {spec!r}")
        parts = [p.strip() for p in spec.strip().split(":")]
        head = parts[0]
        if not head.startswith("pods="):
            raise ValueError(
                f"bad topology spec {spec!r}: expected 'pods=G[:codec[:algo]]'")
        try:
            pods = int(head[len("pods="):])
        except ValueError:
            raise ValueError(f"bad pod count in topology spec {spec!r}")
        codec, algo = "identity", "ring"
        if len(parts) >= 2 and parts[1]:
            codec = parts[1]
        if len(parts) >= 3 and parts[2]:
            algo = parts[2]
        if len(parts) > 3:
            raise ValueError(f"bad topology spec {spec!r}: too many fields")
        return cls(pods=pods, codec=codec, algo=algo)

    @property
    def spec(self) -> str:
        return f"pods={self.pods}:{self.codec}:{self.algo}"

    def hierarchical(self) -> bool:
        return self.pods > 1


def as_topology(spec) -> Optional[Topology]:
    """None | spec-string | Topology -> Optional[Topology]."""
    if spec is None:
        return None
    return Topology.from_spec(spec)


def _codec_nbytes(codec_name: str, nbytes: float) -> float:
    """Cross-pod payload bytes after the topology codec.  Uses the
    codec registry's per-element payload accounting on a synthetic f32
    vector of the same byte size (collectives here are f32 payloads)."""
    if codec_name in (None, "identity"):
        return nbytes
    from .compress import get_codec
    codec = get_codec(codec_name)
    numel = max(int(round(nbytes / 4.0)), 1)
    return float(codec.payload_nbytes((numel,), "float32"))


def hierarchical_accounting(acct: dict, topology: Optional[Topology],
                            sizes: Dict[str, int]) -> dict:
    """Rewrite a ``wire_accounting`` dict for a two-level topology.

    For each collective over the pod-split axis, the flat bytes become
    an intra-pod stage (full precision, unchanged per-cell bytes) plus
    an inter-pod stage (one codec-compressed contribution per pod).
    Other collectives are unchanged.  Adds ``intra_bytes_per_step`` /
    ``inter_bytes_per_step`` totals so the emitters can report both
    tiers; ``bytes_per_step`` stays the total.
    """
    if topology is None or not topology.hierarchical():
        return acct
    out = {k: v for k, v in acct.items() if k != "collectives"}
    out["collectives"] = {}
    out["topology"] = topology.spec
    total = intra_total = inter_total = 0.0
    for name, c in acct["collectives"].items():
        c = dict(c)
        if c.get("axis") == topology.axis and sizes.get(topology.axis, 1) > 1:
            per_cell = c["payload_bytes_per_cell"]   # post-policy payload
            k_total = sizes[topology.axis]
            pods = topology.pods
            cells = c["cells"]
            other = cells // k_total       # independent reductions in flight
            intra = per_cell * k_total * other
            inter_per_pod = _codec_nbytes(topology.codec, per_cell)
            inter = inter_per_pod * pods * other
            c["intra_bytes_per_step"] = intra
            c["inter_bytes_per_step"] = inter
            c["bytes_per_step"] = intra + inter
            intra_total += intra
            inter_total += inter
        else:
            intra_total += c["bytes_per_step"]
            c["intra_bytes_per_step"] = c["bytes_per_step"]
            c["inter_bytes_per_step"] = 0.0
        total += c["bytes_per_step"]
        out["collectives"][name] = c
    out["bytes_per_step"] = total
    out["intra_bytes_per_step"] = intra_total
    out["inter_bytes_per_step"] = inter_total
    return out

"""Core: the paper's doubly distributed optimization algorithms."""
from .admm import (ADMMConfig, admm_distributed,
                   admm_setup_simulated, admm_simulated)
from .comm import Comm, CommSchedule, OverlapComm, StaleComm, SyncComm
from .comm_model import Topology, as_topology
from .compress import (CompressedComm, CompressionPolicy,
                       CompressionSchedule, as_compression, as_policy,
                       available_codecs, get_codec, wire_accounting)
from .d3ca import (D3CAConfig, d3ca_distributed, d3ca_simulated,
                   make_d3ca_step, make_d3ca_step_sparse)
from .engines import (CellProgram, EngineProgram, comm_accounting, drive,
                      grid_program, mesh_program, prepare_shard_map,
                      prepare_shard_map_sparse)
from .losses import LOSSES, get_loss
from .partition import (DoublyPartitioned, SparseDoublyPartitioned,
                        partition, partition_sparse)
from .radisa import (RADiSAConfig, make_radisa_step, make_radisa_step_sparse,
                     radisa_distributed, radisa_simulated)
from .reference import duality_gap, objective, rel_opt, serial_sdca
from .sfk import SFKConfig, make_sfk_step, sfk_simulated
from .solver import (BLOCK_FORMATS, ENGINES, LOCAL_BACKENDS, SolveResult,
                     Solver, available_solvers, get_solver, register_solver)

__all__ = [
    "ADMMConfig", "admm_distributed", "admm_setup_simulated",
    "admm_simulated",
    "Comm", "CommSchedule", "OverlapComm", "StaleComm", "SyncComm",
    "Topology", "as_topology",
    "CompressedComm", "CompressionPolicy", "CompressionSchedule",
    "as_compression", "as_policy", "available_codecs",
    "get_codec", "wire_accounting",
    "D3CAConfig", "d3ca_distributed", "d3ca_simulated", "make_d3ca_step",
    "make_d3ca_step_sparse",
    "CellProgram", "EngineProgram", "comm_accounting", "drive",
    "grid_program", "mesh_program",
    "prepare_shard_map", "prepare_shard_map_sparse",
    "LOSSES", "get_loss",
    "DoublyPartitioned", "SparseDoublyPartitioned", "partition",
    "partition_sparse",
    "RADiSAConfig", "make_radisa_step", "make_radisa_step_sparse",
    "radisa_distributed", "radisa_simulated",
    "duality_gap", "objective", "rel_opt", "serial_sdca",
    "SFKConfig", "make_sfk_step", "sfk_simulated",
    "BLOCK_FORMATS", "ENGINES", "LOCAL_BACKENDS", "SolveResult", "Solver",
    "available_solvers", "get_solver", "register_solver",
]

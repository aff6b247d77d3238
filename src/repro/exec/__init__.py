"""repro.exec — SPMD mesh execution of the SPARe protocol.

The emulated :class:`~repro.train.trainer.SpareTrainer` proves the
protocol; this package runs it for real: :class:`MeshExecutor` places
the model on a ``(data, model)`` mesh, executes the train step under
``shard_map`` with the §3.1 weighted psum on the wire, and applies
failure masking as pure weight-table updates — zero extra collectives,
zero recompiles per survivor set. Works on any machine via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see README
§repro.exec).
"""
from .equivalence import (
    SurvivorCheck,
    int8_sweep_tolerance,
    recoverable_failure_sets,
    survivor_set_sweep,
    tree_max_rel_err,
)
from .executor import MeshExecutor

__all__ = [
    "MeshExecutor",
    "SurvivorCheck",
    "int8_sweep_tolerance",
    "recoverable_failure_sets",
    "survivor_set_sweep",
    "tree_max_rel_err",
]

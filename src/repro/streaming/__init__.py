"""Device-tier streaming engine: the TPU-native adaptation of Jet.

The whole dataflow graph compiles into ONE XLA program executed SPMD on
every chip (the tasklet model's "whole DAG on every core"), state is
sharded so partitioning-of-state == partitioning-of-compute, keyed
exchange is a reduce-scatter/all-to-all, and snapshots are consistent by
construction at step boundaries.
"""

from .window import VectorWindowSpec, window_state_init
from .executor import StreamExecutor, StreamJobConfig

__all__ = ["VectorWindowSpec", "window_state_init", "StreamExecutor",
           "StreamJobConfig"]

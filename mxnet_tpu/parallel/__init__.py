"""mxnet_tpu.parallel — meshes, shardings, collectives, sequence parallelism.

TPU-native distributed layer (SURVEY §2.3 / §5 mapping): one collectives
module over jax.sharding meshes replaces the reference's CommCPU/CommDevice/
CommDeviceTree/NCCL/ps-lite stack. Also home of the capabilities the
reference lacks that are first-class here: tensor parallelism (tp.py) and
ring-attention sequence parallelism (ring_attention.py).
"""
from .mesh import (  # noqa: F401
    make_mesh, data_parallel_mesh, set_mesh, current_mesh, shard, replicate,
    activation_sharding, MeshConfig, mesh_factorizations,
)
from .collectives import (  # noqa: F401
    allreduce, allgather, reduce_scatter, ppermute,
    allreduce_across_processes, compressed_allreduce,
)
from .ring_attention import ring_attention  # noqa: F401
from . import tp  # noqa: F401
from . import pp  # noqa: F401
from .pp import gpipe, stack_stage_params, shard_stages  # noqa: F401
from .layout import StateLayout, megatron_specs  # noqa: F401
from .train import ShardedTrainStep, scan_steps  # noqa: F401
from .scaling import weak_scaling_table  # noqa: F401

"""paddle_tpu.distributed — mesh, collectives, dp/tp/pp/sp/ep parallelism.

Mirrors ``paddle.distributed`` + fleet (ref: incubate/fleet, collective
ops); see each module for the TPU-native design notes.
"""
from .env import (  # noqa: F401
    init_parallel_env, get_world_size, get_rank, ParallelEnv, init_mesh,
    get_mesh, set_mesh, mesh_axis_size, MeshGuard,
)
from .collective import (  # noqa: F401
    all_reduce, all_gather, reduce_scatter, broadcast, all_to_all, ppermute,
    reduce, scatter, barrier, ReduceOp,
)
from .parallel import (  # noqa: F401
    DataParallel, DistributedTrainStep, shard_tensor, param_spec,
)
from .tp_layers import (  # noqa: F401
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, mark_sharding,
)
from .gradcomm import CommOptions, plan_buckets  # noqa: F401
from .ring_attention import ring_attention, ring_attention_inner  # noqa: F401
from .ulysses import all_to_all_attention, all_to_all_attention_inner  # noqa: F401
from . import moe  # noqa: F401  (registers the expert layer's ops)
from .pipeline import pipeline_forward, PipelineStage, gpipe_inner  # noqa: F401
from . import fleet as _fleet_mod  # noqa: F401
from .fleet import fleet, DistributedStrategy  # noqa: F401

spawn = None  # single-controller SPMD: no process spawning needed

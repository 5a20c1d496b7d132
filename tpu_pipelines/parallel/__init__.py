"""Parallelism layer: device meshes, shardings, distributed bootstrap.

TPU-native replacement for the reference's tf.distribute + NCCL stack
(SURVEY.md §2b/§2c): parallelism is expressed as a ``jax.sharding.Mesh`` plus
``NamedSharding`` annotations; ``jax.jit`` lowers them to XLA collectives over
ICI/DCN.  No user-level collective library exists or is needed.
"""

from tpu_pipelines.parallel.mesh import (  # noqa: F401
    VALID_MASK_KEY,
    MeshConfig,
    data_parallel_sharding,
    make_mesh,
    masked_mean,
    replicate,
    shard_batch,
)

"""Parallelism strategies: sharding rules over the 5-axis mesh.

The reference implements exactly one strategy — data parallelism via DDP
(SURVEY.md §2c). Here DP is a *sharding annotation* (batch over ``data``,
params replicated), and the other strategies are additional annotations over
the same mesh rather than new machinery: tensor parallelism shards weight
matrices over ``model``, sequence parallelism shards the token axis over
``seq`` (ring attention), expert parallelism shards experts over ``expert``.
"""

from deeplearning_mpi_tpu.parallel.expert_parallel import ep_spec  # noqa: F401
from deeplearning_mpi_tpu.parallel.flash_sharded import (  # noqa: F401
    make_flash_attention_fn,
)
from deeplearning_mpi_tpu.parallel.pipeline import (  # noqa: F401
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
)
from deeplearning_mpi_tpu.parallel.ring_attention import (  # noqa: F401
    make_ring_attention_fn,
    ring_attention,
)
from deeplearning_mpi_tpu.parallel.ring_flash import (  # noqa: F401
    ring_flash_attention,
)
from deeplearning_mpi_tpu.parallel.tensor_parallel import (  # noqa: F401
    infer_state_sharding,
    infer_tp_param_sharding,
    shard_state,
)
from deeplearning_mpi_tpu.parallel.ulysses import (  # noqa: F401
    make_ulysses_attention_fn,
    ulysses_attention,
)
from deeplearning_mpi_tpu.parallel.zero import (  # noqa: F401
    OverlapUnsupported,
    make_overlapped_train_step,
    plan_buckets,
    zero1_spec,
)

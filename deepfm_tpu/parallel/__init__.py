from .embedding import (  # noqa: F401
    ExchangePlan,
    exchange_capacity,
    exchange_plan,
    lookup_fn_from_config,
    make_sharded_lookup_fn,
    permute_ids,
    resolve_shard_exchange,
    sharded_l2,
    sharded_lookup,
)
from .mesh import DATA_AXIS, MODEL_AXIS, build_mesh, initialize_distributed, mesh_shape  # noqa: F401
from .spmd import (  # noqa: F401
    SPMDContext,
    abstract_spmd_state,
    create_spmd_state,
    make_context,
    make_spmd_eval_step,
    make_spmd_predict_step,
    make_spmd_train_loop,
    make_spmd_train_step,
    padded_vocab,
    shard_batch,
    shard_batch_stacked,
)

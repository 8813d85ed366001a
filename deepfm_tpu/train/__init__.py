from .optimizer import build_optimizer, ftrl  # noqa: F401
from .step import (  # noqa: F401
    TrainState,
    create_train_state,
    jitted_train_step,
    make_eval_step,
    make_loss_fn,
    make_predict_step,
    make_train_step,
    new_auc_state,
    sigmoid_cross_entropy,
)

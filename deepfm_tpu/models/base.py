"""Model interface + registry.

Every model family is a pair of pure functions over explicit pytrees:

    init(key, cfg)  -> (params, model_state)
    apply(params, model_state, feat_ids, feat_vals, *, cfg, train, rng,
          lookup_fn) -> (logits, new_model_state)

``params`` are trainable; ``model_state`` is non-trainable (e.g. batch-norm
moving stats) — the functional replacement for the reference's TF graph
collections.  ``lookup_fn(tables, ids)`` abstracts embedding gathers so the
same model runs with replicated tables (single chip) or row-sharded tables
(``deepfm_tpu/parallel``) without modification: ``tables`` is one table or a
tuple of tables read with the same ids (FM_W and FM_V come in one call), and
the rows come back in the same structure.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

from ..core.config import ModelConfig


class ModelDef(NamedTuple):
    name: str
    init: Callable
    apply: Callable
    # (params, l2_reg) -> scalar regularization penalty; each family declares
    # which of its tables the reference-style L2 applies to.
    l2_penalty: Callable


def _no_penalty(params, l2_reg):
    return 0.0


_REGISTRY: dict[str, ModelDef] = {}


def register_model(
    name: str, init: Callable, apply: Callable, l2_penalty: Callable = _no_penalty
) -> ModelDef:
    md = ModelDef(name, init, apply, l2_penalty)
    _REGISTRY[name] = md
    return md


def get_model(name_or_cfg: str | ModelConfig) -> ModelDef:
    name = name_or_cfg if isinstance(name_or_cfg, str) else name_or_cfg.model_name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def registered_models() -> list[str]:
    return sorted(_REGISTRY)

"""Config schema tests."""

import pytest

from deepfm_tpu.core.config import Config


def test_from_dict_ignores_unknown_fields(caplog):
    """Saved configs must keep loading across framework versions: unknown
    fields (e.g. the retired mesh.data_axis) are dropped with a warning."""
    import logging

    with caplog.at_level(logging.WARNING):
        cfg = Config.from_dict(
            {
                "mesh": {"data_axis": "data", "model_parallel": 2},
                "model": {"feature_size": 99, "retired_knob": 1},
            }
        )
    assert cfg.mesh.model_parallel == 2
    assert cfg.model.feature_size == 99
    assert any("unknown field" in r.message for r in caplog.records)



@pytest.mark.parametrize("value", ["off", "auto", "on"])
@pytest.mark.parametrize("section,key", [("model", "fused_kernel"),
                                         ("run", "funnel_pallas")])
def test_retired_kernel_knobs_still_load(caplog, section, key, value):
    """The two kernel knobs retired in PR 46: a saved config naming either,
    at any value it could hold, loads to the config without it and names
    the key it dropped (before, "on" made the SPMD DeepFM forward raise)."""
    import logging

    base = {"model": {"feature_size": 99}}
    saved = {"model": dict(base["model"])}
    saved.setdefault(section, {})[key] = value
    with caplog.at_level(logging.WARNING):
        cfg = Config.from_dict(saved)
    assert cfg == Config.from_dict(base)
    assert any(f"{section}.{key}" in r.getMessage() for r in caplog.records)


def test_a_saved_config_loads_with_no_unknown_field(tmp_path, caplog):
    """A config.json as export_servable writes it (serve/export.py) loads
    back through the servable's loader equal and without a warning: no
    field the code writes is one it would drop."""
    import json
    import logging

    from deepfm_tpu.serve.export import _load_config

    cfg = Config.from_dict({"model": {"feature_size": 300, "field_size": 6}})
    (tmp_path / "config.json").write_text(json.dumps(cfg.to_dict()))
    with caplog.at_level(logging.WARNING):
        assert _load_config(str(tmp_path)) == cfg
    assert not [r for r in caplog.records if "unknown field" in r.getMessage()]

# -- cross-section validation (exchange capacity / sort bound / tiers) ------

def test_exchange_capacity_degenerate_raises():
    """A capacity so small the overflow psum fallback engages on every
    batch (one example's field_size distinct ids can't fit across all
    owners) must raise at config time, not silently run slow."""
    import pytest

    with pytest.raises(ValueError, match="overflow psum fallback"):
        Config.from_dict({
            "model": {"shard_exchange": "alltoall",
                      "shard_exchange_capacity": 0.0001},
            "mesh": {"data_parallel": 1, "model_parallel": 4},
        })


def test_exchange_capacity_suspicious_warns():
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Config.from_dict({
            "model": {"shard_exchange": "alltoall",
                      "shard_exchange_capacity": 0.05},
            "mesh": {"data_parallel": 1, "model_parallel": 4},
        })
    assert any("overflow fallback" in str(x.message) for x in w)


def test_exchange_capacity_auto_and_psum_stay_silent():
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Config.from_dict({
            "model": {"shard_exchange": "alltoall"},
            "mesh": {"data_parallel": 2, "model_parallel": 4},
        })
        Config.from_dict({
            "model": {"shard_exchange": "psum",
                      "shard_exchange_capacity": 0.0001},
            "mesh": {"data_parallel": 1, "model_parallel": 4},
        })
    assert not [x for x in w if "fallback" in str(x.message)]


def test_packed_sort_bound_warns_on_huge_vocab_exchange():
    """10M rows at 9984 local ids/shard cannot pack (24 + 14 bits > 32):
    the dedup sorts silently demote to the ~4x variadic argsort — the
    config must say so loudly."""
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Config.from_dict({
            "model": {"feature_size": 10_000_000},
            "optimizer": {"lazy_embedding_updates": True},
            "mesh": {"data_parallel": 4, "model_parallel": 2},
        })
    assert any("packed-sort" in str(x.message)
               or "variadic argsort" in str(x.message) for x in w)
    # flagship shape on [2,4] packs (17 + 15 bits) — no warning
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Config.from_dict({
            "model": {"feature_size": 117_581},
            "optimizer": {"lazy_embedding_updates": True},
            "mesh": {"data_parallel": 2, "model_parallel": 4},
        })
    assert not [x for x in w if "argsort" in str(x.message)]


def test_packed_sort_id_bound_matches_sort_condition():
    from deepfm_tpu.core.config import packed_sort_id_bound

    assert packed_sort_id_bound(64) == 1 << 26
    assert packed_sort_id_bound(19968) == 1 << 17   # flagship per-shard
    assert packed_sort_id_bound(1) == 1 << 31


def test_tiered_geometry_validation():
    import warnings

    import pytest

    with pytest.raises(ValueError, match="tiered_hot_slots"):
        Config.from_dict({
            "model": {"tiered_embeddings": True, "tiered_hot_slots": 64},
            "data": {"batch_size": 1024},
        })
    with pytest.raises(ValueError, match="tiered_page_rows"):
        Config.from_dict({"model": {"tiered_page_rows": 0}})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Config.from_dict({
            "model": {"tiered_embeddings": True,
                      "tiered_stage_rows": 64},
            "data": {"batch_size": 1024},
        })
    assert any("tiered_stage_rows" in str(x.message) for x in w)

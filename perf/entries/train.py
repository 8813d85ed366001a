"""Window driver of the training cells.

Drives the chain ``train/loop._run_train_guarded`` itself calls, minus files
and checkpoints: mesh → ``make_context`` → ``create_spmd_state`` →
``make_spmd_train_step``, fed by ``DevicePrefetcher(host_batches,
shard_batch)``.  Host batches are numpy dicts with int64 ids made from the seed
in set-up (a pool, cycled), so the range check, the int64→int32 narrowing, the
``device_put``, the prefetch thread and the real ``shard_map`` step are all
live; record decoding is not.

Set-up builds ONE step and ONE state, drives them through their first three
steps with the window's own call and feed (that is what ``correct`` compares,
perf/check.py), warms up, and hands the same objects to the window.  The
window dispatches steps until the host clock passes ``seconds``, then waits
for the last state: no sync and no log line per step.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import math
import shutil
import sys
import time

CHECK_STEPS = 3
WARM_STEPS = 2


def _fail(msg: str):
    print(f"perf: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(3)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def _adam_mu(opt_state):
    """The first-moment tree inside an optax state (the leaf-holding ``mu``
    of ``ScaleByAdamState``), wherever the chain put it."""
    found = []

    def visit(x):
        if hasattr(x, "mu") and hasattr(x, "nu"):
            found.append(x.mu)
        elif isinstance(x, (tuple, list)):
            for y in x:
                visit(y)

    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer state")
    return found[0]


def _named_norms(tree, scale: float = 1.0) -> dict:
    """{'mlp/layer_0/kernel': ‖leaf‖·scale} as device scalars."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_leaf_name(p): scale * jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in leaves}


def build_config(cell, seed: int):
    """The program's Config for this cell: the configuration file's overrides,
    the traffic's batch, the seed."""
    from deepfm_tpu.core.config import Config

    over = {sec: {k: (tuple(v) if isinstance(v, list) else v)
                  for k, v in fields.items()}
            for sec, fields in cell.config["overrides"].items()}
    over.setdefault("data", {})["batch_size"] = int(
        cell.traffic["params"]["batch_size"])
    over.setdefault("run", {})["seed"] = int(seed)
    return Config().with_overrides(**over)


class Env:
    """One cell's built objects: the ONE compiled step and the ONE state that
    set-up drives through their first steps and the window then drives on."""

    def close(self) -> None:
        """Stop the feed and drop the state and every device batch."""
        self.feed.close()
        self.state = self.metrics = None


def build(cell, seed: int, *, require_chip: bool = True) -> Env:
    """Context, state, step, host pool and feed of a cell, from the seed."""
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    import jax

    env = Env()
    env.marks = [("start", time.perf_counter())]
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        _fail(f"cell {cell.name} needs {cell.chips} TPU chip(s); jax found "
              f"{len(devices)} x {devices[0].platform}")
    peaks_all = json.loads((cell.perf_dir / "peaks.json").read_text())
    env.kind = devices[0].device_kind
    if require_chip and env.kind not in peaks_all:
        _fail(f"device kind {env.kind!r} is not in perf/peaks.json")
    env.peaks = peaks_all.get(env.kind)
    env.devices, env.used = devices, devices[:cell.chips]
    env.marks.append(("chip_open", time.perf_counter()))

    from deepfm_tpu.data.pipeline import DevicePrefetcher
    from deepfm_tpu.parallel import spmd
    from deepfm_tpu.parallel.mesh import build_mesh, initialize_distributed

    env.cfg = cfg = build_config(cell, seed)
    initialize_distributed(cfg.mesh)
    ctx = spmd.make_context(cfg, build_mesh(cfg.mesh, devices=env.used))
    env.state = spmd.create_spmd_state(ctx)
    env.step = spmd.make_spmd_train_step(ctx)
    env.marks.append(("state", time.perf_counter()))

    params = cell.traffic["params"]
    gen = cell.module("generators", cell.traffic["generator"])
    env.pool = gen.make_pool(params, rows=ctx.true_feature_size,
                             fields=cfg.model.field_size, seed=seed)
    env.batch_size = int(params["batch_size"])
    env.feed = DevicePrefetcher(itertools.cycle(env.pool),
                                lambda b: spmd.shard_batch(ctx, b),
                                depth=cfg.data.prefetch_batches)
    env.marks.append(("pool", time.perf_counter()))
    env.metrics = None
    return env


def _touched_rows(env: Env, mu, scale: float) -> dict:
    """The first gradient's rows at the first batch's distinct ids, for every
    table (a leaf with a row for each feature).  The ids are padded with the
    pad row's id 0 to the batch's full count, so that every seed gathers one
    shape and finds its program in the cache."""
    import jax
    import numpy as np

    ids = np.unique(env.pool[0]["feat_ids"])
    padded = np.zeros(env.pool[0]["feat_ids"].size, np.int32)
    padded[:ids.size] = ids
    tables = {_leaf_name(p): x
              for p, x in jax.tree_util.tree_flatten_with_path(mu)[0]
              if x.ndim and x.shape[0] >= env.cfg.model.feature_size}
    rows = jax.jit(lambda t, i: {k: x[i] for k, x in t.items()})(
        tables, padded)
    return {k: scale * np.asarray(x, np.float32)[:ids.size]
            for k, x in rows.items()}


def first_steps(env: Env) -> dict:
    """Drive the state through its first CHECK_STEPS steps with the window's
    own call and feed, and read what ``correct`` compares: each step's loss,
    the first gradient (from Adam's first moment after one step, (1-b1)·g) as
    per-leaf norms and, for the leaves small enough, whole, and the per-leaf
    norm of the parameters' change; and the first gradient's rows, in every
    table, at the distinct ids of the first batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf.check import WHOLE_LEAF_MAX

    scale = 1.0 / (1.0 - env.cfg.optimizer.adam_b1)
    p0 = jax.tree_util.tree_map(jnp.copy, env.state.params)
    prog = {"loss": []}
    for i in range(CHECK_STEPS):
        env.state, env.metrics = env.step(env.state, next(env.feed))
        prog["loss"].append(float(env.metrics["loss"]))
        if i == 0:
            mu = _adam_mu(env.state.opt_state)
            grad = jax.jit(lambda mu: _named_norms(mu, scale))(mu)
            prog["grad"] = {
                _leaf_name(p): scale * np.asarray(x, np.float32)
                for p, x in jax.tree_util.tree_flatten_with_path(mu)[0]
                if x.size < WHOLE_LEAF_MAX}
            prog["grad_rows"] = _touched_rows(env, mu, scale)
    delta = jax.jit(lambda a, b: _named_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))(
            env.state.params, p0)
    prog["grad_norm"] = {k: float(v) for k, v in grad.items()}
    prog["delta_norm"] = {k: float(v) for k, v in delta.items()}
    env.marks.append(("first_steps", time.perf_counter()))
    return prog


def _window(env: Env, seconds: float, trace_dir) -> dict:
    """Warm up, then dispatch steps until the host clock passes ``seconds``
    and wait for the last state.  With a ``trace_dir`` the profiler traces the
    window and the spans are written into its trace as well."""
    import jax

    step, feed, state = env.step, env.feed, env.state
    for _ in range(WARM_STEPS):
        state, metrics = step(state, next(feed))
    jax.block_until_ready(state)

    span = contextlib.nullcontext
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    feed_wait = dispatch = 0.0
    steps = 0
    clock = time.perf_counter
    t_start = clock()
    while True:
        t_a = clock()
        with span("perf.feed_wait"):
            batch = next(feed)
        t_b = clock()
        with span("perf.dispatch"):
            state, metrics = step(state, batch)
        t_c = clock()
        feed_wait += t_b - t_a
        dispatch += t_c - t_b
        steps += 1
        if t_c - t_start >= seconds:
            break
    with span("perf.wait_last_state"):
        jax.block_until_ready(state)
    window_s = clock() - t_start
    if trace_dir is not None:
        jax.profiler.stop_trace()
    env.state = None
    return {"t_start": t_start, "window_s": window_s, "steps": steps,
            "feed_wait_s": feed_wait, "dispatch_s": dispatch,
            "last_loss": float(metrics["loss"])}


def _layer_metrics(cell, env: Env, spans: dict, rate: float, trace_dir):
    """-> (per-layer metrics, reduced trace) of a traced run; a reader that
    finds nothing to read returns None and its metric is left out."""
    import numpy as np

    from perf import trace as trace_mod

    reduced = trace_mod.reduce_xplane(
        trace_mod.newest_xplane(str(trace_dir)), "local_step")
    shutil.rmtree(trace_dir, ignore_errors=True)
    unique = float(np.mean([np.unique(b["feat_ids"]).size for b in env.pool]))
    model = cell.config["overrides"]["model"]
    work_mod = cell.module("work", model["model_name"])
    view = {
        "spans": spans,
        "trace": reduced,
        "work": {
            "flops_per_example": work_mod.flops_per_example(model),
            "least_bytes_per_step": work_mod.least_bytes_per_step(
                model, env.batch_size, unique),
            "unique_rows": unique,
        },
        "examples_per_s": rate,
        "peaks": env.peaks,
        "chips": cell.chips,
    }
    out = {}
    for m in cell.per_layer:
        value = cell.module("metrics", m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out, reduced


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float,
        require_chip: bool = True) -> dict:
    env = build(cell, seed, require_chip=require_chip)
    trace_dir = (cell.perf_dir.parent / ".perf_trace" / cell.name
                 if trace else None)
    try:
        prog = first_steps(env)
        spans = _window(env, seconds, trace_dir)
    finally:
        env.close()
    marks = env.marks + [("warm_up", spans["t_start"]),
                         ("window", spans["t_start"] + spans["window_s"])]
    print("perf phases: import %.2f " % (marks[0][1] - t0) + " ".join(
        f"{b[0]} {b[1] - a[1]:.2f}" for a, b in zip(marks[:-1], marks[1:])),
          file=sys.stderr)

    steps, window_s = spans["steps"], spans["window_s"]
    rate = steps * env.batch_size / window_s
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in env.used]
    result = {
        "correct": False,
        "attempted": steps,
        "failed": 0 if math.isfinite(spans["last_loss"]) else steps,
        "metrics": {},
        "device": {"platform": env.devices[0].platform, "kind": env.kind,
                   "count": len(env.devices), "memory_peak_bytes": max(mem)},
    }
    if not trace:
        values = {"train_examples_per_s": rate,
                  "setup_s": spans["t_start"] - t0}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        result["metrics"], reduced = _layer_metrics(cell, env, spans, rate,
                                                    trace_dir)
        if reduced.get("devices"):
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}

    # correct: the plain reference follows the same three steps, on the chip,
    # now that the window has closed, the peak has been read and the
    # program's state is freed (env.close)
    from perf import check

    ref_mod = cell.module("reference", env.cfg.model.model_name)
    t_ref = time.perf_counter()
    ref = ref_mod.follow(cell.config, seed, env.pool[:CHECK_STEPS])
    print(f"perf reference_s={time.perf_counter() - t_ref:.2f}",
          file=sys.stderr)
    limits = json.loads(
        (cell.perf_dir / "limits" / f"{cell.name}.json").read_text())
    ok, rows = check.verdict(check.compare(prog, ref), limits)
    result["correct"] = bool(ok and result["failed"] == 0)
    result["checks"] = rows
    print(f"perf correct={result['correct']} steps={steps} "
          f"window_s={window_s:.3f}", file=sys.stderr)
    for name, row in rows.items():
        print(f"perf check {name}: {row['value']:.6g} (limit {row['limit']:g},"
              f" at {row['at']})", file=sys.stderr, flush=True)
    return result

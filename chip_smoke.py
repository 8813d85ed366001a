#!/usr/bin/env python3
"""chip_smoke.py — prove the program still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the reference model (DeepFM, V=117,581, F=39, K=32, deep
128/64/32, dropout 0.5, Adam 5e-4, batch 1024, bf16 MLP) with every other
field at its default: seeded synthetic records -> ``launch.cli`` train
(input pipeline, checkpoints, eval, export) -> the same command again
(resume) -> ``infer`` on the chip and on the CPU from the same checkpoint
-> ``serve.server`` answering ``:predict`` in every bucket.  With four
chips visible it goes on to the sharded meshes ([1,4], [2,2], default
[4,1]), a [1,4] -> [2,2] resume, and the four-chip serving pool.

Contract (see the builder's instructions): there is no CPU mode — without
an accelerator the script exits non-zero at once and prints no result; one
process uses the chip at a time, so THIS process never initialises a jax
backend: it starts each phase as a child, waits for it to exit before the
next one that needs the chip, and reads the child's start-up report
(core/platform.runtime_report) to learn what the child got.  Every phase's
failure is the script's failure, every wait has a timeout, and every
child is reaped on every exit path.  Everything it writes goes under
``chiprun_out/chip_smoke/``; the last stdout line is the result object.

Per phase it prints wall time split into set-up/compile and steady work,
XLA compile seconds (from jax's own compile log) and, for the trainer, the
record reader in use — counts and times for the log, not metrics.
"""

from __future__ import annotations

import atexit
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from deepfm_tpu.data.example_proto import decode_ctr_batch
from deepfm_tpu.data.libsvm import generate_synthetic_ctr
from deepfm_tpu.data.tfrecord import read_records

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
REQUIRED_PLATFORM = "tpu"
# On a host with several chips the one-chip phases run in children that see
# exactly one of them (libtpu's own process-to-chip binding), so their mesh
# resolves to the default [1,1] exactly as on a one-chip host.
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1"}

# the reference model, spelled out (tests/test_golden_trajectory.py)
V, F, K = 117_581, 39, 32
BATCH = 1024
MODEL_FLAGS = [
    "--feature_size", str(V), "--field_size", str(F),
    "--embedding_size", str(K), "--deep_layers", "128,64,32",
    "--learning_rate", "0.0005", "--optimizer", "Adam",
    "--batch_size", str(BATCH), "--set", "model.compute_dtype=bfloat16",
]
TRAIN_STEPS = 40          # one epoch of 40 batches
MESH_STEPS = 8            # four-chip mesh comparison: 8 steps per run
BUCKETS = (8, 32, 128, 512)
REQUEST_ROWS = (5, 20, 100, 400)   # one request size inside each bucket

# Agreement of predicted probabilities between two runs of the same
# function (chip infer vs CPU infer, server vs chip infer).
#
# The default path computes the MLP in bf16 on both sides, and the two
# compilers disagree about which intermediate roundings to keep
# (xla_allow_excess_precision), so EVERY row differs: on the v5e the chip
# and the CPU differ by median 2.8e-4, p99 1.06e-3, max 1.09e-3 (CHANGES.md
# PR 21).  The bf16 bounds are ~4x that.  They catch a wrong checkpoint, a
# wrong row order or a broken lookup (errors in the second digit), but NOT
# an f32->bf16 slip in the tables: rounding both tables to bf16 moves the
# same rows by median 3.1e-5, max 8.4e-4 (measured on the CPU) — less than
# honest bf16 noise.
#
# So the same checkpoint is scored once more with the MLP in f32 at highest
# matmul precision on both sides, where nothing but summation order differs
# (observed: max 1e-6, the last of pred.txt's 6 decimals).  There a table
# slip's 8.4e-4 stands 40x above the bound.  Two runs on the SAME chip
# (server vs infer, pool vs server) agree to that bound too (observed
# 5e-7), bf16 and all: one compiler, one set of roundings.
BF16 = {"max": 4e-3, "median": 1e-3}
EXACT = {"max": 2e-5, "median": 5e-6}
EXACT_ARGV = ["--set", "model.compute_dtype=float32"]
EXACT_ENV = {"JAX_DEFAULT_MATMUL_PRECISION": "highest"}

_LIVE: list[subprocess.Popen] = []


class PhaseError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# children

def _kill(proc: subprocess.Popen) -> None:
    """Terminate a child and everything it started (its own process
    group); no-op for a child that already exited."""
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # the leader or stragglers
        except ProcessLookupError:
            pass
        proc.wait()
    if proc in _LIVE:
        _LIVE.remove(proc)


def _reap_all() -> None:
    for proc in list(_LIVE):
        _kill(proc)


def _on_signal(signum, _frame):
    _reap_all()
    sys.exit(128 + signum)


class Child:
    """One phase's process: stdout lines are timestamped as they arrive
    (the split into set-up and steady work reads them), stderr goes to a
    log file (jax's compile log is parsed from it)."""

    def __init__(self, name: str, argv: list[str], env: dict | None = None):
        self.name = name
        self.out_path = os.path.join(OUT, "logs", f"{name}.out")
        self.err_path = os.path.join(OUT, "logs", f"{name}.err")
        child_env = dict(os.environ, JAX_LOG_COMPILES="1",
                         PYTHONUNBUFFERED="1", **(env or {}))
        self.lines: list[tuple[float, str]] = []
        self._err = open(self.err_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
            stderr=self._err, text=True, start_new_session=True,
        )
        _LIVE.append(self.proc)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        with open(self.out_path, "w") as log:
            for line in self.proc.stdout:
                self.lines.append((time.monotonic(), line.rstrip("\n")))
                log.write(line)
                log.flush()

    def wait(self, timeout: float) -> float:
        """Wait for a clean exit; returns wall seconds.  A non-zero exit or
        an expired timeout (a hang) fails the phase."""
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.close()
            raise PhaseError(
                f"{self.name}: no exit within {timeout:.0f}s (hung?)\n"
                f"{self.tail()}") from None
        wall = time.monotonic() - self.t0
        self.close()
        if rc != 0:
            raise PhaseError(f"{self.name}: exit code {rc}\n{self.tail()}")
        return wall

    def close(self) -> None:
        _kill(self.proc)
        self._reader.join(timeout=10)
        self._err.close()

    def tail(self, n: int = 30) -> str:
        with open(self.err_path, errors="replace") as f:
            err = [ln for ln in f.read().splitlines()
                   if "Finished " not in ln and "Compiling " not in ln]
        out = [ln for _, ln in self.lines]
        return "\n".join(["--- stdout ---", *out[-n:],
                          "--- stderr ---", *err[-n:]])

    def events(self, kind: str | None = None) -> list[tuple[float, dict]]:
        """(seconds since spawn, record) for every JSON line on stdout."""
        out = []
        for t, line in self.lines:
            if line.startswith("{"):
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if kind is None or rec.get("kind") == kind:
                    out.append((t - self.t0, rec))
        return out

    def runtime(self) -> dict:
        """The child's start-up report (its first ``runtime`` event)."""
        reports = self.events("runtime")
        if not reports:
            raise PhaseError(f"{self.name}: no start-up report\n{self.tail()}")
        return reports[0][1]

    def compile_log(self) -> tuple[float, int, list[tuple[str, float]]]:
        """(total seconds, executables, slowest five) from jax's log of
        XLA compilations on the child's stderr."""
        pat = re.compile(r"Finished XLA compilation of (.+?) in ([0-9.e-]+) sec")
        with open(self.err_path, errors="replace") as f:
            # a line repeats when two logging handlers are installed; the
            # (name, nanosecond duration) pair identifies one compilation
            found = list(dict.fromkeys(
                (m.group(1), float(m.group(2)))
                for m in pat.finditer(f.read())))
        top = sorted(found, key=lambda x: -x[1])[:5]
        return sum(s for _, s in found), len(found), top


def _check_platform(name: str, report: dict, want: str) -> None:
    if report.get("platform") != want:
        raise PhaseError(
            f"{name}: child ran on platform {report.get('platform')!r} "
            f"({report.get('device_kind')!r}), expected {want!r}")


def _report_phase(name: str, child: Child, wall: float,
                  steady_from: float | None, extra: dict | None = None) -> dict:
    """Print one phase's times.  ``steady_from`` = seconds after spawn at
    which set-up/compile ended and steady work began (None: no marker)."""
    compile_s, n, top = child.compile_log()
    row = {"phase": name, "wall_s": round(wall, 1),
           "setup_compile_s": None if steady_from is None
           else round(steady_from, 1),
           "steady_s": None if steady_from is None
           else round(wall - steady_from, 1),
           "xla_compile_s": round(compile_s, 2), "xla_compiles": n,
           "compile_share": round(compile_s / max(wall, 1e-9), 3),
           "slowest_compiles": [[e, round(s, 2)] for e, s in top]}
    row.update(extra or {})
    print("PHASE " + json.dumps(row), flush=True)
    return row


def _cache_summary(when: str, cache_dir: str) -> None:
    files = [os.path.join(d, f) for d, _, fs in os.walk(cache_dir) for f in fs]
    print(f"CACHE {when}: {cache_dir} holds {len(files)} files, "
          f"{sum(os.path.getsize(f) for f in files)} bytes", flush=True)


def _py(*args: str) -> list[str]:
    return [sys.executable, *args]


# ---------------------------------------------------------------------------
# phases

def phase_probe() -> dict:
    """Which device does jax find?  No accelerator -> fail at once."""
    child = Child("probe", _py(os.path.abspath(__file__), "--child", "probe"))
    wall = child.wait(300)
    report = child.runtime()
    if report.get("platform") != REQUIRED_PLATFORM:
        raise PhaseError(
            f"no accelerator: jax found platform {report.get('platform')!r} "
            f"({report.get('device_kind')!r}) — chip_smoke.py has no CPU mode")
    _report_phase("probe", child, wall, None, {"runtime": report})
    if report["device_count"] > 1:
        # the one-chip phases depend on a child seeing exactly one chip:
        # establish that before spending minutes on them
        child = Child("probe_one_chip", _py(os.path.abspath(__file__),
                                            "--child", "probe"), ONE_CHIP_ENV)
        wall = child.wait(180)
        one = child.runtime()
        if (one.get("platform"), one.get("device_count")) != (
                REQUIRED_PLATFORM, 1):
            raise PhaseError(f"probe_one_chip: a child under {ONE_CHIP_ENV} "
                             f"sees {one}, not one chip")
        _report_phase("probe_one_chip", child, wall, None, {"runtime": one})
    return report


def write_data() -> dict:
    t0 = time.monotonic()
    dirs = {k: os.path.join(OUT, k) for k in
            ("data", "data_mesh", "te_chip", "te_cpu", "te_chip_f32",
             "te_cpu_f32")}
    for d in dirs.values():
        os.makedirs(d)
    def gen(path, n, seed):
        generate_synthetic_ctr(path, num_records=n, feature_size=V,
                               field_size=F, seed=seed)

    gen(os.path.join(dirs["data"], "tr-00000.tfrecords"), TRAIN_STEPS * BATCH, 1)
    gen(os.path.join(dirs["data"], "va-00000.tfrecords"), 4 * BATCH, 2)
    gen(os.path.join(dirs["te_chip"], "te-00000.tfrecords"), BATCH, 3)
    for k in ("te_cpu", "te_chip_f32", "te_cpu_f32"):   # the same rows
        shutil.copy(os.path.join(dirs["te_chip"], "te-00000.tfrecords"),
                    os.path.join(dirs[k], "te-00000.tfrecords"))
    gen(os.path.join(dirs["data_mesh"], "tr-00000.tfrecords"),
        MESH_STEPS * BATCH, 4)
    print(f"DATA written in {time.monotonic() - t0:.1f}s under {OUT}",
          flush=True)
    return dirs


def train_argv(data_dir: str, model_dir: str, *, servable: str | None = None,
               dropout: str = "0.5,0.5,0.5", epochs: int = 1,
               mesh: tuple[int, int] | None = None, val: bool = True) -> list[str]:
    argv = _py("-m", "deepfm_tpu.launch.cli", "--task_type", "train",
               "--training_data_dir", data_dir, "--model_dir", model_dir,
               *MODEL_FLAGS, "--dropout", dropout,
               "--num_epochs", str(epochs),
               "--set", "run.log_steps=1",
               "--set", "run.checkpoint_every_steps=16")
    if val:
        argv += ["--val_data_dir", data_dir]
    if servable:
        argv += ["--servable_model_dir", servable]
    if mesh is not None:
        argv += ["--data_parallel", str(mesh[0]),
                 "--model_parallel", str(mesh[1])]
    return argv


def run_trainer(name: str, argv: list[str], *, env: dict | None = None,
                timeout: float = 420,
                want_mesh: list[int] | None = None) -> tuple[Child, dict]:
    child = Child(name, argv, env)
    wall = child.wait(timeout)
    report = child.runtime()
    _check_platform(name, report, REQUIRED_PLATFORM)
    if want_mesh is not None and report.get("mesh") != want_mesh:
        raise PhaseError(f"{name}: mesh {report.get('mesh')}, "
                         f"expected {want_mesh}")
    steps = child.events("train")
    losses = [rec["loss"] for _, rec in steps]
    if not all(np.isfinite(losses)):
        raise PhaseError(f"{name}: non-finite loss in {losses}")
    step_ms = sorted(rec["step_ms"] for _, rec in steps[1:])
    row = _report_phase(
        name, child, wall, steps[0][0] if steps else None,
        {"steps": len(steps),
         "loss_first": losses[0] if losses else None,
         "loss_last": losses[-1] if losses else None,
         "median_step_ms": step_ms[len(step_ms) // 2] if step_ms else None,
         "record_reader": report.get("record_reader"),
         "runtime": report})
    return child, row


def phase_train(dirs: dict, env: dict | None) -> None:
    model, servable = os.path.join(OUT, "model"), os.path.join(OUT, "servable")
    argv = train_argv(dirs["data"], model, servable=servable)
    child, _ = run_trainer("train", argv, env=env, want_mesh=[1, 1])
    losses = [rec["loss"] for _, rec in child.events("train")]
    if len(losses) != TRAIN_STEPS:
        raise PhaseError(f"train: {len(losses)} steps, expected {TRAIN_STEPS}")
    head, tail = np.mean(losses[:8]), np.mean(losses[-8:])
    if not tail < head:
        raise PhaseError(f"train: loss did not fall ({head:.4f} -> {tail:.4f})")
    evals = child.events("eval")
    if not evals or not np.isfinite(evals[-1][1]["auc"]):
        raise PhaseError(f"train: no finite eval AUC in {evals}")
    if not child.events("export") or not os.path.isdir(servable):
        raise PhaseError("train: no export event / servable dir")
    print(f"TRAIN loss {head:.4f} -> {tail:.4f} (means of first/last 8 of "
          f"{TRAIN_STEPS} steps), eval auc {evals[-1][1]['auc']:.4f} "
          f"loss {evals[-1][1]['loss']:.4f}", flush=True)

    # the same command again: restores the saved step and says so
    child, _ = run_trainer("resume", argv, env=env, want_mesh=[1, 1])
    resumed = child.events("resume")
    if not resumed or int(resumed[0][1]["step"]) != TRAIN_STEPS:
        raise PhaseError(f"resume: expected a resume event at step "
                         f"{TRAIN_STEPS}, got {resumed}")


def run_infer(name: str, test_dir: str, env: dict | None, platform: str,
              exact: bool = False) -> np.ndarray:
    argv = _py("-m", "deepfm_tpu.launch.cli", "--task_type", "infer",
               "--model_dir", os.path.join(OUT, "model"),
               "--test_data_dir", test_dir, *MODEL_FLAGS)
    if exact:   # later --set wins: the MLP in f32 at highest precision
        argv, env = argv + EXACT_ARGV, {**(env or {}), **EXACT_ENV}
    child = Child(name, argv, env)
    wall = child.wait(300)
    report = child.runtime()
    _check_platform(name, report, platform)
    done = child.events("infer")
    if not done or int(done[-1][1]["examples"]) != BATCH:
        raise PhaseError(f"{name}: expected {BATCH} scored rows, got {done}")
    _report_phase(name, child, wall,
                  child.events("runtime")[0][0], {"runtime": report})
    probs = np.loadtxt(os.path.join(test_dir, "pred.txt"), dtype=np.float64)
    if probs.shape != (BATCH,) or not np.all(np.isfinite(probs)) \
            or probs.min() < 0 or probs.max() > 1:
        raise PhaseError(f"{name}: bad predictions {probs.shape}")
    return probs


def _compare_probs(what: str, a: np.ndarray, b: np.ndarray,
                   bound: dict) -> None:
    d = np.abs(a - b)
    print(f"AGREE {what}: max|dp|={d.max():.2e} median|dp|={np.median(d):.2e} "
          f"p99|dp|={np.quantile(d, 0.99):.2e} rows>1e-4: "
          f"{int((d > 1e-4).sum())}/{d.size}", flush=True)
    if d.max() > bound["max"] or np.median(d) > bound["median"]:
        raise PhaseError(
            f"{what}: predictions disagree (max {d.max():.2e} > "
            f"{bound['max']} or median {np.median(d):.2e} > "
            f"{bound['median']})")


def _http(url: str, body: dict | None = None, timeout: float = 60):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def _wait_ready(child: Child, url: str, timeout: float) -> tuple[dict, float]:
    """Poll ``url`` until it answers ready; an expired timeout or a dead
    child fails the phase."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise PhaseError(f"{child.name}: exited with "
                             f"{child.proc.returncode} before ready\n"
                             f"{child.tail()}")
        try:
            doc = _http(url, timeout=5)
            if doc.get("ready"):
                return doc, time.monotonic() - child.t0
        except (urllib.error.URLError, OSError, json.JSONDecodeError):
            pass  # not listening yet; the deadline bounds the loop
        time.sleep(0.5)
    raise PhaseError(f"{child.name}: not ready within {timeout:.0f}s\n"
                     f"{child.tail()}")


def _test_rows(dirs: dict):
    feats, _ = decode_ctr_batch(
        read_records(os.path.join(dirs["te_chip"], "te-00000.tfrecords"),
                     verify=False), F)
    return feats["feat_ids"], feats["feat_vals"]


def _predict_rounds(base: str, ids, vals, rounds: int = 2) -> tuple[np.ndarray, int]:
    """``rounds`` passes of one request per bucket over disjoint test rows;
    returns the scores of the last pass (row-aligned with the test file's
    head) and the number of requests sent."""
    sent, scores = 0, None
    for _ in range(rounds):
        scores, lo = [], 0
        for n in REQUEST_ROWS:
            inst = [{"feat_ids": ids[i].tolist(), "feat_vals": vals[i].tolist()}
                    for i in range(lo, lo + n)]
            doc = _http(f"{base}/v1/models/deepfm:predict", {"instances": inst})
            if len(doc.get("predictions", [])) != n:
                raise PhaseError(f"predict({n} rows): bad response {str(doc)[:300]}")
            scores += doc["predictions"]
            sent += 1
            lo += n
    scores = np.asarray(scores, np.float64)
    if not np.all(np.isfinite(scores)):
        raise PhaseError("predict: non-finite scores")
    return scores, sent


def phase_serve(dirs: dict, chip_probs: np.ndarray,
                env: dict | None) -> np.ndarray:
    port = 18511
    child = Child("serve", _py(
        "-m", "deepfm_tpu.serve.server", "--servable",
        os.path.join(OUT, "servable"), "--port", str(port),
        "--buckets", ",".join(map(str, BUCKETS))), env)
    try:
        base = f"http://127.0.0.1:{port}"
        ready, t_ready = _wait_ready(child, base + "/readyz", 300)
        _check_platform("serve", ready.get("runtime", {}), REQUIRED_PLATFORM)
        ids, vals = _test_rows(dirs)
        t0 = time.monotonic()
        scores, sent = _predict_rounds(base, ids, vals)
        steady = time.monotonic() - t0
        _compare_probs("server vs chip infer", scores,
                       chip_probs[:scores.size], EXACT)
        m = _http(base + "/v1/metrics")
        want_hist = {str(b): sent // len(BUCKETS) for b in BUCKETS}
        if (m["requests_total"], m["dispatches_total"], m["rejected_total"],
                m["batch_size_hist"]) != (sent, sent, 0, want_hist):
            raise PhaseError(
                f"serve: expected {sent} requests = {sent} dispatches "
                f"{want_hist}, 0 rejected; metrics say {m}")
        wall = time.monotonic() - child.t0
        _report_phase("serve", child, wall, t_ready,
                      {"requests": sent, "request_loop_s": round(steady, 2),
                       "runtime": ready["runtime"]})
    finally:
        child.close()
    return scores


# ---------------------------------------------------------------------------
# four chips: the same trainer on sharded meshes, reshard-resume, the pool

def _check_placement(mesh: list[int], used: list, whole: int) -> None:
    """Four devices hold state, and under [1,4] none holds a whole table
    (``whole`` = what the one chip of the [1,1] run held)."""
    if len(used) != 4 or None in used or min(used) < 0.1 * max(used):
        raise PhaseError(f"mesh {mesh}: state is not on four devices: {used}")
    if mesh == [1, 4] and max(used) > 0.5 * whole:
        raise PhaseError(f"mesh [1,4]: a device holds {max(used)} bytes, "
                         f"more than half the one-chip state {whole} — "
                         f"the tables are not row-sharded")


def phase_four_chips(dirs: dict, server_scores: np.ndarray) -> None:
    # Dropout OFF (keep 1.0) for the mesh comparison: each data shard draws
    # its own dropout mask (fold_in axis_index), so with dropout on, runs
    # under different data-parallel degrees see different masks and their
    # losses are not comparable.  Same seed, same records.  The first
    # step's loss — same init, same batch, no update yet — must sit inside
    # tests/test_spmd.py's sharded-versus-dense band (rtol 2e-5).  That
    # test holds the band for five steps at a toy width; at the reference
    # width Adam's sign-like first updates amplify reduction-order noise in
    # the 3.8M table entries, and the CPU virtual mesh already drifts up to
    # 4.4e-5 over 8 steps under [1,4] (1.3e-5 under [4,1]), so later steps
    # get 3e-4.  A sharding bug (a gradient counted twice, a shard's rows
    # dropped) moves the loss in the second or third digit.
    keep, band_first, band = "1.0,1.0,1.0", 2e-5, 3e-4

    def mesh_run(name, mesh, want_mesh, env=None):
        argv = train_argv(dirs["data_mesh"], os.path.join(OUT, f"model_{name}"),
                          dropout=keep, mesh=mesh, val=False)
        child, row = run_trainer(f"mesh_{name}", argv, env=env,
                                 want_mesh=want_mesh)
        return ([rec["loss"] for _, rec in child.events("train")],
                row["runtime"]["bytes_in_use"])

    ref_loss, ref_bytes = mesh_run("1x1", None, [1, 1], ONE_CHIP_ENV)
    whole = max(b or 0 for b in ref_bytes)   # the whole state on one chip
    for name, mesh, want in (("1x4", (1, 4), [1, 4]), ("2x2", (2, 2), [2, 2]),
                             ("default", None, [4, 1])):
        loss, used = mesh_run(name, mesh, want)
        if len(loss) != MESH_STEPS:
            raise PhaseError(f"mesh {want}: {len(loss)} steps, not {MESH_STEPS}")
        rel = np.abs(np.array(loss) - ref_loss) / np.abs(ref_loss)
        print(f"MESH {want}: loss {loss} rel dev from [1,1]: first step "
              f"{rel[0]:.2e}, max {rel.max():.2e}; bytes_in_use {used} "
              f"(one chip holds {whole})", flush=True)
        if rel[0] > band_first or rel.max() > band:
            raise PhaseError(
                f"mesh {want}: loss leaves the band of the one-chip run "
                f"(first step {rel[0]:.2e} > {band_first} or max "
                f"{rel.max():.2e} > {band}): {loss} vs {ref_loss}")
        _check_placement(want, used, whole)

    # a checkpoint written under [1,4] resumes under [2,2]
    argv = train_argv(dirs["data_mesh"], os.path.join(OUT, "model_1x4"),
                      dropout=keep, mesh=(2, 2), epochs=2, val=False)
    child, _ = run_trainer("reshard_resume", argv, want_mesh=[2, 2])
    resumed = child.events("resume")
    steps = child.events("train")
    if (not child.events("resume_reshard") or not resumed
            or int(resumed[0][1]["step"]) != MESH_STEPS
            or len(steps) != MESH_STEPS):
        raise PhaseError(
            f"reshard_resume: expected resume_reshard + resume at step "
            f"{MESH_STEPS} + {MESH_STEPS} more steps; got "
            f"{child.events('resume_reshard')}, {resumed}, {len(steps)} steps")
    print(f"RESHARD [1,4] -> [2,2]: resumed at step {MESH_STEPS}, loss "
          f"{steps[0][1]['loss']:.6f} -> {steps[-1][1]['loss']:.6f}", flush=True)

    # the four-chip pool answers through the router with the one-chip
    # server's scores
    port, member_port = 18520, 18621
    child = Child("pool", _py(
        "-m", "deepfm_tpu.serve.pool", "--servable",
        os.path.join(OUT, "servable"), "--router", "--groups", "1",
        "--group-dp", "1", "--group-mp", "4", "--port", str(port),
        "--member-port-base", str(member_port),
        "--buckets", ",".join(map(str, BUCKETS)), "--max-restarts", "0"))
    try:
        member, t_ready = _wait_ready(
            child, f"http://127.0.0.1:{member_port}/readyz", 420)
        _check_platform("pool member", member.get("runtime", {}),
                        REQUIRED_PLATFORM)
        if member["runtime"].get("mesh") != [1, 4]:
            raise PhaseError(f"pool: member mesh {member['runtime'].get('mesh')}")
        _wait_ready(child, f"http://127.0.0.1:{port}/readyz", 120)
        ids, vals = _test_rows(dirs)
        scores, sent = _predict_rounds(f"http://127.0.0.1:{port}", ids, vals)
        print(f"POOL [1,4]: exchange={member.get('exchange')} member "
              f"bytes_in_use {member['runtime']['bytes_in_use']}", flush=True)
        _compare_probs("four-chip pool vs one-chip server", scores,
                       server_scores, EXACT)
        _report_phase("pool", child, time.monotonic() - child.t0, t_ready,
                      {"requests": sent, "runtime": member["runtime"]})
    finally:
        child.close()


# ---------------------------------------------------------------------------
# child modes (these DO touch jax; the parent never reaches them)

def _child_probe() -> None:
    from deepfm_tpu.core.platform import configure_runtime, runtime_report

    configure_runtime()
    print(json.dumps({"kind": "runtime", **runtime_report()}), flush=True)


# ---------------------------------------------------------------------------

def main() -> int:
    if sys.argv[1:2] == ["--child"]:
        {"probe": _child_probe}[sys.argv[2]]()
        return 0
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    atexit.register(_reap_all)
    t0 = time.monotonic()
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "logs"))
    try:
        device = phase_probe()
        count = int(device["device_count"])
        _cache_summary("before", device["compile_cache_dir"])
        dirs = write_data()
        one = ONE_CHIP_ENV if count > 1 else None
        phase_train(dirs, one)
        chip = run_infer("infer_chip", dirs["te_chip"], one, REQUIRED_PLATFORM)
        # a child that needs no chip: one CPU device, same checkpoint, same rows
        on_cpu = {"JAX_PLATFORMS": "cpu"}
        cpu = run_infer("infer_cpu", dirs["te_cpu"], on_cpu, "cpu")
        _compare_probs("chip infer vs CPU infer (bf16 MLP)", chip, cpu, BF16)
        _compare_probs(
            "chip infer vs CPU infer (f32 MLP, highest precision)",
            run_infer("infer_chip_f32", dirs["te_chip_f32"], one,
                      REQUIRED_PLATFORM, exact=True),
            run_infer("infer_cpu_f32", dirs["te_cpu_f32"], on_cpu, "cpu",
                      exact=True),
            EXACT)
        scores = phase_serve(dirs, chip, one)
        if count >= 4:
            phase_four_chips(dirs, scores)
    except PhaseError as e:
        print(f"chip_smoke FAILED after {time.monotonic() - t0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        _reap_all()
        # keep the logs and predictions; records, checkpoints and servable
        # are bulk (the tool brings back at most 64 MiB)
        for name in os.listdir(OUT):
            if name.startswith(("data", "model", "servable")):
                shutil.rmtree(os.path.join(OUT, name), ignore_errors=True)
    _cache_summary("after", device["compile_cache_dir"])
    print(f"chip_smoke passed in {time.monotonic() - t0:.0f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

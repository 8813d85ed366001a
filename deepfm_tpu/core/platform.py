"""Runtime set-up shared by every entry point, and the report of what a
process got.

``configure_runtime()`` is called once by each entry point (the launcher
CLI, ``serve/server.py main``, the pool member, ``perf/entries/train.py``,
``__graft_entry__``) BEFORE the first jax backend initialisation: it places
the persistent compile cache, on the CPU platform only relaxes XLA:CPU's
collective watchdogs, and elsewhere starts importing Pallas on a thread of
its own, beside the chip's opening (``await_pallas``).  It never initialises
a backend itself, so a supervisor that only forks workers stays off the
chip.

``runtime_report()`` is the one shape in which trainer, server and pool
member say where they ran: platform, device kind and count, mesh, jax
version, compile-cache directory, per-device bytes in use.
A parent that must not touch jax (one process per chip) learns what its
child got from this report — as a ``runtime`` log event from the CLI, and
under the ``runtime`` key of ``/readyz`` from the servers.
"""

from __future__ import annotations

import glob
import os
import threading

# <checkout>/.jax_cache — derived from the package's own location so every
# process of one checkout agrees on it whatever its working directory
# (the directory is part of the cache key: a cache that moves never hits)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def is_tpu_backend() -> bool:
    """True when the default backend is a TPU.  A failure to get devices
    (a chip held by another process, a broken runtime) propagates — it is
    not evidence that the platform is something else."""
    import jax

    return jax.devices()[0].platform == "tpu"


def host_cpu_count() -> int:
    """Usable host cores (cgroup/affinity-aware where the OS exposes it)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


def _requested_platform() -> str:
    """First entry of ``JAX_PLATFORMS`` ("" when unset)."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()


def expected_platform() -> str:
    """The platform jax will pick, decided WITHOUT initialising a backend —
    for supervisors that must know before they fork whether their children
    will contend for a chip.  An explicit ``JAX_PLATFORMS`` decides; with
    none, jax takes the TPU exactly when the host exposes one (the
    accelerator device nodes libtpu opens)."""
    requested = _requested_platform()
    if requested:
        return requested
    if glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"):
        return "tpu"
    return "cpu"


def refuse_shared_chip(processes: int, what: str) -> None:
    """Exit when ``processes`` > 1 jax processes would be started on one
    TPU host.  A chip belongs to one process at a time: the second child
    cannot open the device and would crash-loop through its restart budget
    while the first serves."""
    if processes > 1 and expected_platform() == "tpu":
        raise SystemExit(
            f"{what}: refusing to start {processes} jax processes on one "
            f"TPU host — a chip belongs to one process at a time, so every "
            f"process after the first would fail to open the device and "
            f"crash-loop.  Run one process per host (its mesh can span all "
            f"of the host's chips)."
        )


def compile_cache_dir() -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it, else one fixed git-ignored directory inside
    the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def _relax_cpu_collective_timeouts(
    warn_s: int = 120, terminate_s: int = 900
) -> None:
    """Raise XLA:CPU's collective-rendezvous watchdogs (default 20 s warn /
    40 s TERMINATE-the-process) via XLA_FLAGS.  On an oversubscribed host —
    N virtual devices time-slicing a core or two, exactly the CI/virtual-
    mesh topology — a long first-compile or a heavy step can keep one
    device thread away from a rendezvous past 40 s and XLA kills the
    process mid-training.  No-op for flags the caller already set."""
    flags = os.environ.get("XLA_FLAGS", "")
    add = []
    if "xla_cpu_collective_call_warn_stuck_timeout_seconds" not in flags:
        add.append(
            f"--xla_cpu_collective_call_warn_stuck_timeout_seconds={warn_s}"
        )
    if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
        add.append(
            f"--xla_cpu_collective_call_terminate_timeout_seconds={terminate_s}"
        )
    if add:
        os.environ["XLA_FLAGS"] = " ".join([flags] + add).strip()


# the Pallas import started by ``configure_runtime``: (thread, its error)
_pallas_import: tuple[threading.Thread, list] | None = None


def _start_pallas_import() -> None:
    """Import ``jax.experimental.pallas`` and its TPU module on a daemon
    thread, once: about a second of the host's (jax's Mosaic-GPU interpreter
    among it, whatever the platform) that would otherwise land in front of
    the first step that traces a kernel."""
    global _pallas_import
    if _pallas_import is not None:
        return
    error: list = []

    def run():
        try:
            from jax.experimental import pallas  # noqa: F401
            from jax.experimental.pallas import tpu  # noqa: F401
        except BaseException as e:  # raised again where it is awaited
            error.append(e)

    thread = threading.Thread(target=run, name="pallas-import", daemon=True)
    _pallas_import = (thread, error)
    thread.start()


def await_pallas() -> None:
    """Join the Pallas import ``configure_runtime`` started, if it did, and
    raise what it raised.  A kernel's first trace calls this before its own
    import of Pallas."""
    if _pallas_import is None:
        return
    thread, error = _pallas_import
    thread.join()
    if error:
        raise error[0]


def configure_runtime() -> str:
    """Process-wide jax set-up; call before the first backend init.

    * The CPU watchdog flags go into ``XLA_FLAGS`` only when the CPU is the
      requested platform: every backend parses ``XLA_FLAGS`` and aborts the
      process on a name it does not know.
    * The persistent compile cache: with ``JAX_COMPILATION_CACHE_DIR`` set
      jax reads the variable itself and nothing is set in code; otherwise
      the checkout's fixed directory.  Every executable is cached: the
      serve buckets (~2 s each on the v5e) clear jax's default threshold of
      1.0 s of compile time, but the eval step (0.7-0.9 s) and everything
      a CPU child compiles do not, and were rebuilt by every process until
      the threshold went to 0 (CHANGES.md PR 21).

    * jax's trace, lowering, compile and cache-load events go to the
      training path's span recorder from here on
      (``obs/trace.install_compile_listener``).
    * Off the CPU, Pallas is imported on a thread of its own while the
      caller opens the chip (``await_pallas`` joins it).

    Returns the cache directory in use."""
    import jax

    from ..obs.trace import install_compile_listener

    install_compile_listener()
    if _requested_platform() == "cpu":
        _relax_cpu_collective_timeouts()
    else:
        _start_pallas_import()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()


def runtime_report(mesh=None) -> dict:
    """What this process runs on (initialises the backend).  ``mesh`` is the
    ``[data, model]`` mesh the caller built, when it has one.
    ``bytes_in_use`` is per local device, ``None`` where the backend keeps
    no allocator statistics (XLA:CPU) — call after state creation so a
    placement that lands everything on device 0 is visible."""
    import jax

    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": None,
        "jax_version": jax.__version__,
        "compile_cache_dir": compile_cache_dir(),
        "bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()
        ],
    }
    if mesh is not None:
        report["mesh"] = [int(n) for n in mesh.devices.shape]
    return report

"""Static-analysis suite tests (deepfm_tpu/analysis).

Fixture snippets run the real engines against in-memory sources: every
AST rule gets a positive (seeded violation caught) and a negative (clean
idiom not flagged) case; the baseline ratchet, suppression syntax, and
JSON output schema are covered; the trace-time audit is exercised both on
the real entrypoints (must be clean — this IS the CI gate as a test) and
against deliberately broken contracts (must trip).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from deepfm_tpu.analysis import run_ast_engine
from deepfm_tpu.analysis.baseline import (
    load_baseline,
    partition,
    write_baseline,
)

REPO = __file__.rsplit("/tests/", 1)[0]


def rules_of(findings):
    return sorted({f.rule for f in findings})


def analyze(src: str, path: str = "mod.py"):
    return run_ast_engine({path: src})


# ---------------------------------------------------------------- engine 1

class TestTracerHostOp:
    def test_item_inside_jit_caught(self):
        f = analyze(
            "import jax\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return float(x.sum().item())\n"
        )
        assert "tracer-host-op" in rules_of(f)
        assert any(".item()" in x.message for x in f)

    def test_numpy_call_inside_jit_caught(self):
        f = analyze(
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return np.asarray(x) + 1\n"
        )
        assert "tracer-host-op" in rules_of(f)

    def test_jit_reachable_via_factory_and_callee(self):
        # jax.jit(make_step(cfg)) marks the factory's returned inner fn;
        # the helper it calls by bare name is traced transitively
        f = analyze(
            "import jax\n"
            "def helper(x):\n"
            "    return x.tolist()\n"
            "def make_step(cfg):\n"
            "    def step(x):\n"
            "        return helper(x)\n"
            "    return step\n"
            "fn = jax.jit(make_step(None))\n"
        )
        assert "tracer-host-op" in rules_of(f)

    def test_static_shape_idiom_not_flagged(self):
        # int(x.shape[0]) is a python int at trace time — trace-safe
        f = analyze(
            "import jax\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    b = int(x.shape[0])\n"
            "    n = int(len(x))\n"
            "    return x.reshape(b, -1), n\n"
        )
        assert "tracer-host-op" not in rules_of(f)

    def test_partially_static_arg_still_flagged(self):
        # .shape inside the expression must not exempt a traced sum
        f = analyze(
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return int(jnp.sum(x) / x.shape[0])\n"
        )
        assert "tracer-host-op" in rules_of(f)

    def test_executor_map_is_not_a_transform(self):
        # ThreadPoolExecutor.map must not mark the callback jit-reachable
        f = analyze(
            "from concurrent.futures import ThreadPoolExecutor\n"
            "def fetch(u):\n"
            "    return float(u.score)\n"
            "def fan_out(ex, urls):\n"
            "    return list(ex.map(fetch, urls))\n"
        )
        assert "tracer-host-op" not in rules_of(f)

    def test_same_name_methods_all_analyzed(self):
        # bare-name collisions must not skip the second def's body
        f = analyze(
            "import jax\n"
            "class A:\n"
            "    def sample(self, key, shape):\n"
            "        return jax.random.normal(key, shape)\n"
            "class B:\n"
            "    def sample(self, key, shape):\n"
            "        a = jax.random.normal(key, shape)\n"
            "        b = jax.random.uniform(key, shape)\n"
            "        return a + b\n"
        )
        assert "prng-reuse" in rules_of(f)

    def test_host_side_float_not_flagged(self):
        f = analyze(
            "def configure(ms):\n"
            "    return float(ms) / 1e3\n"
        )
        assert "tracer-host-op" not in rules_of(f)

    def test_np_dtype_attribute_not_flagged(self):
        f = analyze(
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x.astype(np.float32)\n"
        )
        assert f == []


class TestTracedNondeterminism:
    def test_wall_clock_in_jit_caught(self):
        f = analyze(
            "import jax, time\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x * time.time()\n"
        )
        assert "traced-nondeterminism" in rules_of(f)

    def test_python_random_in_jit_caught(self):
        f = analyze(
            "import jax, random\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x + random.random()\n"
        )
        assert "traced-nondeterminism" in rules_of(f)

    def test_jax_random_alias_not_nondeterminism(self):
        # `from jax import random` draws are keyed and deterministic — only
        # STDLIB random is trace-time nondeterminism
        f = analyze(
            "import jax\n"
            "from jax import random\n"
            "@jax.jit\n"
            "def step(key, x):\n"
            "    return x + random.normal(key, x.shape)\n"
        )
        assert "traced-nondeterminism" not in rules_of(f)

    def test_np_random_in_jit_is_nondeterminism_not_host_op(self):
        # the right fix is a jax key, not a jnp spelling — rule id matters
        # for the suppression/baseline contract
        f = analyze(
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x + np.random.normal(size=3)\n"
        )
        assert rules_of(f) == ["traced-nondeterminism"]

    def test_wall_clock_outside_jit_ok(self):
        f = analyze(
            "import time\n"
            "def poll(x):\n"
            "    return time.time() - x\n"
        )
        assert f == []


class TestPrngReuse:
    def test_double_draw_caught(self):
        f = analyze(
            "import jax\n"
            "def init(key):\n"
            "    key = jax.random.PRNGKey(0)\n"
            "    a = jax.random.normal(key, (3,))\n"
            "    b = jax.random.normal(key, (3,))\n"
            "    return a + b\n"
        )
        assert "prng-reuse" in rules_of(f)

    def test_split_between_draws_ok(self):
        f = analyze(
            "import jax\n"
            "def init(key):\n"
            "    k1, k2 = jax.random.split(jax.random.PRNGKey(0))\n"
            "    a = jax.random.normal(k1, (3,))\n"
            "    b = jax.random.normal(k2, (3,))\n"
            "    return a + b\n"
        )
        assert "prng-reuse" not in rules_of(f)

    def test_parameter_key_double_draw_caught(self):
        # the most common shape: a key RECEIVED by the function is fresh
        # exactly once — two draws from it are correlated
        f = analyze(
            "import jax\n"
            "def sample(key, shape):\n"
            "    a = jax.random.normal(key, shape)\n"
            "    b = jax.random.uniform(key, shape)\n"
            "    return a + b\n"
        )
        assert "prng-reuse" in rules_of(f)

    def test_parameter_key_single_draw_ok(self):
        f = analyze(
            "import jax\n"
            "def sample(key, shape):\n"
            "    return jax.random.normal(key, shape)\n"
        )
        assert "prng-reuse" not in rules_of(f)

    def test_stdlib_random_not_a_key_draw(self):
        # stdlib random shares the module name; two calls with a shared
        # first-arg Name must not read as correlated key draws
        f = analyze(
            "import random\n"
            "def jitter(lo, hi):\n"
            "    a = random.uniform(lo, hi)\n"
            "    b = random.uniform(lo, hi)\n"
            "    return a + b\n"
        )
        assert "prng-reuse" not in rules_of(f)

    def test_from_jax_import_random_alias_caught(self):
        f = analyze(
            "from jax import random\n"
            "def sample(key, shape):\n"
            "    a = random.normal(key, shape)\n"
            "    b = random.uniform(key, shape)\n"
            "    return a + b\n"
        )
        assert "prng-reuse" in rules_of(f)

    def test_exclusive_branches_not_reuse(self):
        # one draw per path: never more than one consumption at runtime
        f = analyze(
            "import jax\n"
            "def sample(key, flag, shape):\n"
            "    if flag:\n"
            "        x = jax.random.normal(key, shape)\n"
            "    else:\n"
            "        x = jax.random.uniform(key, shape)\n"
            "    return x\n"
        )
        assert "prng-reuse" not in rules_of(f)

    def test_branch_then_second_draw_caught(self):
        # both paths consume, so the draw AFTER the if is a real reuse
        f = analyze(
            "import jax\n"
            "def sample(key, flag, shape):\n"
            "    if flag:\n"
            "        x = jax.random.normal(key, shape)\n"
            "    else:\n"
            "        x = jax.random.uniform(key, shape)\n"
            "    return x + jax.random.normal(key, shape)\n"
        )
        assert "prng-reuse" in rules_of(f)

    def test_rearm_via_split_subscript_ok(self):
        # key = jax.random.split(key)[0] is a fresh subkey
        f = analyze(
            "import jax\n"
            "def sample(key, shape):\n"
            "    a = jax.random.normal(key, shape)\n"
            "    key = jax.random.split(key)[0]\n"
            "    b = jax.random.normal(key, shape)\n"
            "    return a + b\n"
        )
        assert "prng-reuse" not in rules_of(f)

    def test_loop_invariant_key_draw_caught(self):
        # iteration 2 draws from the key iteration 1 consumed
        f = analyze(
            "import jax\n"
            "def sample(key, n):\n"
            "    out = []\n"
            "    for _ in range(n):\n"
            "        out.append(jax.random.normal(key, (3,)))\n"
            "    return out\n"
        )
        assert "prng-reuse" in rules_of(f)
        assert len([x for x in f if x.rule == "prng-reuse"]) == 1

    def test_loop_with_fold_in_ok(self):
        f = analyze(
            "import jax\n"
            "def sample(rng, n):\n"
            "    out = []\n"
            "    for i in range(n):\n"
            "        key = jax.random.fold_in(rng, i)\n"
            "        out.append(jax.random.normal(key, (3,)))\n"
            "    return out\n"
        )
        assert "prng-reuse" not in rules_of(f)

    def test_rearm_by_fold_in_ok(self):
        f = analyze(
            "import jax\n"
            "def init(rng, step):\n"
            "    key = jax.random.fold_in(rng, step)\n"
            "    a = jax.random.normal(key, (3,))\n"
            "    key = jax.random.fold_in(rng, step + 1)\n"
            "    b = jax.random.normal(key, (3,))\n"
            "    return a + b\n"
        )
        assert "prng-reuse" not in rules_of(f)


class TestInt32Cast:
    def test_arithmetic_result_caught(self):
        f = analyze(
            "import jax.numpy as jnp\n"
            "def seg(ids, fields):\n"
            "    return (ids * fields).astype(jnp.int32)\n"
        )
        assert "int32-cast" in rules_of(f)

    def test_cast_before_clip_caught(self):
        f = analyze(
            "import jax.numpy as jnp\n"
            "def narrow(ids, v):\n"
            "    return jnp.clip(ids.astype(jnp.int32), 0, v - 1)\n"
        )
        assert "int32-cast" in rules_of(f)
        assert any("AFTER" in x.message for x in f)

    def test_clip_before_cast_ok(self):
        f = analyze(
            "import jax.numpy as jnp\n"
            "def narrow(ids, v):\n"
            "    return jnp.clip(ids, 0, v - 1).astype(jnp.int32)\n"
        )
        assert "int32-cast" not in rules_of(f)

    def test_bounded_floordiv_ok(self):
        f = analyze(
            "import jax.numpy as jnp\n"
            "def win(uids, per):\n"
            "    return (uids // per).astype(jnp.int32)\n"
        )
        assert "int32-cast" not in rules_of(f)


class TestSwallowedException:
    def test_silent_pass_caught(self):
        f = analyze(
            "def poll(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        assert "swallowed-exception" in rules_of(f)

    def test_bare_except_caught(self):
        f = analyze(
            "def poll(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except:\n"
            "        return None\n"
        )
        assert "swallowed-exception" in rules_of(f)

    def test_tuple_exception_type_caught(self):
        f = analyze(
            "def poll(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except (Exception, SystemExit):\n"
            "        pass\n"
        )
        assert "swallowed-exception" in rules_of(f)

    def test_narrow_tuple_ok(self):
        f = analyze(
            "def poll(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except (OSError, ValueError):\n"
            "        pass\n"
        )
        assert "swallowed-exception" not in rules_of(f)

    def test_reraise_ok(self):
        f = analyze(
            "def poll(fn, purge):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        purge()\n"
            "        raise\n"
        )
        assert "swallowed-exception" not in rules_of(f)

    def test_using_exception_ok(self):
        f = analyze(
            "def poll(fn, log):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception as e:\n"
            "        log.append(str(e))\n"
        )
        assert "swallowed-exception" not in rules_of(f)

    def test_narrow_except_ok(self):
        f = analyze(
            "def poll(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except OSError:\n"
            "        pass\n"
        )
        assert "swallowed-exception" not in rules_of(f)


GUARDED_CLASS = """
import threading

class Swapper:
    def __init__(self):
        self._lock = threading.Lock()
        self.swaps = 0
        self.last_ms = None

    def status(self):
        with self._lock:
            return {"swaps": self.swaps, "last_ms": self.last_ms}

    def poll(self, ms):
        {MUTATION}
        with self._lock:
            self.swaps += 1
"""


class TestGuardedBy:
    def test_unguarded_mutation_caught(self):
        src = GUARDED_CLASS.replace("{MUTATION}", "self.last_ms = ms")
        f = analyze(src)
        assert "guarded-by" in rules_of(f)
        assert any("last_ms" in x.message for x in f)

    def test_guarded_mutation_ok(self):
        src = GUARDED_CLASS.replace(
            "{MUTATION}",
            "with self._lock:\n            self.last_ms = ms"
        )
        assert "guarded-by" not in rules_of(analyze(src))

    def test_init_exempt(self):
        src = GUARDED_CLASS.replace("{MUTATION}", "pass")
        # __init__ assigns swaps/last_ms lock-free: not flagged
        assert "guarded-by" not in rules_of(analyze(src))

    def test_container_mutation_caught(self):
        f = analyze(
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = []\n"
            "    def drain(self):\n"
            "        with self._lock:\n"
            "            out, self._items = self._items, []\n"
            "        return out\n"
            "    def put(self, x):\n"
            "        self._items.append(x)\n"
        )
        assert "guarded-by" in rules_of(f)

    def test_tuple_unpack_mutation_caught(self):
        # `self.a, self.b = ...` mutates both attributes
        src = GUARDED_CLASS.replace(
            "{MUTATION}", "self.last_ms, self.swaps = ms, 0"
        )
        f = analyze(src)
        assert "guarded-by" in rules_of(f)
        assert {m for x in f for m in ("last_ms", "swaps") if m in x.message} \
            == {"last_ms", "swaps"}

    def test_del_subscript_mutation_caught(self):
        f = analyze(
            "import threading\n"
            "class M:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._m = {}\n"
            "    def get(self, k):\n"
            "        with self._lock:\n"
            "            return self._m.get(k)\n"
            "    def evict(self, k):\n"
            "        del self._m[k]\n"
        )
        assert "guarded-by" in rules_of(f)

    def test_lock_held_helper_fixpoint_ok(self):
        # _trip is only ever called under the lock: its mutations count as
        # held (the factored-out-critical-section idiom must not be noise)
        f = analyze(
            "import threading\n"
            "class Breaker:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.opens = 0\n"
            "    def record(self):\n"
            "        with self._lock:\n"
            "            self._trip()\n"
            "    def _trip(self):\n"
            "        self.opens += 1\n"
        )
        assert "guarded-by" not in rules_of(f)


class TestSuppressions:
    SRC = (
        "def poll(fn):\n"
        "    try:\n"
        "        fn()\n"
        "    # da:allow[swallowed-exception] probe: failure means fallback\n"
        "    except Exception:\n"
        "        pass\n"
    )

    def test_justified_suppression_silences(self):
        assert analyze(self.SRC) == []

    def test_suppression_without_reason_is_a_finding(self):
        src = self.SRC.replace(" probe: failure means fallback", "")
        f = analyze(src)
        assert rules_of(f) == ["suppression-missing-reason"]

    def test_wrong_rule_id_does_not_silence(self):
        src = self.SRC.replace("swallowed-exception", "guarded-by")
        assert "swallowed-exception" in rules_of(analyze(src))

    def test_unused_suppression_is_a_finding(self):
        # the flagged code was fixed but the comment lingers: report it so
        # it cannot silently swallow the NEXT finding on that line
        f = analyze(
            "def poll(fn):\n"
            "    # da:allow[swallowed-exception] probe fallback\n"
            "    return fn()\n"
        )
        assert rules_of(f) == ["unused-suppression"]

    def test_docstring_syntax_example_not_a_suppression(self):
        f = analyze(
            '"""Docs: suppress with `# da:allow[rule-id] reason`."""\n'
            "def f(x):\n"
            "    return x\n"
        )
        assert f == []


# ------------------------------------------------------------- baseline

class TestBaselineRatchet:
    SRC = (
        "def poll(fn):\n"
        "    try:\n"
        "        fn()\n"
        "    except Exception:\n"
        "        pass\n"
    )

    def test_ratchet_accepts_then_tightens(self, tmp_path):
        findings = analyze(self.SRC)
        assert findings
        path = str(tmp_path / "baseline.json")
        write_baseline(path, findings)
        baseline = load_baseline(path)
        new, accepted, stale = partition(findings, baseline)
        assert new == [] and len(accepted) == len(findings) and stale == []
        # a second, NEW finding is not covered by the old baseline
        worse = self.SRC + (
            "def poll2(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except BaseException:\n"
            "        pass\n"
        )
        new, accepted, _ = partition(analyze(worse), baseline)
        assert len(new) == 1 and len(accepted) == len(findings)

    def test_fingerprints_survive_line_moves(self):
        a = analyze(self.SRC)
        b = analyze("import os\n\n\n" + self.SRC)  # shifted 3 lines down
        assert [f.fingerprint for f in a] == [f.fingerprint for f in b]
        assert a[0].line != b[0].line

    def test_identical_findings_ratchet_by_count(self, tmp_path):
        # fixing ONE of two byte-identical findings must not resurface the
        # survivor as new (no occurrence renumbering)
        two = (
            "def a(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n"
            "def b(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        findings = analyze(two)
        assert len(findings) == 2
        assert findings[0].fingerprint == findings[1].fingerprint
        path = str(tmp_path / "b.json")
        write_baseline(path, findings)
        baseline = load_baseline(path)
        # one fixed: survivor stays accepted, shrunk count reported stale
        one = analyze(two.rsplit("def b", 1)[0])
        new, accepted, stale = partition(one, baseline)
        assert new == [] and len(accepted) == 1 and stale == [
            findings[0].fingerprint
        ]
        # a THIRD identical occurrence exceeds the budget -> new
        three = two + two.replace("def a", "def c").rsplit("def b", 1)[0]
        new, accepted, _ = partition(analyze(three), baseline)
        assert len(accepted) == 2 and len(new) == 1

    def test_stale_entries_reported_not_fatal(self, tmp_path):
        findings = analyze(self.SRC)
        path = str(tmp_path / "baseline.json")
        write_baseline(path, findings)
        new, accepted, stale = partition([], load_baseline(path))
        assert new == [] and accepted == [] and len(stale) == len(findings)


# ------------------------------------------------------------- CLI / JSON

class TestCli:
    def _run(self, tmp_path, src, *args):
        mod = tmp_path / "mod.py"
        mod.write_text(src)
        return subprocess.run(
            [sys.executable, "-m", "deepfm_tpu.analysis", str(mod), *args],
            capture_output=True, text=True, cwd=REPO,
        )

    def test_json_schema_and_exit_codes(self, tmp_path):
        proc = self._run(
            tmp_path,
            "def f(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n",
            "--format", "json",
        )
        assert proc.returncode == 1, proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["schema"] == 1
        assert doc["counts"]["new"] == len(doc["new"]) == 1
        rec = doc["new"][0]
        for key in ("rule", "path", "line", "col", "message", "hint",
                    "fingerprint", "source"):
            assert key in rec
        assert rec["rule"] == "swallowed-exception"

    def test_clean_file_exits_zero(self, tmp_path):
        proc = self._run(tmp_path, "def f(x):\n    return x + 1\n")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_syntax_error_exits_two_not_one(self, tmp_path):
        # a broken analyzer input must never read as "new findings"
        proc = self._run(tmp_path, "def f(:\n")
        assert proc.returncode == 2, (proc.returncode, proc.stderr)
        assert "syntax error" in proc.stderr

    def test_fingerprints_stable_across_invoking_cwd(self, tmp_path):
        # the checked-in baseline must hold from any working directory:
        # paths anchor to the repo root (.git), not os.getcwd()
        proc = subprocess.run(
            [sys.executable, "-m", "deepfm_tpu.analysis",
             os.path.join(REPO, "deepfm_tpu"),
             "--baseline", os.path.join(REPO, "analysis_baseline.json")],
            capture_output=True, text=True, cwd=str(tmp_path),
            env={**os.environ, "PYTHONPATH": REPO},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_write_baseline_subset_merges_not_truncates(self, tmp_path):
        # rewriting the baseline from a subset run must keep other files'
        # accepted debt
        repo = tmp_path / "scratch"
        (repo / ".git").mkdir(parents=True)
        bad = (
            "def f(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        (repo / "a.py").write_text(bad)
        (repo / "b.py").write_text(bad.replace("def f", "def g"))
        env = {**os.environ, "PYTHONPATH": REPO}

        def run(*argv):
            return subprocess.run(
                [sys.executable, "-m", "deepfm_tpu.analysis", *argv],
                capture_output=True, text=True, cwd=str(repo), env=env,
            )

        assert run(str(repo), "--write-baseline").returncode == 0
        # subset re-write over a.py only: b.py's debt must survive
        assert run(str(repo / "a.py"), "--write-baseline").returncode == 0
        proc = run(str(repo))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_default_baseline_resolves_against_repo_root(self, tmp_path):
        # a scratch repo with accepted debt must gate green from ANY cwd
        # without --baseline (default resolves against the .git root the
        # finding paths anchor to, not the invoker's cwd)
        repo = tmp_path / "scratch"
        (repo / ".git").mkdir(parents=True)
        (repo / "mod.py").write_text(
            "def f(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        env = {**os.environ, "PYTHONPATH": REPO}
        proc = subprocess.run(
            [sys.executable, "-m", "deepfm_tpu.analysis",
             str(repo / "mod.py"), "--write-baseline"],
            capture_output=True, text=True, cwd=str(tmp_path), env=env,
        )
        assert proc.returncode == 0, proc.stderr
        assert (repo / "analysis_baseline.json").exists()  # at the ROOT
        elsewhere = tmp_path / "elsewhere"
        elsewhere.mkdir()
        proc = subprocess.run(
            [sys.executable, "-m", "deepfm_tpu.analysis",
             str(repo / "mod.py")],
            capture_output=True, text=True, cwd=str(elsewhere), env=env,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_trace_audit_crash_exits_two(self, tmp_path, monkeypatch):
        # a crashing audit is an analyzer failure, not "new findings"
        import deepfm_tpu.analysis.trace_audit as ta
        from deepfm_tpu.analysis import cli as cli_mod

        def boom():
            raise RuntimeError("broken jax install")

        monkeypatch.setattr(ta, "run_trace_audit", boom)
        mod = tmp_path / "clean.py"
        mod.write_text("def f(x):\n    return x\n")
        assert cli_mod.main([str(mod), "--trace-audit"]) == 2

    def test_corrupt_baseline_exits_two_not_one(self, tmp_path):
        bad = tmp_path / "b.json"
        bad.write_text("<<<<<<< merge conflict\n")
        proc = self._run(tmp_path, "def f(x):\n    return x\n",
                         "--baseline", str(bad))
        assert proc.returncode == 2, (proc.returncode, proc.stderr)
        assert "baseline" in proc.stderr

    def test_write_baseline_then_green(self, tmp_path):
        src = (
            "def f(fn):\n"
            "    try:\n"
            "        fn()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        base = tmp_path / "b.json"
        proc = self._run(tmp_path, src, "--write-baseline",
                         "--baseline", str(base))
        assert proc.returncode == 0
        proc = self._run(tmp_path, src, "--baseline", str(base))
        assert proc.returncode == 0, proc.stdout


# --------------------------------------------------- the repo gate itself

class TestRepoIsClean:
    """The analyzer over the real package IS a tier-1 test: a regression
    that reintroduces a flagged idiom fails pytest, not just CI."""

    def test_package_has_no_unbaselined_findings(self):
        import os

        files = {}
        for dirpath, dirnames, names in os.walk(os.path.join(REPO, "deepfm_tpu")):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for n in names:
                if n.endswith(".py"):
                    full = os.path.join(dirpath, n)
                    rel = os.path.relpath(full, REPO).replace(os.sep, "/")
                    with open(full, encoding="utf-8") as f:
                        files[rel] = f.read()
        findings = run_ast_engine(files)
        baseline = load_baseline(os.path.join(REPO, "analysis_baseline.json"))
        new, _accepted, _stale = partition(findings, baseline)
        assert new == [], "\n".join(f.render() for f in new)


# ---------------------------------------------------------------- engine 3

def canalyze(src, path: str = "mod.py"):
    """Engine 1 + engine 3 over one in-memory module (or a {path: src}
    dict for cross-module cases)."""
    files = {path: src} if isinstance(src, str) else src
    return run_ast_engine(files, concurrency=True)


class TestBlockingUnderLock:
    def test_sleep_under_lock_caught(self):
        f = canalyze(
            "import threading, time\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1)\n"
        )
        assert "blocking-under-lock" in rules_of(f)
        assert any("time.sleep" in x.message for x in f)

    def test_sleep_outside_lock_clean(self):
        f = canalyze(
            "import threading, time\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            n = 1\n"
            "        time.sleep(1)\n"
        )
        assert "blocking-under-lock" not in rules_of(f)

    def test_helper_http_reached_under_lock_caught(self):
        # interprocedural: the blocking op lives in a helper; the lock is
        # held at the CALL site
        f = canalyze(
            "import threading\n"
            "from urllib.request import urlopen\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def _fetch(self):\n"
            "        return urlopen('http://x').read()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self._fetch()\n"
        )
        hits = [x for x in f if x.rule == "blocking-under-lock"]
        assert hits and any("_fetch" in x.message for x in hits)
        # the finding anchors at the held call site, not the helper
        assert hits[0].line == 10

    def test_cross_module_store_call_under_lock_caught(self):
        f = canalyze({
            "pkg/__init__.py": "",
            "pkg/store.py": (
                "import os\n"
                "def list_versions(root):\n"
                "    return os.listdir(root)\n"
            ),
            "pkg/user.py": (
                "import threading\n"
                "from .store import list_versions\n"
                "class A:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lock:\n"
                "            return list_versions('/x')\n"
            ),
        })
        hits = [x for x in f if x.rule == "blocking-under-lock"]
        assert hits and hits[0].path == "pkg/user.py"

    def test_export_lock_idiom_blessed(self):
        # a lock NAMED for serializing I/O is the sanctioned Tracer idiom
        f = canalyze(
            "import threading\n"
            "class T:\n"
            "    def __init__(self):\n"
            "        self._export_lock = threading.Lock()\n"
            "    def export(self):\n"
            "        with self._export_lock:\n"
            "            open('/tmp/x', 'w').write('y')\n"
        )
        assert "blocking-under-lock" not in rules_of(f)

    def test_nonblocking_queue_get_clean(self):
        f = canalyze(
            "import threading, queue\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = queue.Queue()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            return self._q.get_nowait()\n"
        )
        assert "blocking-under-lock" not in rules_of(f)

    def test_blocking_queue_get_under_lock_caught(self):
        f = canalyze(
            "import threading, queue\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = queue.Queue()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            return self._q.get(timeout=1)\n"
        )
        assert "blocking-under-lock" in rules_of(f)

    def test_acquire_release_region_counts_as_held(self):
        f = canalyze(
            "import threading, time\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        self._lock.acquire()\n"
            "        try:\n"
            "            time.sleep(1)\n"
            "        finally:\n"
            "            self._lock.release()\n"
        )
        assert "blocking-under-lock" in rules_of(f)

    def test_condition_wait_releases_own_lock(self):
        # cv.wait() drops the condition's lock while blocked — the
        # canonical consumer loop is clean
        f = canalyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "    def f(self):\n"
            "        with self._cv:\n"
            "            while True:\n"
            "                self._cv.wait()\n"
        )
        assert "blocking-under-lock" not in rules_of(f)


class TestLockOrderCycle:
    TWO_LOCK_CYCLE = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def f(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def g(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )

    def test_opposite_order_caught_on_both_edges(self):
        f = canalyze(self.TWO_LOCK_CYCLE)
        hits = [x for x in f if x.rule == "lock-order-cycle"]
        assert len(hits) == 2
        assert {x.line for x in hits} == {8, 12}

    def test_consistent_order_clean(self):
        f = canalyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def g(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
        )
        assert "lock-order-cycle" not in rules_of(f)

    def test_self_deadlock_through_helper_caught(self):
        # f holds the plain Lock and calls g, which takes it again —
        # certain deadlock, visible only interprocedurally
        f = canalyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def g(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self.g()\n"
        )
        hits = [x for x in f if x.rule == "lock-order-cycle"]
        assert hits and "self-deadlock" in hits[0].message

    def test_rlock_reentry_clean(self):
        f = canalyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def g(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self.g()\n"
        )
        assert "lock-order-cycle" not in rules_of(f)

    def test_cross_class_cycle_through_calls_caught(self):
        # A.f holds A's lock and calls B.g (acquires B's lock); B.h holds
        # B's lock and calls back into A.k (acquires A's lock)
        f = canalyze(
            "import threading\n"
            "class B:\n"
            "    def __init__(self, a: 'A'):\n"
            "        self._block = threading.Lock()\n"
            "        self._a = a\n"
            "    def g(self):\n"
            "        with self._block:\n"
            "            pass\n"
            "    def h(self):\n"
            "        with self._block:\n"
            "            self._a.k()\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._alock = threading.Lock()\n"
            "        self._b = B(self)\n"
            "    def k(self):\n"
            "        with self._alock:\n"
            "            pass\n"
            "    def f(self):\n"
            "        with self._alock:\n"
            "            self._b.g()\n"
        )
        assert "lock-order-cycle" in rules_of(f)


class TestSignalUnsafeLock:
    def test_plain_lock_handler_caught(self):
        f = canalyze(
            "import signal, threading\n"
            "_lock = threading.Lock()\n"
            "def handler(signum, frame):\n"
            "    with _lock:\n"
            "        pass\n"
            "def normal():\n"
            "    with _lock:\n"
            "        pass\n"
            "signal.signal(signal.SIGTERM, handler)\n"
        )
        hits = [x for x in f if x.rule == "signal-unsafe-lock"]
        assert hits and "handler" in hits[0].message

    def test_rlock_handler_clean(self):
        # the FlightRecorder idiom: RLock makes handler re-entry safe
        f = canalyze(
            "import signal, threading\n"
            "_lock = threading.RLock()\n"
            "def handler(signum, frame):\n"
            "    with _lock:\n"
            "        pass\n"
            "def normal():\n"
            "    with _lock:\n"
            "        pass\n"
            "signal.signal(signal.SIGTERM, handler)\n"
        )
        assert "signal-unsafe-lock" not in rules_of(f)

    def test_handler_only_lock_clean(self):
        # no normal-path acquirer -> no interleaving to deadlock with
        f = canalyze(
            "import signal, threading\n"
            "_lock = threading.Lock()\n"
            "def handler(signum, frame):\n"
            "    with _lock:\n"
            "        pass\n"
            "signal.signal(signal.SIGTERM, handler)\n"
        )
        assert "signal-unsafe-lock" not in rules_of(f)

    def test_stop_callback_through_helper_caught(self):
        # PreemptionGuard stop-callbacks run from the signal path; the
        # lock acquire sits one call deep
        f = canalyze(
            "import threading\n"
            "class W:\n"
            "    def __init__(self, guard):\n"
            "        self._lock = threading.Lock()\n"
            "        guard.register_stop_callback(self._on_stop)\n"
            "    def _flush(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "    def _on_stop(self):\n"
            "        self._flush()\n"
            "    def normal(self):\n"
            "        with self._lock:\n"
            "            pass\n"
        )
        assert "signal-unsafe-lock" in rules_of(f)

    def test_excepthook_plain_lock_caught(self):
        f = canalyze(
            "import sys, threading\n"
            "_lock = threading.Lock()\n"
            "def hook(t, v, tb):\n"
            "    with _lock:\n"
            "        pass\n"
            "def normal():\n"
            "    with _lock:\n"
            "        pass\n"
            "sys.excepthook = hook\n"
        )
        assert "signal-unsafe-lock" in rules_of(f)

    def test_lockfree_event_handler_clean(self):
        # the sanctioned shape: the handler only sets an Event
        f = canalyze(
            "import signal, threading\n"
            "_stop = threading.Event()\n"
            "signal.signal(signal.SIGTERM, lambda s, fr: _stop.set())\n"
        )
        assert "signal-unsafe-lock" not in rules_of(f)


class TestThreadLifecycle:
    def test_started_never_joined_caught(self):
        f = canalyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._t = threading.Thread(target=self._run,\n"
            "                                   daemon=True)\n"
            "        self._t.start()\n"
            "    def _run(self):\n"
            "        pass\n"
        )
        hits = [x for x in f if x.rule == "thread-lifecycle"]
        assert hits and "no stop path" in hits[0].message

    def test_join_path_clean(self):
        f = canalyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._t = threading.Thread(target=self._run,\n"
            "                                   daemon=True)\n"
            "        self._t.start()\n"
            "    def _run(self):\n"
            "        pass\n"
            "    def close(self):\n"
            "        self._t.join(timeout=5)\n"
        )
        assert "thread-lifecycle" not in rules_of(f)

    def test_fire_and_forget_non_daemon_caught(self):
        f = canalyze(
            "import threading\n"
            "def work():\n"
            "    pass\n"
            "def go():\n"
            "    threading.Thread(target=work).start()\n"
        )
        hits = [x for x in f if x.rule == "thread-lifecycle"]
        assert hits and "non-daemon" in hits[0].message

    def test_daemon_fire_and_forget_durable_state_caught(self):
        # the daemon is killed mid-write at interpreter exit
        f = canalyze(
            "import threading\n"
            "def work():\n"
            "    with open('/tmp/x', 'w') as fh:\n"
            "        fh.write('y')\n"
            "def go():\n"
            "    threading.Thread(target=work, daemon=True).start()\n"
        )
        hits = [x for x in f if x.rule == "thread-lifecycle"]
        assert hits and "durable" in hits[0].message

    def test_daemon_fire_and_forget_pure_compute_clean(self):
        f = canalyze(
            "import threading\n"
            "def work():\n"
            "    return 1 + 1\n"
            "def go():\n"
            "    threading.Thread(target=work, daemon=True).start()\n"
        )
        assert "thread-lifecycle" not in rules_of(f)


class TestGuardedByAcquireRelease:
    """Satellite: acquire()/try/finally-release() pairs are guarded
    regions for BOTH engines, not just `with` blocks."""

    def test_mutation_inside_pair_not_flagged_elsewhere_is(self):
        f = analyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.n = 0\n"
            "    def f(self):\n"
            "        self._lock.acquire()\n"
            "        try:\n"
            "            self.n += 1\n"
            "        finally:\n"
            "            self._lock.release()\n"
            "    def bad(self):\n"
            "        self.n = 5\n"
        )
        hits = [x for x in f if x.rule == "guarded-by"]
        assert len(hits) == 1 and hits[0].line == 13

    def test_mutation_after_release_flagged(self):
        f = analyze(
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.n = 0\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self.n += 1\n"
            "    def g(self):\n"
            "        self._lock.acquire()\n"
            "        self.n += 1\n"
            "        self._lock.release()\n"
            "        self.n = 2\n"
        )
        hits = [x for x in f if x.rule == "guarded-by"]
        assert len(hits) == 1 and hits[0].line == 13


class TestConcurrencySuppressions:
    SLEEPY = (
        "import threading, time\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)  # da:allow[blocking-under-lock] "
        "startup path, single-threaded by construction\n"
    )

    def test_da_allow_covers_concurrency_rules(self):
        f = canalyze(self.SLEEPY)
        assert "blocking-under-lock" not in rules_of(f)
        assert "unused-suppression" not in rules_of(f)

    def test_concurrency_suppression_not_unused_without_flag(self):
        # a da:allow for a rule THIS run never evaluated must not read
        # as dead — or every plain run would flag the concurrency
        # suppressions and vice versa
        f = run_ast_engine({"mod.py": self.SLEEPY}, concurrency=False)
        assert "unused-suppression" not in rules_of(f)

    def test_dead_concurrency_suppression_flagged_with_flag(self):
        src = self.SLEEPY.replace("time.sleep(1)", "n = 1")
        f = canalyze(src)
        assert "unused-suppression" in rules_of(f)


class TestConcurrencyCli:
    """Seeded violations through the real CLI: each class exits 1, the
    clean repo exits 0 (the ratcheted gate check.sh runs)."""

    def _run(self, tmp_path, src, *args):
        mod = tmp_path / "mod.py"
        mod.write_text(src)
        return subprocess.run(
            [sys.executable, "-m", "deepfm_tpu.analysis", str(mod),
             "--concurrency", *args],
            capture_output=True, text=True, cwd=REPO,
        )

    def test_seeded_sleep_under_lock_exits_one(self, tmp_path):
        proc = self._run(
            tmp_path,
            "import threading, time\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            time.sleep(30)\n",
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "blocking-under-lock" in proc.stdout

    def test_seeded_two_lock_cycle_exits_one(self, tmp_path):
        proc = self._run(tmp_path, TestLockOrderCycle.TWO_LOCK_CYCLE)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "lock-order-cycle" in proc.stdout

    def test_seeded_plain_lock_signal_handler_exits_one(self, tmp_path):
        proc = self._run(
            tmp_path,
            "import signal, threading\n"
            "_lock = threading.Lock()\n"
            "def handler(signum, frame):\n"
            "    with _lock:\n"
            "        pass\n"
            "def normal():\n"
            "    with _lock:\n"
            "        pass\n"
            "signal.signal(signal.SIGTERM, handler)\n",
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "signal-unsafe-lock" in proc.stdout

    def test_github_format_emits_error_annotations(self, tmp_path):
        proc = self._run(
            tmp_path,
            "import threading, time\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            time.sleep(30)\n",
            "--format", "github",
        )
        assert proc.returncode == 1
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith("::error "))
        # tmp file lives outside the repo root, so the path is relative
        # but still ends at the analyzed module
        assert "mod.py" in line.split(",")[0]
        assert "title=blocking-under-lock" in line

    def test_github_format_clean_exits_zero(self, tmp_path):
        proc = self._run(tmp_path, "def f(x):\n    return x + 1\n",
                        "--format", "github")
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestRepoIsConcurrencyClean:
    """The concurrency gate over the real package IS a tier-1 test, and
    it ratchets at ZERO accepted debt: the baseline holds no entry for
    any engine-3 rule."""

    def test_package_clean_under_concurrency_engine(self):
        files = {}
        for dirpath, dirnames, names in os.walk(
                os.path.join(REPO, "deepfm_tpu")):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for n in names:
                if n.endswith(".py"):
                    full = os.path.join(dirpath, n)
                    rel = os.path.relpath(full, REPO).replace(os.sep, "/")
                    with open(full, encoding="utf-8") as f:
                        files[rel] = f.read()
        findings = run_ast_engine(files, concurrency=True)
        baseline = load_baseline(os.path.join(REPO, "analysis_baseline.json"))
        from deepfm_tpu.analysis import CONCURRENCY_RULES
        assert not any(e.get("rule") in CONCURRENCY_RULES
                       for e in baseline.values()), \
            "engine-3 debt must be fixed or da:allow'd inline, never baselined"
        new, _accepted, _stale = partition(findings, baseline)
        assert new == [], "\n".join(f.render() for f in new)


# ---------------------------------------------------------------- engine 2

class TestTraceAudit:
    def test_real_entrypoints_hold_all_contracts(self):
        from deepfm_tpu.analysis.trace_audit import run_trace_audit

        findings = run_trace_audit()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_off_bucket_shape_caught(self, monkeypatch):
        import deepfm_tpu.serve.batcher as batcher
        from deepfm_tpu.analysis import trace_audit

        monkeypatch.setattr(batcher, "pick_bucket",
                            lambda buckets, rows: 7)  # never a bucket
        findings = trace_audit.audit_buckets()
        assert findings and findings[0].rule == "trace-recompile"
        assert "precompiled bucket" in findings[0].message

    def test_bucket_coverage_holds_for_any_sorted_set(self):
        from deepfm_tpu.analysis.trace_audit import audit_buckets

        assert audit_buckets(buckets=(8, 32)) == []
        assert audit_buckets(buckets=(16,)) == []

    def test_trace_findings_fingerprint_per_contract(self):
        # two different defects, same rule+path, must not share a
        # fingerprint (a baselined one could mask the other)
        from deepfm_tpu.analysis.findings import fingerprint_findings
        from deepfm_tpu.analysis.trace_audit import _finding

        a = _finding("trace-dtype", "msg A", where="deepfm_tpu/x.py",
                     slug="predict-f64")
        b = _finding("trace-dtype", "msg B", where="deepfm_tpu/x.py",
                     slug="predict-out-dtype")
        fingerprint_findings([a, b])
        assert a.fingerprint != b.fingerprint

    def test_audit_probes_the_engines_real_defaults(self):
        # imported, not copied: a serving-default change re-points the audit
        from deepfm_tpu.analysis.trace_audit import _default_buckets
        from deepfm_tpu.serve.batcher import DEFAULT_BUCKETS

        assert _default_buckets() is DEFAULT_BUCKETS

    def test_undonated_train_step_caught(self, monkeypatch):
        import jax

        import deepfm_tpu.train.step as step_mod
        from deepfm_tpu.analysis import trace_audit

        # swap the canonical constructor for an undonated jit and re-audit
        monkeypatch.setattr(
            step_mod, "jitted_train_step",
            lambda cfg, **kw: jax.jit(step_mod.make_train_step(cfg)),
        )
        findings = trace_audit.audit_train_step()
        assert any(f.rule == "trace-donation" for f in findings), \
            "\n".join(f.render() for f in findings)

    def test_constant_baked_params_caught(self):
        """load_servable-style closure predict (params as constants) must
        fail the weights-are-arguments check."""
        import jax

        from deepfm_tpu.analysis.trace_audit import (
            _abstract_payload,
            _audit_cfg,
        )

        cfg = _audit_cfg()
        model, payload = _abstract_payload(cfg)
        n_leaves = len(jax.tree_util.tree_leaves(payload))

        @jax.jit
        def predict_closed(feat_ids, feat_vals):
            # params closed over -> lowered signature has only 2 inputs
            return feat_ids.sum() + feat_vals.sum()

        lo = predict_closed.lower(
            jax.ShapeDtypeStruct((8, cfg.model.field_size), jax.numpy.int64),
            jax.ShapeDtypeStruct((8, cfg.model.field_size), jax.numpy.float32),
        )
        n_in = len(jax.tree_util.tree_leaves(lo.in_avals))
        assert n_in != n_leaves + 2  # the audit's discriminator fires


class TestCollectiveContract:
    """Engine-2 collective-traffic contract (trace_audit.py
    audit_spmd_exchange): the alltoall-mode sharded train step must not
    move the dense row tensor outside the lax.cond fallback arm."""

    def _lower_psum(self):
        import jax
        import jax.numpy as jnp

        from deepfm_tpu.analysis.trace_audit import _audit_cfg
        from deepfm_tpu.core.config import MeshConfig
        from deepfm_tpu.parallel import (
            abstract_spmd_state, build_mesh, make_context,
            make_spmd_train_step,
        )

        base = _audit_cfg().with_overrides(data={"batch_size": 128})
        mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
        c = base.with_overrides(model={"shard_exchange": "psum"})
        ctx = make_context(c, mesh)
        state = abstract_spmd_state(ctx)
        b, f = 128, c.model.field_size
        batch = {
            "feat_ids": jax.ShapeDtypeStruct((b, f), jnp.int32),
            "feat_vals": jax.ShapeDtypeStruct((b, f), jnp.float32),
            "label": jax.ShapeDtypeStruct((b,), jnp.float32),
        }
        step = make_spmd_train_step(ctx, donate=False)
        text = step.lower(state, batch).as_text()
        return text, {(64, f, 32), (64, f)}

    def test_exchange_contract_clean_on_real_step(self):
        from deepfm_tpu.analysis.trace_audit import audit_spmd_exchange

        findings = audit_spmd_exchange()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_dense_regression_caught(self):
        """A psum-mode lowering fed through the alltoall contract — the
        shape a regression would take if resolve_shard_exchange wiring
        broke — must be flagged on BOTH axes: dense traffic on the main
        line, and no all_to_all present."""
        from deepfm_tpu.analysis.trace_audit import (
            check_exchange_collectives,
        )

        text, dense = self._lower_psum()
        viol = check_exchange_collectives(text, dense, mode="alltoall")
        assert any("UNCONDITIONAL main line" in v.message for v in viol)
        assert any("WITHOUT any all_to_all" in v.message for v in viol)
        assert all(v.rule == "trace-collective" for v in viol)
        # the same lowering satisfies the psum contract (detector sees
        # the dense all-reduce)...
        assert check_exchange_collectives(text, dense, mode="psum") == []
        # ...and a blind detector (wrong dense shapes) fails LOUDLY in
        # psum mode instead of passing alltoall vacuously
        blind = check_exchange_collectives(
            text, {(1, 2, 3)}, mode="psum"
        )
        assert blind and "detector" in blind[0].message

    def test_collective_scanner_branch_indexing(self):
        """summarize_collectives must separate case branches (the fallback
        arm may be dense; the exchange arm may not) and read region-op
        signatures from their closing line."""
        from deepfm_tpu.analysis.trace_audit import summarize_collectives

        text = "\n".join([
            "module {",
            "  func.func private @body(%arg0: tensor<8x4xf32>)"
            " -> tensor<4x3xf32> {",
            '    %g = "stablehlo.all_gather"(%arg0) : (tensor<8x4xf32>)'
            " -> (tensor<8x16xf32>)",
            '    %1 = "stablehlo.case"(%i) ({',
            '      %2 = "stablehlo.all_to_all"(%arg0) :'
            " (tensor<4x2xi32>) -> tensor<4x2xi32>",
            "      stablehlo.return %2 : tensor<4x2xi32>",
            "    }, {",
            '      %3 = "stablehlo.all_reduce"(%arg0) ({',
            "      ^bb0(%a: tensor<f32>, %b: tensor<f32>):",
            "        %s = stablehlo.add %a, %b : tensor<f32>",
            "        stablehlo.return %s : tensor<f32>",
            "      }) : (tensor<16x8xf32>) -> tensor<16x8xf32>",
            "      stablehlo.return %3 : tensor<16x8xf32>",
            "    }) : (tensor<i32>) -> tensor<4x3xf32>",
            "    return %1 : tensor<4x3xf32>",
            "  }",
            "}",
        ])
        cols = summarize_collectives(text)
        by_op = {c["op"]: c for c in cols}
        assert by_op["all_gather"]["branch"] is None
        assert by_op["all_gather"]["shapes"] == [(8, 4)]
        assert by_op["all_to_all"]["branch"] == (1, 0)
        assert by_op["all_reduce"]["branch"] == (1, 1)
        # region-op signature picked up from the closing line
        assert by_op["all_reduce"]["shapes"] == [(16, 8)]


class TestZeroUpdateContract:
    """Engine-2 zero-update contract (trace_audit.audit_zero_update): the
    dp-sharded weight update must lower with reduce-scatter (never a
    grad-sized data-axis all-reduce) on dense grads and dp-sharded
    (1/dp per-shard) moment leaves — and each seeded violation (a
    replicated-path lowering fed through the contract; replicated
    moments behind the flag) is caught."""

    def _replicated_lowering(self):
        import jax
        import jax.numpy as jnp

        from deepfm_tpu.analysis.trace_audit import _audit_cfg
        from deepfm_tpu.core.config import MeshConfig
        from deepfm_tpu.parallel import (
            abstract_spmd_state, build_mesh, make_context,
            make_spmd_train_step,
        )

        base = _audit_cfg().with_overrides(
            data={"batch_size": 128},
            optimizer={"zero_sharding": "off"},
        )
        mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
        ctx = make_context(base, mesh)
        state = abstract_spmd_state(ctx)
        b, f = 128, base.model.field_size
        batch = {
            "feat_ids": jax.ShapeDtypeStruct((b, f), jnp.int32),
            "feat_vals": jax.ShapeDtypeStruct((b, f), jnp.float32),
            "label": jax.ShapeDtypeStruct((b,), jnp.float32),
        }
        step = make_spmd_train_step(ctx, donate=False)
        return ctx, state, step.lower(state, batch).as_text()

    def test_real_zero_step_holds_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_zero_update

        findings = audit_zero_update()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_allreduce_lowering_caught(self):
        """A replicated-path (zero=off) lowering fed through the zero
        contract — the shape the regression takes if the spmd wiring
        silently falls back to pmean + full-width update — must be
        flagged on all three axes: the surviving data-axis all-reduce,
        the missing per-leaf reduce-scatter, the missing window gather."""
        from deepfm_tpu.analysis.trace_audit import check_zero_collectives

        _, _, text = self._replicated_lowering()
        viol = check_zero_collectives(
            text, dp=2, mp=4, n_sharded_leaves=11
        )
        slugs = {v.source for v in viol}
        assert "zero-dense-allreduce" in slugs
        assert "zero-reduce-scatter-missing" in slugs
        assert "zero-allgather-missing" in slugs
        assert all(v.rule == "trace-collective" for v in viol)

    def test_seeded_replicated_moments_caught(self):
        """Replicated moments behind the flag: (a) a plain opt_state with
        no zero_dp layout at all; (b) a zero-layout tree whose flat
        moment leaves carry replicated shardings — both flagged."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepfm_tpu.analysis.trace_audit import (
            check_zero_state_sharding,
        )
        from deepfm_tpu.parallel import abstract_spmd_state

        ctx, state, _ = self._replicated_lowering()
        viol = check_zero_state_sharding(
            ctx.state_shardings.opt_state, state.opt_state, dp=2
        )
        assert [v.source for v in viol] == ["zero-moments-unsharded"]
        # (b): the sharded layout with its data axis stripped — every
        # flat moment leaf claims full-size per-shard residency
        from deepfm_tpu.core.config import MeshConfig
        from deepfm_tpu.parallel import build_mesh, make_context

        base = ctx.cfg.with_overrides(optimizer={"zero_sharding": "on"})
        mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
        zctx = make_context(base, mesh)
        zstate = abstract_spmd_state(zctx)
        stripped = jax.tree_util.tree_map(
            lambda sh: NamedSharding(mesh, P()), zctx.state_shardings
        )
        viol = check_zero_state_sharding(
            stripped.opt_state, zstate.opt_state, dp=2
        )
        assert [v.source for v in viol] == ["zero-moments-replicated"]


class TestSeededViolationsEndToEnd:
    """The acceptance trio: a tracer .item() inside jit, an unguarded
    mutation of a locked attribute, and an off-bucket request shape are
    each caught by the suite."""

    def test_trio(self, monkeypatch):
        item_src = (
            "import jax\n"
            "@jax.jit\n"
            "def predict(x):\n"
            "    return x.sum().item()\n"
        )
        race_src = GUARDED_CLASS.replace("{MUTATION}", "self.last_ms = ms")
        assert "tracer-host-op" in rules_of(analyze(item_src))
        assert "guarded-by" in rules_of(analyze(race_src))

        import deepfm_tpu.serve.batcher as batcher
        from deepfm_tpu.analysis import trace_audit

        monkeypatch.setattr(batcher, "pick_bucket",
                            lambda buckets, rows: rows)  # raw shape leaks
        findings = trace_audit.audit_buckets(buckets=(8, 32, 128, 512))
        assert any(f.rule == "trace-recompile" for f in findings)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))


class TestPagingContract:
    """The tiered store's paging trace-audit contract
    (trace_audit.audit_paged_step, wired into scripts/check.sh via
    run_trace_audit): the lowered steady-state slot-space step contains
    no host transfers outside the designated staging arguments."""

    def test_real_paged_step_holds_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_paged_step

        findings = audit_paged_step()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_smuggled_host_read_caught(self):
        """A step that sneaks a device->host transfer (concretizing a
        traced value) must be caught by the transfer contract."""
        import jax
        import jax.numpy as jnp

        from deepfm_tpu.analysis.trace_audit import audit_paged_step
        from deepfm_tpu.tiered.step import make_paged_train_step

        def smuggling_builder(cfg, capacity):
            real = make_paged_train_step(cfg, capacity, donate=False)

            def step(state, batch, stage_slots, stage):
                # the sneak: host-reads the traced slot stream
                if int(jnp.sum(batch["slot_ids"])) >= 0:
                    pass
                return real(state, batch, stage_slots, stage)

            return jax.jit(step)

        findings = audit_paged_step(step_builder=smuggling_builder)
        assert any(f.rule == "trace-transfer" for f in findings), findings

    def test_baked_staging_pack_caught(self):
        """A step that drops the staging arguments and bakes concrete
        staged rows into the executable is an undeclared per-step host
        transfer — convicted by the leaf-count contract."""
        import jax
        import jax.numpy as jnp

        from deepfm_tpu.analysis.trace_audit import (
            _PAGED_STAGE,
            audit_paged_step,
        )
        from deepfm_tpu.tiered.step import make_paged_train_step
        from deepfm_tpu.tiered.trainer import (
            _rest_template,
            _split_rest,
            _widths,
        )

        def baked_builder(cfg, capacity):
            real = make_paged_train_step(cfg, capacity, donate=False)
            template = _rest_template(cfg)
            _, _, _, _, keys = _split_rest(cfg, template)
            widths = _widths(cfg, keys)
            p = _PAGED_STAGE
            slots = jnp.arange(p, dtype=jnp.int32)
            stage = {k: {part: jnp.zeros(
                (p,) if w == 1 else (p, w), jnp.float32)
                for part in ("rows", "m", "v")}
                for k, w in widths.items()}

            def step(state, batch):
                return real(state, batch, slots, stage)

            return jax.jit(step)

        findings = audit_paged_step(step_builder=baked_builder)
        assert any(f.rule == "trace-transfer"
                   and "baked" in f.message for f in findings), findings

    def test_undonated_paged_step_caught(self):
        from deepfm_tpu.analysis.trace_audit import audit_paged_step
        from deepfm_tpu.tiered.step import make_paged_train_step

        findings = audit_paged_step(
            step_builder=lambda c, cap: make_paged_train_step(
                c, cap, donate=False))
        assert any(f.rule == "trace-donation" for f in findings), findings


class TestShardedPredictContract:
    """The serving pool's sharded-predict trace contract
    (trace_audit.audit_sharded_predict, wired into scripts/check.sh via
    run_trace_audit): all_to_all on the predict path, no dense row leak,
    per-group bucket coverage, swap-is-a-cache-hit."""

    def test_real_sharded_predict_holds_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_sharded_predict

        findings = audit_sharded_predict()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_dense_row_leak_caught(self):
        """A psum-mode predict lowering fed through the alltoall contract
        — the shape the regression takes if the pool's exchange wiring
        breaks — is flagged on both axes: dense traffic on the main
        line, and no all_to_all present."""
        import jax

        from deepfm_tpu.analysis.trace_audit import (
            _audit_cfg,
            check_exchange_collectives,
        )
        from deepfm_tpu.serve.pool.sharded import (
            abstract_serve_payload,
            build_serve_mesh,
            build_sharded_predict_with,
            make_serve_context,
        )

        cfg = _audit_cfg()
        mesh = build_serve_mesh(2, 4)
        ctx = make_serve_context(cfg, mesh, exchange="psum")
        pw = build_sharded_predict_with(ctx)
        f = ctx.cfg.model.field_size
        b = 32
        text = pw.lower(
            abstract_serve_payload(ctx),
            jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
            jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
        ).as_text()
        dense = {(b // 2, f, ctx.cfg.model.embedding_size), (b // 2, f)}
        viol = check_exchange_collectives(
            text, dense, mode="alltoall", variant="serve-seeded",
            where="deepfm_tpu/serve/pool/sharded.py",
        )
        assert any("UNCONDITIONAL main line" in v.message for v in viol)
        assert any("WITHOUT any all_to_all" in v.message for v in viol)
        assert all(v.rule == "trace-collective" for v in viol)
        # the same lowering satisfies the psum self-check
        assert check_exchange_collectives(
            text, dense, mode="psum", variant="serve-seeded") == []

    def test_seeded_off_bucket_and_indivisible_shape_caught(self):
        from deepfm_tpu.analysis.trace_audit import audit_group_buckets

        # a bucket that does not divide over the group's data axis is a
        # shape no group executable was compiled for
        findings = audit_group_buckets(buckets=(8, 12), data_parallel=8)
        assert any(f.rule == "trace-recompile"
                   and "data_parallel" in f.message for f in findings)
        # the plain off-bucket regression (engine dispatching raw sizes)
        # still rides the inherited admission audit
        import deepfm_tpu.serve.batcher as batcher
        orig = batcher.pick_bucket
        batcher.pick_bucket = lambda buckets, rows: rows
        try:
            findings = audit_group_buckets(
                buckets=(8, 32, 128, 512), data_parallel=2)
            assert any(f.rule == "trace-recompile" for f in findings)
        finally:
            batcher.pick_bucket = orig
        # clean on the real defaults at every audited group dp
        for dp in (1, 2, 4):
            assert audit_group_buckets(data_parallel=dp) == []

    def test_seeded_baked_payload_mixed_generation_caught(self):
        """A predict whose weights compile in as constants is exactly the
        mixed-generation hazard: each commit would build a NEW executable
        while old dispatches run the old one.  The leaf-count contract
        convicts it."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_sharded_predict
        from deepfm_tpu.models.base import get_model
        from deepfm_tpu.serve.pool.sharded import (
            build_sharded_predict_with,
        )

        def baked_builder(ctx):
            real = build_sharded_predict_with(ctx)
            model = get_model(ctx.cfg.model)
            params, mstate = model.init(
                jax.random.PRNGKey(0), ctx.cfg.model
            )
            concrete = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s),
                {"params": params, "model_state": mstate},
                ctx.payload_shardings,
            )

            @jax.jit
            def predict_baked(feat_ids, feat_vals):
                return real(concrete, feat_ids, feat_vals)

            return predict_baked

        findings = audit_sharded_predict(predict_builder=baked_builder)
        assert any(f.rule == "trace-recompile"
                   and "baked" in f.message for f in findings), findings


class TestMultitenantContract:
    """The fleet's executable-sharing trace contract
    (trace_audit.audit_multitenant, wired into scripts/check.sh via
    run_trace_audit): two distinct same-spec tenant payloads lower
    through ONE shard-group predict to identical modules with payload
    leaves as parameters."""

    def test_real_fleet_holds_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_multitenant

        findings = audit_multitenant()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_spec_divergent_tenants_caught(self):
        """A tenant whose model spec diverges (wider embeddings) cannot
        share the pool's executables: the audit convicts the sharing
        claim and NAMES the diverging field — the same field the config
        gate (core.config.EXECUTABLE_SPEC_FIELDS) refuses at load."""
        from deepfm_tpu.analysis.trace_audit import audit_multitenant

        findings = audit_multitenant(
            tenant_models=[{}, {"embedding_size": 64}]
        )
        assert any(
            f.rule == "trace-recompile"
            and "spec-divergent" in f.message
            and "embedding_size" in f.message
            for f in findings
        ), findings

    def test_seeded_baked_tenant_payload_caught(self):
        """A tenant payload compiled in as constants is the per-tenant-
        module regression: every tenant swap would build a NEW
        executable.  The leaf-count discriminator convicts it."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_multitenant
        from deepfm_tpu.models.base import get_model
        from deepfm_tpu.serve.pool.sharded import (
            build_sharded_predict_with,
        )

        def baked_builder(ctx):
            real = build_sharded_predict_with(ctx)
            model = get_model(ctx.cfg.model)
            params, mstate = model.init(
                jax.random.PRNGKey(0), ctx.cfg.model
            )
            concrete = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s),
                {"params": params, "model_state": mstate},
                ctx.payload_shardings,
            )

            @jax.jit
            def predict_baked(feat_ids, feat_vals):
                return real(concrete, feat_ids, feat_vals)

            return predict_baked

        findings = audit_multitenant(predict_builder=baked_builder)
        assert any(f.rule == "trace-recompile"
                   and "baked" in f.message for f in findings), findings


class TestFunnelContract:
    """The recommendation funnel's trace contract
    (trace_audit.audit_funnel, wired into scripts/check.sh via
    run_trace_audit): transfer-guard-clean retrieve+expand+rank, index
    leaves as lowered parameters, per-shard top-k present, no
    corpus-sized collective operand."""

    def test_real_funnel_holds_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_funnel

        findings = audit_funnel()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_full_corpus_gather_caught(self):
        """The score-all-then-merge lowering the contract forbids: each
        shard all-gathers its FULL per-shard score tensor and top-ks
        globally — corpus-proportional wire bytes per query batch."""
        import jax
        import jax.numpy as jnp
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from deepfm_tpu.analysis.trace_audit import audit_funnel
        from deepfm_tpu.models.two_tower import encode_tower
        from deepfm_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        def gather_builder(ctx):
            qcfg = ctx.query_cfg.model
            k = ctx.top_k

            def local(payload, uids, uvals):
                u = encode_tower(payload["query"], uids, uvals,
                                 cfg=qcfg, side="user")
                emb = payload["index"]["item_emb"]
                iid = payload["index"]["item_ids"]
                scores = u @ emb.T
                scores = jnp.where(iid[None, :] >= 0, scores, -jnp.inf)
                # the violation: the [B_local, rows_local] score tensor
                # (and the corpus id vector) cross the wire
                all_s = lax.all_gather(scores, MODEL_AXIS, axis=1,
                                       tiled=True)
                all_i = lax.all_gather(iid, MODEL_AXIS, axis=0,
                                       tiled=True)
                s, li = lax.top_k(all_s, k)
                return s, jnp.take(all_i, li, axis=0)

            mapped = shard_map(
                local, mesh=ctx.mesh,
                in_specs=(ctx.payload_specs, P(DATA_AXIS, None),
                          P(DATA_AXIS, None)),
                out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
                check_vma=False,
            )
            return jax.jit(lambda p, i, v: mapped(p, i, v))

        findings = audit_funnel(retrieve_builder=gather_builder)
        assert any(f.rule == "trace-collective"
                   and "corpus-sized" in f.message
                   for f in findings), findings

    def test_seeded_baked_index_caught(self):
        """A retrieve whose index (and weights) compile in as constants:
        every index refresh would be a recompile, and serving would pin
        to one corpus snapshot.  The leaf-count contract convicts it."""
        import jax
        import numpy as np

        from deepfm_tpu.analysis.trace_audit import audit_funnel
        from deepfm_tpu.funnel.index import build_retrieve_with
        from deepfm_tpu.models.base import get_model
        from deepfm_tpu.models.two_tower import init_two_tower

        def baked_builder(ctx):
            real = build_retrieve_with(ctx)
            model = get_model(ctx.rank_cfg.model)
            rp, rs = model.init(jax.random.PRNGKey(0), ctx.rank_cfg.model)
            qp, _ = init_two_tower(jax.random.PRNGKey(1),
                                   ctx.query_cfg.model)
            d = ctx.query_cfg.model.tower_dim
            concrete = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s),
                {
                    "query": {k: qp[k] for k in ("user_embedding",
                                                 "user_tower")},
                    "rank": {"params": rp, "model_state": rs},
                    "index": {
                        "item_ids": np.arange(ctx.capacity,
                                              dtype=np.int32),
                        "item_emb": np.zeros((ctx.capacity, d),
                                             np.float32),
                    },
                },
                ctx.payload_shardings,
            )

            @jax.jit
            def retrieve_baked(uids, uvals):
                return real(concrete, uids, uvals)

            return retrieve_baked

        findings = audit_funnel(retrieve_builder=baked_builder)
        assert any(f.rule == "trace-recompile"
                   and "baked" in f.message for f in findings), findings

    def test_seeded_whole_shard_dequantize_caught(self):
        """The int8 tier's bandwidth contract, violated the obvious way:
        dequantize the WHOLE shard's code matrix to f32 before scoring.
        The lowering then materializes a corpus-sized f32 result — the
        exact copy the quantized scorer exists to never hold."""
        import jax
        import jax.numpy as jnp
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from deepfm_tpu.analysis.trace_audit import audit_funnel
        from deepfm_tpu.models.two_tower import encode_tower
        from deepfm_tpu.parallel.mesh import DATA_AXIS

        def dequant_builder(ctx):
            qcfg = ctx.query_cfg.model
            k = ctx.top_k

            def local(payload, uids, uvals):
                u = encode_tower(payload["query"], uids, uvals,
                                 cfg=qcfg, side="user")
                codes = payload["index"]["item_codes"]
                scl = payload["index"]["item_scales"]
                iid = payload["index"]["item_ids"]
                # the violation: a [rows_local, D] f32 copy of the shard
                deq = codes.astype(jnp.float32) * scl[:, None]
                s = u @ deq.T
                s = jnp.where(iid[None, :] >= 0, s, -jnp.inf)
                sk, li = lax.top_k(s, k)
                return sk, jnp.take(iid, li)

            mapped = shard_map(
                local, mesh=ctx.mesh,
                in_specs=(ctx.payload_specs, P(DATA_AXIS, None),
                          P(DATA_AXIS, None)),
                out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
                check_vma=False,
            )
            return jax.jit(lambda p, i, v: mapped(p, i, v))

        findings = audit_funnel(retrieve_builder=dequant_builder,
                                modes=("int8",))
        assert any(f.rule == "trace-quantized"
                   and f.source.endswith("corpus-f32")
                   for f in findings), findings

    def test_seeded_corpus_rescore_gather_caught(self):
        """The other int8 leak: scoring streams tiles correctly, but the
        rescore stage gathers a corpus-sized result instead of only the
        K*oversample shortlist.  The dtype-agnostic gather matcher must
        convict it even though no corpus-sized f32 exists."""
        import jax
        import jax.numpy as jnp
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from deepfm_tpu.analysis.trace_audit import audit_funnel
        from deepfm_tpu.models.two_tower import encode_tower
        from deepfm_tpu.funnel.quant import score_topk_tiles
        from deepfm_tpu.parallel.mesh import DATA_AXIS

        def gathering_builder(ctx):
            qcfg = ctx.query_cfg.model
            k = ctx.top_k
            kos = ctx.top_k * ctx.oversample
            tile = ctx.retrieval_tile

            def local(payload, uids, uvals):
                u = encode_tower(payload["query"], uids, uvals,
                                 cfg=qcfg, side="user")
                codes = payload["index"]["item_codes"]
                scl = payload["index"]["item_scales"]
                iid = payload["index"]["item_ids"]
                s_a, rows = score_topk_tiles(u, codes, scl, iid,
                                             kos=kos, tile=tile)
                # the violation: a corpus-sized (i32) gather — and kept
                # live by routing the shortlist ids through it
                order = jnp.argsort(iid)
                iid_sorted = jnp.take(iid, order)
                inv = jnp.argsort(order)
                cid = jnp.take(iid_sorted, jnp.take(inv, rows))
                sk, ci = lax.top_k(s_a, k)
                return sk, jnp.take_along_axis(cid, ci, axis=1)

            mapped = shard_map(
                local, mesh=ctx.mesh,
                in_specs=(ctx.payload_specs, P(DATA_AXIS, None),
                          P(DATA_AXIS, None)),
                out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
                check_vma=False,
            )
            return jax.jit(lambda p, i, v: mapped(p, i, v))

        findings = audit_funnel(retrieve_builder=gathering_builder,
                                modes=("int8",))
        assert any(f.rule == "trace-quantized"
                   and f.source.endswith("rescore-gather")
                   for f in findings), findings
        # the scoring stage really did stream tiles: the f32 rule must
        # NOT fire, or this test would prove nothing about the gather
        assert not any(f.source.endswith("corpus-f32")
                       for f in findings), findings


class TestElasticReshardContract:
    """The elastic reshard's trace contract (trace_audit.audit_elastic,
    wired into scripts/check.sh via run_trace_audit): no host round-trip
    on table leaves, the table as a lowered parameter, minimal-traffic
    planning on every audited N→M move."""

    def test_real_reshard_holds_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_elastic

        findings = audit_elastic()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_host_round_trip_caught(self):
        """An adapter that concretizes the traced table (a device->host
        transfer in the middle of the reshard) must be convicted by the
        transfer contract on every move."""
        import jax
        import jax.numpy as jnp

        from deepfm_tpu.analysis.trace_audit import audit_elastic

        def smuggling_builder(sharding, rows_to):
            def adapt(a):
                # the sneak: host-reads the traced rows mid-reshard
                if float(jnp.sum(a)) >= 0:
                    pass
                return a[:rows_to]

            return jax.jit(adapt, out_shardings=sharding)

        findings = audit_elastic(reshard_builder=smuggling_builder)
        assert any(f.rule == "trace-transfer"
                   and "host round-trip" in f.message
                   for f in findings), findings

    def test_seeded_baked_table_caught(self):
        """An adapter that drops the table argument and bakes a concrete
        snapshot into the executable is a smuggled host staging copy —
        convicted by the leaf-count contract."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from deepfm_tpu.analysis.trace_audit import audit_elastic

        def baked_builder(sharding, rows_to):
            width = 32  # the audit cfg's embedding size
            const = np.zeros((rows_to, width), np.float32)

            def adapt():
                return jnp.asarray(const)

            return jax.jit(adapt, out_shardings=sharding)

        findings = audit_elastic(reshard_builder=baked_builder)
        assert any(f.rule == "trace-transfer"
                   and "baked" in f.message for f in findings), findings


# ------------------------------------------------------------ observability

class TestObservabilityAudit:
    """audit_observability: instrumentation never enters lowered code.
    The real entrypoints pass (covered by
    test_real_entrypoints_hold_all_contracts, which runs every engine-2
    audit); each seeded violation here is a way a well-meaning metrics
    patch could smuggle observability INTO the executables."""

    def test_real_predict_and_step_hold_the_contract(self):
        from deepfm_tpu.analysis.trace_audit import audit_observability

        findings = audit_observability()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_host_timer_in_trace_caught(self):
        """A host timer read at trace time (the 'time the kernel from
        inside' mistake) bakes a different constant per retrace —
        convicted by the determinism check."""
        import time

        import jax
        import numpy as np

        from deepfm_tpu.analysis.trace_audit import audit_observability

        def timer_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                # the timer value is CLOSED OVER by the traced function
                c = np.float32(time.perf_counter())
                return jax.nn.sigmoid(logits) + c - c

            return predict_with

        findings = audit_observability(predict_builder=timer_builder)
        assert any(f.rule == "trace-observability"
                   and "lowerings" in f.message for f in findings), \
            "\n".join(f.render() for f in findings)

    def test_seeded_registry_callback_in_jit_caught(self):
        """A registry call smuggled under jit via debug.callback lowers
        as a host-callback custom_call — convicted by the callback scan."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_observability
        from deepfm_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        hist = reg.histogram("deepfm_seeded_scores", "seeded violation")

        def callback_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                out = jax.nn.sigmoid(logits)
                jax.debug.callback(
                    lambda v: hist.observe(float(v)), out[0]
                )
                return out

            return predict_with

        findings = audit_observability(predict_builder=callback_builder)
        assert any(f.rule == "trace-observability"
                   and "host callback" in f.message for f in findings), \
            "\n".join(f.render() for f in findings)

    def test_seeded_registry_call_on_traced_value_caught(self):
        """A DIRECT registry call on a traced value inside the train step
        concretizes the tracer — the audit reports the lowering failure
        as a finding instead of crashing."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_observability
        from deepfm_tpu.obs.metrics import MetricsRegistry
        from deepfm_tpu.train.step import create_train_state, make_train_step

        reg = MetricsRegistry()
        loss_hist = reg.histogram("deepfm_seeded_loss", "seeded violation")

        def step_builder(cfg):
            inner = make_train_step(cfg)

            def bad_step(state, batch):
                new_state, metrics = inner(state, batch)
                loss_hist.observe(float(metrics["loss"]))  # traced value!
                return new_state, metrics

            return jax.jit(bad_step, donate_argnums=(0,))

        findings = audit_observability(step_builder=step_builder)
        assert any(f.rule == "trace-observability"
                   and "train step" in f.message for f in findings), \
            "\n".join(f.render() for f in findings)
        # keep create_train_state imported for the abstract state shape
        assert callable(create_train_state)

    def test_seeded_flywheel_offer_under_trace_caught(self, tmp_path):
        """A flywheel impression logger offered the TRACED score from
        inside the jitted predict (the 'log from where the score is
        born' mistake) concretizes the tracer — the audit's flywheel
        section, which re-lowers with a live logger armed, reports it
        instead of crashing."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_observability
        from deepfm_tpu.flywheel.impressions import ImpressionLogger

        logger = ImpressionLogger(str(tmp_path), sample_rate=1.0).start()

        def offering_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                out = jax.nn.sigmoid(logits)
                # the traced score is offered to the logger — float()
                # on the tracer concretizes; the contract is that the
                # offer happens on the HOST after the response doc
                # (serve/pool/router.py _try_group), never here
                logger.offer(
                    key="seeded", instances=[{}], scores=[out[0]])
                return out

            return predict_with

        try:
            findings = audit_observability(
                predict_builder=offering_builder)
        finally:
            logger.stop()
        assert any(f.rule == "trace-observability"
                   and "flywheel" in f.message for f in findings), \
            "\n".join(f.render() for f in findings)


# ------------------------------------------------------------ control plane

class TestControlPlaneAudit:
    """audit_control_plane: every SLO decision (admission, hedging,
    autoscaling) is host-side policy — none of it may enter the lowered
    serving graph.  The real predict passes with a live, fed control
    plane; each seeded violation is a way a well-meaning adaptive-serving
    patch could fuse a decision INTO the executables."""

    def test_real_predict_holds_under_live_control_plane(self):
        from deepfm_tpu.analysis.trace_audit import audit_control_plane

        findings = audit_control_plane()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_seeded_admission_on_traced_value_caught(self):
        """An admission decision that reads a TRACED value (pricing the
        request against the model's own output) concretizes under the
        transfer guard — the audit reports the lowering failure as a
        finding instead of crashing."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_control_plane
        from deepfm_tpu.serve.control.admission import AdmissionController
        from deepfm_tpu.serve.control.cost import BucketCostModel

        adm = AdmissionController(
            BucketCostModel((8, 32)), deadline_ms=50.0)
        adm.cost.observe(8, 0.001)

        def bad_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                out = jax.nn.sigmoid(logits)
                # the queue-depth input to the admission decision is a
                # traced value — int() concretizes it at trace time
                adm.check(rows=8, queued_rows=int(out[0] * 1000),
                          max_queue_rows=4096, deadline_s=None)
                return out

            return predict_with

        findings = audit_control_plane(predict_builder=bad_builder)
        assert any(f.rule == "trace-control-plane"
                   and "admission or scale decision" in f.message
                   for f in findings), \
            "\n".join(f.render() for f in findings)

    def test_seeded_scale_decision_in_jit_caught(self):
        """A scale decision smuggled into the graph via io_callback
        lowers as a host-callback custom_call — convicted by the
        callback scan."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import io_callback

        from deepfm_tpu.analysis.trace_audit import audit_control_plane
        from deepfm_tpu.serve.control.autoscale import AutoScaler

        scaler = AutoScaler(min_groups=1, max_groups=4)

        def _decide(v):
            scaler.observe(0.0, groups=1, util=float(v))
            return np.float32(0.0)

        def bad_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                out = jax.nn.sigmoid(logits)
                # the autoscale decision rides the dispatch
                zero = io_callback(
                    _decide, jax.ShapeDtypeStruct((), jnp.float32),
                    out[0],
                )
                return out + zero

            return predict_with

        findings = audit_control_plane(predict_builder=bad_builder)
        assert any(f.rule == "trace-control-plane"
                   and "host callback" in f.message for f in findings), \
            "\n".join(f.render() for f in findings)


class TestRegionFrontAudit:
    """audit_region_front: the region layer (rendezvous homes,
    replication lag, staleness drain, budgeted failover) is pure control
    plane — statically jax-free, runnable with no device, and invisible
    to the lowered serving graph.  The real predict passes with a live,
    fed region front; each seeded violation is a way a cross-region
    patch could leak a routing decision into the executables."""

    def test_real_predict_holds_under_live_region_front(self):
        from deepfm_tpu.analysis.trace_audit import audit_region_front

        findings = audit_region_front()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_region_package_is_statically_jax_free(self):
        """The import-hygiene hold inspects real sources: nothing under
        deepfm_tpu/region imports jax today (construction would also
        catch it, but the AST walk convicts even unused imports)."""
        import ast
        import inspect

        from deepfm_tpu import region as pkg
        from deepfm_tpu.region import front, replicator

        for mod in (pkg, front, replicator):
            tree = ast.parse(inspect.getsource(mod))
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.module \
                        and node.level == 0:
                    names = [node.module]
                else:
                    continue
                assert not any(n == "jax" or n.startswith("jax.")
                               for n in names), \
                    f"{mod.__name__} imports jax: {names}"

    def test_seeded_staleness_decision_on_traced_value_caught(self):
        """A staleness observation fed from the model's own output is a
        traced value — int() concretizes it at trace time and the audit
        reports the lowering failure as a finding instead of crashing."""
        import jax

        from deepfm_tpu.analysis.trace_audit import audit_region_front
        from deepfm_tpu.region.front import RegionFront

        front = RegionFront(
            {"use1": {"router_url": "http://invalid.test:1/u",
                      "store_root": ""}})

        def bad_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                out = jax.nn.sigmoid(logits)
                # the version the staleness SLO compares against is a
                # traced value — int() concretizes it at trace time
                front.note_store_version("use1", int(out[0] * 1000))
                return out

            return predict_with

        findings = audit_region_front(predict_builder=bad_builder)
        assert any(f.rule == "trace-region-front"
                   and "routing or staleness decision" in f.message
                   for f in findings), \
            "\n".join(f.render() for f in findings)

    def test_seeded_home_pick_in_jit_caught(self):
        """A home-region pick smuggled into the graph via io_callback
        lowers as a host-callback custom_call — convicted by the
        callback scan."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import io_callback

        from deepfm_tpu.analysis.trace_audit import audit_region_front
        from deepfm_tpu.fleet.split import rendezvous_arm

        def _pick(v):
            rendezvous_arm(f"user-{float(v):.3f}", ["use1", "euw1"])
            return np.float32(0.0)

        def bad_builder(model, cfg):
            @jax.jit
            def predict_with(payload, feat_ids, feat_vals):
                logits, _ = model.apply(
                    payload["params"], payload["model_state"],
                    feat_ids, feat_vals, cfg=cfg.model, train=False,
                )
                out = jax.nn.sigmoid(logits)
                # the home pick rides the dispatch
                zero = io_callback(
                    _pick, jax.ShapeDtypeStruct((), jnp.float32),
                    out[0],
                )
                return out + zero

            return predict_with

        findings = audit_region_front(predict_builder=bad_builder)
        assert any(f.rule == "trace-region-front"
                   and "host callback" in f.message for f in findings), \
            "\n".join(f.render() for f in findings)

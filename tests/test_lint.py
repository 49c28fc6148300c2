"""genielint suite: every rule catches its seeded violation, passes its
clean twin, the suppression syntax round-trips, and -- the gate the CI lane
enforces -- the repo at HEAD is finding-free.

Fixture files are laid out under a temp root that mirrors the production
tree (repro/core/..., repro/kernels/..., repro/serve/...), because rule
scoping keys on paths relative to the scan root: a kernel-contract fixture
only triggers if it lives under repro/kernels/.  The fixtures are parsed,
never imported -- the linter is pure-AST, so the snippets do not need a
working jax.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SRC = os.path.join(_REPO, "src")
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools.genielint import LintConfig, run_lint  # noqa: E402
from tools.genielint.config import DEFAULT  # noqa: E402


def _tree(tmp_path, files: dict) -> str:
    """Write {relpath: source} under tmp_path; return the scan root."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src).strip("\n") + "\n")
    return str(tmp_path)


def _findings(root, rule, **cfg):
    config = LintConfig(**cfg) if cfg else DEFAULT
    return [f for f in run_lint(root, config=config, rules=[rule])
            if not f.suppressed]


# ---------------------------------------------------------------------------
# executor-sovereignty
# ---------------------------------------------------------------------------

def test_executor_sovereignty_fixture(tmp_path):
    root = _tree(tmp_path, {
        # violation: a legacy entry point re-deriving selection itself
        "repro/core/index.py": """
            from repro.core.select import select_topk

            def search(counts, k):
                ids, counts = select_topk(counts, k)   # line 4
                return merge_ragged(ids, counts)
        """,
        # clean twin: the executor family may call the governed helpers
        "repro/core/plan.py": """
            def execute(plan, counts):
                return select_topk(_mask_pad_counts(counts), plan.k)
        """,
        # clean: same call *names* in strings/docstrings never trip the rule
        "repro/core/docs.py": '''
            def helper():
                """Delegates instead of calling select_topk( directly."""
                return "merge_ragged("
        ''',
    })
    got = _findings(root, "executor-sovereignty")
    assert [(f.path, f.line) for f in got] == [
        ("repro/core/index.py", 4), ("repro/core/index.py", 5)]
    assert "executor family" in got[0].message


def test_executor_sovereignty_at_head():
    """The replacement for tests/test_plan.py's deleted string grep: no
    module outside the executor family calls the governed selection/merge/
    pad-mask helpers, anywhere under src/."""
    assert _findings(_SRC, "executor-sovereignty") == []


# ---------------------------------------------------------------------------
# pallas-kernel-contract
# ---------------------------------------------------------------------------

_KERNEL_HEADER = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from jax.experimental import pallas as pl\n"
    "\n"
    "TILE = 128\n"
)


def _kernel_fixture(body: str) -> str:
    """Prepend the shared import header (5 lines) to a dedented body, so
    line numbers inside `body` start at 6."""
    return _KERNEL_HEADER + textwrap.dedent(body).strip("\n") + "\n"


def test_pallas_contract_fixture(tmp_path):
    root = _tree(tmp_path, {
        # violations: index-map arity 1 vs grid rank 2; float32 out dtype
        "repro/kernels/bad.py": _kernel_fixture("""
            def bad_count(q, d):
                grid = (4, 4)
                return pl.pallas_call(
                    _kernel,
                    grid=grid,
                    in_specs=[pl.BlockSpec((TILE, TILE), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
                    out_shape=jax.ShapeDtypeStruct((512, 512), jnp.float32),
                )(q.astype(jnp.int32))
        """),
        # violation: 2048x2048 f32 tile = 16 MiB > the 12 MiB budget
        "repro/kernels/fat.py": _kernel_fixture("""
            def fat_count(q):
                return pl.pallas_call(
                    _kernel,
                    grid=(1,),
                    in_specs=[pl.BlockSpec((2048, 2048), lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
                    out_shape=jax.ShapeDtypeStruct((8, 8), jnp.int32),
                )(q.astype(jnp.float32))
        """),
        # clean twin: matched arity, int32 out, small tiles
        "repro/kernels/good.py": _kernel_fixture("""
            def good_count(q, d):
                grid = (4, 4)
                return pl.pallas_call(
                    _kernel,
                    grid=grid,
                    in_specs=[
                        pl.BlockSpec((TILE, TILE), lambda i, j: (i, 0)),
                        pl.BlockSpec((TILE, TILE), lambda i, j: (j, 0)),
                    ],
                    out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
                    out_shape=jax.ShapeDtypeStruct((512, 512), jnp.int32),
                )(q.astype(jnp.int32), d.astype(jnp.int32))
        """),
        # out of scope: same pallas_call outside repro/kernels/ is ignored
        "repro/core/not_a_kernel.py": _kernel_fixture("""
            def lookalike(q):
                return pl.pallas_call(
                    _kernel, grid=(1,),
                    out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float64),
                )(q)
        """),
    })
    got = _findings(root, "pallas-kernel-contract")
    by_file = {}
    for f in got:
        by_file.setdefault(f.path, []).append(f.message)
    assert sorted(by_file) == ["repro/kernels/bad.py", "repro/kernels/fat.py"]
    bad = "\n".join(by_file["repro/kernels/bad.py"])
    assert "takes 1 indices but the grid has rank 2" in bad
    assert "float32 violates the registry count policy" in bad
    fat = "\n".join(by_file["repro/kernels/fat.py"])
    assert "VMEM tile footprint" in fat and "16777472" in fat


def test_pallas_vmem_budget_is_configurable(tmp_path):
    root = _tree(tmp_path, {
        "repro/kernels/fat.py": _kernel_fixture("""
            def fat_count(q):
                return pl.pallas_call(
                    _kernel,
                    grid=(1,),
                    in_specs=[pl.BlockSpec((2048, 2048), lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
                    out_shape=jax.ShapeDtypeStruct((8, 8), jnp.int32),
                )(q.astype(jnp.float32))
        """),
    })
    assert _findings(root, "pallas-kernel-contract")
    assert _findings(root, "pallas-kernel-contract",
                     vmem_budget_bytes=32 * 1024 * 1024) == []


def test_pallas_grouped_and_squeezed_blocks(tmp_path):
    # the column-sweep layout: a grouped query block splits one signature
    # width over two unfoldable dims, and a None dim is squeezed
    sweep = _kernel_fixture("""
        def sweep_topk(query_groups, data_t, kc):
            n_groups, qn, group = query_groups.shape
            mp, nn = data_t.shape
            return pl.pallas_call(
                _kernel,
                grid=(qn // TILE, nn // TILE),
                in_specs=[
                    pl.BlockSpec((n_groups, TILE, group), lambda i, j: (0, i, 0)),
                    pl.BlockSpec((mp, TILE), lambda i, j: (0, j)),
                ],
                out_specs=pl.BlockSpec((None, TILE, kc), lambda i, j: (j, i, 0)),
                out_shape=jax.ShapeDtypeStruct((4, qn, kc), jnp.int32),
            )(query_groups.astype(jnp.int32), data_t.astype(jnp.int32))
    """)
    root = _tree(tmp_path, {
        "repro/kernels/sweep.py": sweep,
        # violation: two in_specs for one applied operand
        "repro/kernels/short.py": _kernel_fixture("""
            def short_count(q):
                return pl.pallas_call(
                    _kernel,
                    grid=(1,),
                    in_specs=[pl.BlockSpec((8, 8), lambda i: (0, 0)),
                              pl.BlockSpec((8, 8), lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
                    out_shape=jax.ShapeDtypeStruct((8, 8), jnp.int32),
                )(q.astype(jnp.int32))
        """),
    })
    got = _findings(root, "pallas-kernel-contract")
    assert [f.path for f in got] == ["repro/kernels/short.py"]
    assert "2 in_specs but 1 operands applied" in got[0].message
    # three 512 x 128 x 4 B blocks: 786432 B; one byte under it fails
    tight = _findings(root, "pallas-kernel-contract",
                      vmem_budget_bytes=3 * 512 * 128 * 4 - 1)
    assert any(f.path == "repro/kernels/sweep.py" and "786432" in f.message
               for f in tight)


# ---------------------------------------------------------------------------
# retrace-hygiene
# ---------------------------------------------------------------------------

def test_retrace_hygiene_fixture(tmp_path):
    root = _tree(tmp_path, {
        # violations: coercion of a traced value; branch on a traced param
        "repro/kernels/traced.py": """
            import jax

            @jax.jit
            def step(counts, k):
                if k > 0:
                    counts = counts + 1
                return float(counts)

            def host_side(x):
                return float(x)   # not traced: legal
        """,
        # clean twin: shape math coercions and is-None branches are static
        "repro/kernels/clean.py": """
            import jax

            @jax.jit
            def step(counts, mask=None):
                n = int(counts.shape[0])
                if mask is not None:
                    counts = counts * mask
                return counts
        """,
    })
    got = _findings(root, "retrace-hygiene")
    assert [(f.path, f.line) for f in got] == [
        ("repro/kernels/traced.py", 5), ("repro/kernels/traced.py", 7)]
    assert "branch on traced parameter" in got[0].message
    assert "float() coercion" in got[1].message


def test_queryplan_cache_key_fixture(tmp_path):
    root = _tree(tmp_path, {
        # violations: field hidden from describe(); field opted out of the key
        "repro/core/plan.py": """
            import dataclasses

            @dataclasses.dataclass(frozen=True)
            class QueryPlan:
                engine: str
                k: int
                secret: int
                debug: str = dataclasses.field(default="", compare=False)

                def describe(self):
                    return dict(engine=self.engine, k=self.k, debug=self.debug)
        """,
    })
    got = _findings(root, "retrace-hygiene")
    msgs = "\n".join(f.message for f in got)
    assert "'secret' missing from describe()" in msgs
    assert "'debug' opts out of the cache key" in msgs

    clean = _tree(tmp_path / "clean", {
        "repro/core/plan.py": """
            import dataclasses

            @dataclasses.dataclass(frozen=True)
            class QueryPlan:
                engine: str
                k: int
                params: tuple    # allowlisted derived key

                def describe(self):
                    return dict(engine=self.engine, k=self.k)
        """,
    })
    assert _findings(clean, "retrace-hygiene") == []

    thawed = _tree(tmp_path / "thawed", {
        "repro/core/plan.py": """
            import dataclasses

            @dataclasses.dataclass
            class QueryPlan:
                k: int

                def describe(self):
                    return dict(k=self.k)
        """,
    })
    got = _findings(thawed, "retrace-hygiene")
    assert len(got) == 1 and "frozen=True" in got[0].message


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

def test_lock_discipline_fixture(tmp_path):
    root = _tree(tmp_path, {
        # violation: _q written under the lock, read without it
        "repro/serve/scheduler.py": """
            import threading

            class Queue:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = []

                def offer(self, x):
                    with self._lock:
                        self._q.append(x)

                def depth(self):
                    return len(self._q)   # line 13: unlocked read
        """,
        # clean twin: every access locked, incl. the lock-private helper
        # pattern (helper writes in its own body, called only under lock)
        "repro/serve/metrics.py": """
            import threading

            class Metrics:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._tenants = {}
                    self._hb = object()

                def _tenant(self, name):
                    t = self._tenants.get(name)
                    if t is None:
                        t = self._tenants[name] = []
                    return t

                def record(self, name, v):
                    with self._lock:
                        self._tenant(name).append(v)
                    self._hb.beat(name)   # plain method call: not a write

                def snapshot(self):
                    with self._lock:
                        return dict(self._tenants)
        """,
    })
    got = _findings(root, "lock-discipline")
    assert [(f.path, f.line) for f in got] == [("repro/serve/scheduler.py", 13)]
    assert "without holding self._lock" in got[0].message


def test_lock_discipline_flags_unlocked_write(tmp_path):
    root = _tree(tmp_path, {
        "repro/serve/frontend.py": """
            import threading

            class Registry:
                def __init__(self):
                    self._reg = threading.Condition()
                    self._tenants = {}

                def register(self, name, svc):
                    with self._reg:
                        self._tenants[name] = svc

                def evict(self, name):
                    self._tenants.pop(name, None)   # line 13: unlocked write
        """,
    })
    got = _findings(root, "lock-discipline")
    assert len(got) == 1
    assert got[0].line == 13 and "written" in got[0].message


# ---------------------------------------------------------------------------
# wall-clock / broad-except
# ---------------------------------------------------------------------------

def test_wall_clock_fixture(tmp_path):
    root = _tree(tmp_path, {
        "repro/serve/timing.py": """
            import time
            from time import time as now

            def bench(fn):
                t0 = time.time()
                fn()
                return time.time() - t0

            def bench2(fn):
                t0 = now()          # aliased import still wall-clock... but
                t1 = time.perf_counter()   # perf_counter is the fix
                return t1 - t0
        """,
        # the by-design carve-out: cross-process heartbeat deadlines
        "repro/runtime/fault_tolerance.py": """
            import time

            def beat():
                return time.time()
        """,
    })
    got = _findings(root, "wall-clock")
    assert [f.line for f in got] == [5, 7]
    assert all(f.path == "repro/serve/timing.py" for f in got)
    assert "perf_counter" in got[0].message


def test_wall_clock_bare_import(tmp_path):
    root = _tree(tmp_path, {
        "repro/launch/t.py": """
            from time import time

            def bench():
                return time()
        """,
    })
    assert [f.line for f in _findings(root, "wall-clock")] == [4]


def test_broad_except_fixture(tmp_path):
    root = _tree(tmp_path, {
        "repro/launch/h.py": """
            def risky():
                try:
                    work()
                except Exception:      # line 4
                    pass
                try:
                    work()
                except (ValueError, BaseException):   # line 8
                    pass
                try:
                    work()
                except:                # line 12: bare
                    pass
                try:
                    work()
                except (KeyError, OSError):   # clean: named failures
                    raise
        """,
    })
    got = _findings(root, "broad-except")
    assert [f.line for f in got] == [4, 8, 12]
    assert "bare except" in got[2].message


# ---------------------------------------------------------------------------
# Suppression syntax
# ---------------------------------------------------------------------------

def test_suppression_round_trip(tmp_path):
    root = _tree(tmp_path, {
        "repro/launch/s.py": """
            def boundary():
                try:
                    work()
                except Exception:  # genielint: ignore[broad-except]
                    record()
                try:
                    work()
                # genielint: ignore[broad-except]
                except Exception:
                    record()
                try:
                    work()
                # genielint: ignore[wall-clock]
                except Exception:      # wrong rule named: NOT suppressed
                    record()
        """,
    })
    all_findings = run_lint(root, rules=["broad-except"])
    assert [(f.line, f.suppressed) for f in all_findings] == [
        (4, True), (9, True), (14, False)]
    # suppressed findings are still reported (for the JSON trail) but do
    # not count against the gate
    assert len(_findings(root, "broad-except")) == 1


def test_suppression_requires_comment_only_line(tmp_path):
    """A directive buried in trailing code two lines up must not leak onto
    the next statement -- only the finding's own line or an immediately
    preceding comment-only line suppresses."""
    root = _tree(tmp_path, {
        "repro/launch/s.py": """
            def boundary():
                x = 1  # genielint: ignore[broad-except]
                y = 2
                try:
                    work()
                except Exception:
                    record()
        """,
    })
    got = run_lint(root, rules=["broad-except"])
    assert [(f.line, f.suppressed) for f in got] == [(6, False)]


# ---------------------------------------------------------------------------
# The HEAD gate + CLI
# ---------------------------------------------------------------------------

def test_repo_is_clean_at_head():
    """The invariant the CI lane enforces: zero unsuppressed findings over
    src/ with every rule enabled.  If this fails, fix the violation (or,
    when the catch-all/wall-clock IS the design, justify it at the site
    with an inline ignore) -- do not widen the config allowlists."""
    findings = [f for f in run_lint(_SRC) if not f.suppressed]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=_REPO)
    report = tmp_path / "lint.json"
    out = subprocess.run(
        [sys.executable, "-m", "tools.genielint", "--json", str(report)],
        cwd=_REPO, env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "genielint: clean" in out.stdout
    rep = json.loads(report.read_text())
    assert rep["ok"] is True and rep["tool"] == "genielint"
    assert rep["n_unsuppressed"] == 0

    bad_root = _tree(tmp_path, {
        "repro/launch/bad.py": """
            import time

            def bench():
                return time.time()
        """,
    })
    out = subprocess.run(
        [sys.executable, "-m", "tools.genielint", "--root", bad_root,
         "--json", str(report)],
        cwd=_REPO, env=env, capture_output=True, text=True)
    assert out.returncode == 1
    assert "wall-clock" in out.stdout
    assert json.loads(report.read_text())["ok"] is False


def test_cli_rejects_unknown_rule():
    env = dict(os.environ, PYTHONPATH=_REPO)
    out = subprocess.run(
        [sys.executable, "-m", "tools.genielint", "--rules", "no-such-rule"],
        cwd=_REPO, env=env, capture_output=True, text=True)
    assert out.returncode == 2
    assert "unknown rule" in out.stderr


# ---------------------------------------------------------------------------
# Config cross-checks against the live code
# ---------------------------------------------------------------------------

def test_kernel_dtype_policy_matches_registry():
    """config.kernel_out_dtypes must equal the registry's widest count
    dtype: kernels emit exact int32 and as_count_dtype only ever narrows,
    so a drift in either direction (a kernel emitting float, or the
    registry widening past int32) breaks the contract."""
    import jax.numpy as jnp

    from repro.core.match import as_count_dtype

    widest = as_count_dtype(jnp.zeros((), jnp.int32), 1 << 30).dtype.name
    assert set(DEFAULT.kernel_out_dtypes) == {widest}
    for mc in (1, 127, 128, 32767, 32768, 1 << 24):
        narrowed = as_count_dtype(jnp.zeros((), jnp.int32), mc).dtype
        assert narrowed.itemsize <= jnp.dtype(widest).itemsize


@pytest.mark.parametrize("paths", [
    DEFAULT.executor_modules, DEFAULT.lock_modules,
    DEFAULT.wall_clock_allow, DEFAULT.traced_modules,
])
def test_config_scopes_point_at_real_files(paths):
    """A rename must not silently de-scope a rule: every path named in the
    config exists under src/."""
    for rel in paths:
        assert os.path.exists(os.path.join(_SRC, rel)), rel


def test_all_rules_registered():
    from tools.genielint.core import ALL_RULES, _load_rules
    _load_rules()
    assert set(ALL_RULES) == {
        "executor-sovereignty", "pallas-kernel-contract", "retrace-hygiene",
        "lock-discipline", "wall-clock", "broad-except"}

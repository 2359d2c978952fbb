"""repro.analysis: the jaxlint passes, baseline, CLI, and runtime sentinels.

Three layers of coverage:

  * pass-level fixtures: for each JL code a true-positive snippet, an
    annotated (suppressed) variant, and a clean variant — run in-process
    through ``ModuleContext.parse`` + ``run_passes``.
  * baseline + CLI: fingerprint round-trip (line-number drift tolerant,
    count-capped) and the documented exit codes (0 clean / 1 new
    findings / 2 bad arguments-or-baseline-or-syntax).
  * seeded regressions over the REAL tree: a scratch copy of src/ lints
    clean against the committed baseline, then each of five seeded
    hot-path regressions (one per JL001-JL005) flips the CLI to exit 1 —
    the acceptance check that every pass bites on the code it guards.

The runtime sentinels get unit tests against a fake engine here; full
serve-replay coverage lives in test_fused_tick.py / test_sharded_serving.py.
"""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.contracts import hot_path, parse_annotations
from repro.analysis.findings import (Finding, load_baseline, write_baseline)
from repro.analysis.lint import lint_paths
from repro.analysis.lint import main as lint_main
from repro.analysis.passes import ALL_CODES, ModuleContext, run_passes
from repro.analysis.sentinels import (CompileBudgetExceeded, CompileSentinel,
                                      SyncSentinel, SyncViolation)

REPO = Path(__file__).resolve().parent.parent


def findings_for(snippet, path="pkg/mod.py", select=None):
    ctx = ModuleContext.parse(path, textwrap.dedent(snippet))
    return run_passes(ctx, select)


def codes(findings):
    return sorted(f.code for f in findings)


# ==========================================================================
# JL000 — annotation hygiene (malformed directives are findings, not noise)
# ==========================================================================
def test_jl000_malformed_annotations():
    fs = findings_for(
        """
        x = 1  # jaxlint: allow-sync
        y = 2  # jaxlint: frobnicate
        # jaxlint: shapes(not a decl!)
        z = 3
        """,
        select=["JL000"],
    )
    assert codes(fs) == ["JL000", "JL000", "JL000"]
    msgs = " ".join(f.message for f in fs)
    assert "require a reason" in msgs and "unknown directive" in msgs
    assert "unparseable shapes" in msgs


def test_jl000_docstring_mentions_are_not_annotations():
    # the annotation parser reads tokenize COMMENT tokens, not raw lines:
    # documentation that *quotes* a directive must not trip JL000
    fs = findings_for(
        '''
        def doc():
            """Write `# jaxlint: allow-sync` or # jaxlint: shapes(broken."""
            return 1
        ''',
    )
    assert fs == []


# ==========================================================================
# JL001 — host sync in hot path
# ==========================================================================
JL001_TP = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def tick():  # jaxlint: hot-path
        x = jnp.zeros((4,))
        got = jax.device_get(x)
        f = float(jnp.sum(x))
        h = np.asarray(x)
        i = x.item()
        return got, f, h, i
"""


def test_jl001_flags_every_sync_construct():
    fs = findings_for(JL001_TP, select=["JL001"])
    assert codes(fs) == ["JL001"] * 4
    msgs = [f.message for f in fs]
    assert any("jax.device_get" in m for m in msgs)
    assert any("float() of device value" in m for m in msgs)
    assert any("np.asarray of device value" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


def test_jl001_decorator_marks_hot():
    fs = findings_for(
        """
        import jax
        from repro.analysis import hot_path

        @hot_path
        def tick():
            return jax.device_get(jnp.zeros(3))
        """,
        select=["JL001"],
    )
    assert codes(fs) == ["JL001"]


def test_jl001_allow_sync_suppresses():
    fs = findings_for(
        """
        import jax

        def tick():  # jaxlint: hot-path
            got = jax.device_get(x)  # jaxlint: allow-sync(designated sync point)
            return got
        """,
        select=["JL001"],
    )
    assert fs == []


def test_jl001_clean_host_math_and_cold_functions():
    fs = findings_for(
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def tick(toks):  # jaxlint: hot-path
            n = np.zeros((3,))
            f = float(n.sum())                # host array: no device sync
            b = float(toks * jnp.dtype("float32").itemsize)   # metadata only
            return f + b

        def cold():                           # not hot: syncs are its job
            x = jnp.zeros((4,))
            return jax.device_get(x)
        """,
        select=["JL001"],
    )
    assert fs == []


# ==========================================================================
# JL002 — concat in sharded code paths
# ==========================================================================
def test_jl002_module_scope_and_suppression():
    # serving/engine.py is a sharded-path module: module-wide scope
    tp = findings_for(
        """
        import jax.numpy as jnp

        def splice(a, b):
            return jnp.concatenate([a, b])
        """,
        path="repro/serving/engine.py",
        select=["JL002"],
    )
    assert codes(tp) == ["JL002"]
    assert "splice helpers" in tp[0].message

    ok = findings_for(
        """
        import jax.numpy as jnp

        def rope(a, b):
            return jnp.concatenate([a, b], axis=-1)  # jaxlint: allow-concat(feature axis)
        """,
        path="repro/serving/engine.py",
        select=["JL002"],
    )
    assert ok == []


def test_jl002_marker_scope_outside_listed_modules():
    snippet = """
        import jax.numpy as jnp

        def gather(parts):  # jaxlint: sharded-path
            return jnp.stack(parts)

        def host_side(parts):
            return jnp.stack(parts)
    """
    fs = findings_for(snippet, path="pkg/util.py", select=["JL002"])
    assert len(fs) == 1 and fs[0].code == "JL002"   # only the marked def


# ==========================================================================
# JL003 — unmasked cache writes in masked scan bodies
# ==========================================================================
JL003_CLEAN = """
    import jax
    import jax.numpy as jnp

    def body(carry, xs):  # jaxlint: masked-scan-body
        old, pos = carry
        logits, new, st = decode_step(xs, old)
        merged = jax.tree_util.tree_map_with_path(keep, new, old)
        trig = jnp.where(active, st, 0.0)
        return (merged, pos), trig
"""


def test_jl003_masked_select_is_clean():
    assert findings_for(JL003_CLEAN, select=["JL003"]) == []


def test_jl003_raw_cache_escape_flagged():
    fs = findings_for(
        JL003_CLEAN.replace(
            "merged = jax.tree_util.tree_map_with_path(keep, new, old)",
            "merged = new",
        ),
        select=["JL003"],
    )
    assert codes(fs) == ["JL003"]
    assert "'merged'" in fs[0].message


def test_jl003_at_write_needs_mask():
    tp = findings_for(
        """
        def body(carry, xs):  # jaxlint: masked-scan-body
            buf = carry
            buf = buf.at[0].set(xs)
            return None
        """,
        select=["JL003"],
    )
    assert codes(tp) == ["JL003"] and ".at[...]" in tp[0].message

    ok = findings_for(
        """
        import jax.numpy as jnp

        def body(carry, xs):  # jaxlint: masked-scan-body
            buf = carry
            buf = buf.at[0].set(jnp.where(m, xs, buf[0]))
            return None
        """,
        select=["JL003"],
    )
    assert ok == []


JL003_ROW_MASKED = """
    import jax.numpy as jnp

    def body(carry, xs):  # jaxlint: masked-scan-body
        old, last = carry
        tok, active = xs
        logits, new, st = decode_step(params, cfg, tok, old, active=active)
        last = jnp.where(active[:, None], logits, last)
        return (new, last), st
"""


def test_jl003_row_mask_at_the_write_is_clean():
    # the decode step given the row mask masks rows at each write it makes
    assert findings_for(JL003_ROW_MASKED, select=["JL003"]) == []


@pytest.mark.parametrize("call", [
    "decode_step(params, cfg, tok, old)",
    "decode_step(params, cfg, tok, old, active=None)",
], ids=["no-mask", "mask-none"])
def test_jl003_decode_step_without_row_mask_flagged(call):
    fs = findings_for(
        JL003_ROW_MASKED.replace(
            "decode_step(params, cfg, tok, old, active=active)", call),
        select=["JL003"])
    assert codes(fs) == ["JL003"] and "'new'" in fs[0].message


def test_jl003_unmasked_touched_slot_write_still_flagged():
    # a stacked write given the row mask is masked; a scatter at the
    # touched slot without a select, in the same body, still fails
    fs = findings_for(
        """
        import jax.numpy as jnp

        def body(carry, xs):  # jaxlint: masked-scan-body
            cache, last = carry
            k_new, g_new, layer, active = xs
            cache = lazy_promote_and_write(cache, k_new, k_new, g_new,
                                           tau=0.1, layer=layer,
                                           active=active)
            lk = cache.lk.at[layer, rows, :, cache.ptr[layer]].set(k_new)
            return (cache, last), lk
        """,
        select=["JL003"])
    assert codes(fs) == ["JL003"] and ".at[...]" in fs[0].message


def test_jl003_suppression():
    fs = findings_for(
        JL003_CLEAN.replace(
            "merged = jax.tree_util.tree_map_with_path(keep, new, old)",
            "merged = new",
        ).replace(
            "return (merged, pos), trig",
            "return (merged, pos), trig  # jaxlint: allow-unmasked-write(test scaffolding)",
        ),
        select=["JL003"],
    )
    assert fs == []


# ==========================================================================
# JL004 — tracer leaks in jitted functions
# ==========================================================================
def test_jl004_decorator_and_call_forms():
    fs = findings_for(
        """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x

        def g(y):
            while y > 0:
                y = y - 1
            return y

        jfn = jax.jit(g)
        """,
        select=["JL004"],
    )
    assert codes(fs) == ["JL004", "JL004"]
    assert any("'f'" in f.message and "if" in f.message for f in fs)
    assert any("'g'" in f.message and "while" in f.message for f in fs)


def test_jl004_static_args_and_shape_reads_are_clean():
    fs = findings_for(
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def g(x, n):
            if n > 0:                 # static: concretized at trace time
                x = x + 1
            if x.shape[0] > 2:        # shape metadata: host-known
                x = x * 2
            assert x is not None      # identity compare: fine
            return x
        """,
        select=["JL004"],
    )
    assert fs == []


def test_jl004_suppression():
    fs = findings_for(
        """
        import jax

        @jax.jit
        def f(x):
            if x > 0:  # jaxlint: allow-tracer-branch(scalar weak-type scaffold)
                return x
            return -x
        """,
        select=["JL004"],
    )
    assert fs == []


# ==========================================================================
# JL005 — undeclared compiled shapes in the tick path
# ==========================================================================
def test_jl005_tick_path_jit_needs_decl():
    tp = findings_for(
        """
        import jax
        fn = jax.jit(lambda x: x)
        """,
        path="repro/serving/sharded.py",
        select=["JL005"],
    )
    assert codes(tp) == ["JL005"]
    assert "COMPILE_SHAPE_BUDGETS" in tp[0].message

    ok_line = findings_for(
        """
        import jax
        # jaxlint: shapes(helper=1)
        fn = jax.jit(lambda x: x)
        """,
        path="repro/serving/sharded.py",
        select=["JL005"],
    )
    assert ok_line == []

    ok_def = findings_for(
        """
        import jax

        def make():  # jaxlint: shapes(helper=per-structure)
            return jax.jit(lambda x: x)
        """,
        path="repro/serving/sharded.py",
        select=["JL005"],
    )
    assert ok_def == []


def test_jl005_only_tick_path_modules():
    fs = findings_for(
        """
        import jax
        fn = jax.jit(lambda x: x)
        """,
        path="repro/models/foo.py",
        select=["JL005"],
    )
    assert fs == []


# ==========================================================================
# JL006 — dead imports
# ==========================================================================
def test_jl006_dead_and_guarded_imports():
    fs = findings_for(
        """
        import os
        from typing import List

        import jax.numpy as jnp

        try:
            import fancy
        except ImportError:
            fancy = None

        __all__ = ["exported"]
        import exported  # noqa: re-export for the package surface

        def f(x):
            return jnp.sum(x)
        """,
        select=["JL006"],
    )
    assert codes(fs) == ["JL006", "JL006"]
    texts = " ".join(f.text for f in fs)
    assert "import os" in texts and "List" in texts


def test_jl006_suppression_and_init_exemption():
    fs = findings_for(
        "import os  # jaxlint: allow-dead-import(subprocess env in doctest)\n",
        select=["JL006"],
    )
    assert fs == []
    init = findings_for("import os\n", path="pkg/__init__.py",
                        select=["JL006"])
    assert init == []


# ==========================================================================
# annotation parser + baseline round-trip
# ==========================================================================
def test_parse_annotations_surface():
    ann = parse_annotations(textwrap.dedent(
        """
        # jaxlint: hot-path
        def f():
            x = 1  # jaxlint: allow-sync(reason text)
            return x
        """
    ))
    assert ann.scope_marker("hot-path", 3)          # marker on line above def
    assert ann.suppressed("JL001", 4)               # on the line
    assert ann.suppressed("JL001", 5)               # line below the comment
    assert not ann.suppressed("JL002", 4)           # wrong code family


def test_baseline_round_trip_and_count_cap(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("import os\nimport sys\n")
    found = lint_paths([str(mod)], select=["JL006"])
    assert len(found) == 2

    bl = tmp_path / "baseline.json"
    write_baseline(found, bl, reason="seed")
    new, accepted = load_baseline(bl).split(found)
    assert new == [] and len(accepted) == 2

    # fingerprints are (code, path, text): line drift stays accepted, but a
    # SECOND occurrence of the same text overflows the count and fails
    mod.write_text("# moved\nimport os\nimport sys\nimport os\n")
    drifted = lint_paths([str(mod)], select=["JL006"])
    assert len(drifted) == 3
    new, accepted = load_baseline(bl).split(drifted)
    assert len(accepted) == 2 and len(new) == 1
    assert new[0].text == "import os"


def test_baseline_version_check(tmp_path):
    bad = tmp_path / "b.json"
    bad.write_text('{"version": 99, "findings": []}')
    with pytest.raises(ValueError, match="version"):
        load_baseline(bad)


# ==========================================================================
# CLI exit codes: 0 clean / 1 new findings / 2 bad input
# ==========================================================================
def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("import os\n\nprint(os.sep)\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import os\n")

    assert lint_main([str(clean)]) == 0
    assert "clean" in capsys.readouterr().out

    assert lint_main([str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "JL006" in out and "1 new finding(s)" in out

    # baseline acceptance turns the same tree green
    bl = tmp_path / "bl.json"
    assert lint_main([str(dirty), "--write-baseline", str(bl),
                      "--reason", "known"]) == 0
    capsys.readouterr()
    assert lint_main([str(dirty), "--baseline", str(bl)]) == 0
    assert "accepted by baseline" in capsys.readouterr().out

    # exit 2: unknown code, missing path, unreadable baseline, syntax error
    assert lint_main([str(clean), "--select", "JL999"]) == 2
    assert lint_main([str(tmp_path / "nope.py")]) == 2
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert lint_main([str(dirty), "--baseline", str(broken)]) == 2
    bad_py = tmp_path / "bad.py"
    bad_py.write_text("def (:\n")
    assert lint_main([str(bad_py)]) == 2
    capsys.readouterr()


# ==========================================================================
# seeded regressions over the real tree: the acceptance check that each
# pass bites on the exact code it guards (scratch copy, subprocess CLI)
# ==========================================================================
@pytest.fixture(scope="module")
def scratch_tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("lint_tree")
    shutil.copytree(REPO / "src" / "repro", root / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "analysis").mkdir()
    shutil.copy(REPO / "analysis" / "baseline.json",
                root / "analysis" / "baseline.json")
    return root


def run_lint_cli(cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "src",
         "--baseline", "analysis/baseline.json"],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=120)


def test_repo_tree_lints_clean_with_committed_baseline():
    r = run_lint_cli(REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


SEEDED = [
    ("JL001", "src/repro/serving/engine.py",
     '            self.stats["fused_slot_rows"] += float(self.slots)',
     '            self.stats["fused_slot_rows"] += float(self.slots)\n'
     '            _dbg = float(jnp.sum(self._tok_dev))'),
    ("JL002", "src/repro/serving/engine.py",
     '            self.stats["fused_slot_rows"] += float(self.slots)',
     '            self.stats["fused_slot_rows"] += float(self.slots)\n'
     '            _cat = jnp.concatenate([jnp.zeros((1,)), jnp.zeros((1,))])'),
    ("JL003", "src/repro/models/inference.py",
     "scan_unroll=scan_unroll, active=active)",
     "scan_unroll=scan_unroll)"),
    ("JL004", "src/repro/serving/sharded.py",
     "                    sampled = sample(key[0], last_logits,\n"
     "                                     temperature=temperature)",
     "                    sampled = sample(key[0], last_logits,\n"
     "                                     temperature=temperature)\n"
     "                    if lengths[0] > 0:\n"
     "                        sampled = sampled"),
    ("JL005", "src/repro/serving/sharded.py",
     "            ent = self._fn_cache.get(key)",
     "            ent = self._fn_cache.get(key)\n"
     "            _unbudgeted = jax.jit(lambda q: q)"),
]


@pytest.mark.parametrize("code,rel,old,new", SEEDED,
                         ids=[s[0] for s in SEEDED])
def test_seeded_regression_fails_lint(scratch_tree, code, rel, old, new):
    target = scratch_tree / rel
    original = target.read_text()
    assert old in original, f"mutation anchor vanished from {rel}"
    try:
        target.write_text(original.replace(old, new, 1))
        r = run_lint_cli(scratch_tree)
        assert r.returncode == 1, r.stdout + r.stderr
        assert code in r.stdout, r.stdout
    finally:
        target.write_text(original)
    # restored tree is green again (mutations don't leak across params)
    r = run_lint_cli(scratch_tree)
    assert r.returncode == 0, r.stdout + r.stderr


# ==========================================================================
# runtime sentinels (unit level; full-replay coverage in test_fused_tick)
# ==========================================================================
jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


class _FakeEngine:
    COMPILE_SHAPE_BUDGETS = {"fused_step": 2}

    def __init__(self, shapes=2):
        self.shapes = shapes

    def compiled_shape_counts(self):
        return {"fused_step": self.shapes}

    def step_batch(self, tasks, chunk=16):
        return object()

    def collect(self, step):
        return jax.device_get(jnp.zeros((1,)))

    def memory_snapshot(self):
        return {"x": float(jax.device_get(jnp.ones(())))}


def test_compile_sentinel_within_and_over_budget():
    with CompileSentinel(_FakeEngine(2)) as cs:
        assert cs.check() == {"fused_step": 2}
    with pytest.raises(CompileBudgetExceeded, match="recompile stall"):
        with CompileSentinel(_FakeEngine(3)):
            pass
    # explicit budgets override the engine declaration
    with CompileSentinel(_FakeEngine(3), budgets={"fused_step": 5}):
        pass
    with pytest.raises(ValueError, match="no shape budgets"):
        CompileSentinel(object())


def test_sync_sentinel_contract():
    eng = _FakeEngine()
    orig = jax.device_get
    with SyncSentinel(eng) as ss:
        jax.device_get(jnp.zeros(1))        # nothing in flight: fine
        step = eng.step_batch([])
        with pytest.raises(SyncViolation, match="collect"):
            jax.device_get(jnp.zeros(1))    # naked sync mid-flight
        eng.memory_snapshot()               # sanctioned frame: fine
        eng.collect(step)
        jax.device_get(jnp.zeros(1))        # collected: fine again
    assert ss.syncs_in_collect >= 2         # collect + memory_snapshot pulls
    assert jax.device_get is orig           # patch removed
    assert "collect" not in vars(eng) and "step_batch" not in vars(eng)


def test_sync_sentinel_dispatch_must_not_block():
    class _BadDispatch(_FakeEngine):
        def step_batch(self, tasks, chunk=16):
            return jax.device_get(jnp.zeros(1))   # sync inside dispatch

    eng = _BadDispatch()
    orig = jax.device_get
    with pytest.raises(SyncViolation):
        with SyncSentinel(eng):
            eng.step_batch([])
    assert jax.device_get is orig           # restored even on unwind
    assert "step_batch" not in vars(eng)


def test_hot_path_decorator_is_transparent():
    @hot_path
    def f(x):
        return x + 1

    assert f.__jaxlint_hot_path__ is True
    assert f(1) == 2

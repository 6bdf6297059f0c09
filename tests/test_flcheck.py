"""flcheck static-analysis pass: corpus selftest, repo-clean gate, rule units.

Three layers:
  * the self-test corpus (``tools/flcheck/corpus``) must match its
    ``# expect: FLCxxx`` markers exactly — every rule with at least one
    positive and one negative snippet;
  * the repo tree itself must scan clean (the same gate CI runs);
  * unit tests for the judgment calls the rules encode: suppression
    comments, module-attribute vs bound-method disambiguation for FLC001,
    and jit-reachability for FLC003 and FLC008.
"""
import os
import textwrap

from tools.flcheck import checker
from tools.flcheck.selftest import run_selftest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_selftest_corpus_passes():
    assert run_selftest() == []


def test_repo_tree_scans_clean():
    errors_path = checker.find_errors_module([os.path.join(REPO, "src")])
    assert errors_path is not None
    fragments = checker.pinned_fragments(errors_path)
    assert fragments, "errors.py must yield at least one pinned fragment"
    diags = checker.check_paths(
        [os.path.join(REPO, d)
         for d in ("src", "tests", "benchmarks", "examples")],
        search_dirs=(os.path.join(REPO, "src"), REPO),
        fragments=fragments,
    )
    assert diags == [], "\n".join(str(d) for d in diags)


def test_every_rule_has_positive_and_negative_snippets():
    corpus = os.path.join(REPO, "tools", "flcheck", "corpus")
    sources = {
        f: open(os.path.join(corpus, f), encoding="utf-8").read()
        for f in os.listdir(corpus) if f.endswith(".py")
    }
    blob = "\n".join(sources.values())
    for rule in checker.RULES:
        assert f"# expect: {rule}" in blob, f"no positive snippet for {rule}"
    for src in sources.values():
        # a negative exemplar in every file: at least one function/stmt
        # that must stay silent (selftest enforces the silence itself)
        assert "good_" in src or "except ImportError" in src


def _scan(tmp_path, source, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return checker.check_paths([str(p)], search_dirs=(str(tmp_path),))


def test_suppression_comment_silences_one_rule(tmp_path):
    diags = _scan(tmp_path, """
        def f(s):
            return hash(s)  # flcheck: disable=FLC002
    """)
    assert diags == []


def test_bare_suppression_silences_all_rules(tmp_path):
    diags = _scan(tmp_path, """
        import jax

        def f(model, s):
            g = jax.jit(model.step)  # flcheck: disable
            return g(hash(s))  # flcheck: disable
    """)
    assert diags == []


def test_unsuppressed_hash_is_flagged(tmp_path):
    diags = _scan(tmp_path, """
        def f(s):
            return hash(s)
    """)
    assert [d.rule for d in diags] == ["FLC002"]


def test_module_attribute_jit_not_flagged(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "__init__.py").write_text("")
    (tmp_path / "pkg" / "mod.py").write_text("def fn(x):\n    return x\n")
    diags = _scan(tmp_path, """
        import jax
        from pkg import mod

        def caller(x):
            return jax.jit(mod.fn)(x)
    """)
    assert diags == []


def test_bound_method_jit_flagged(tmp_path):
    diags = _scan(tmp_path, """
        import jax

        def caller(model, x):
            return jax.jit(model.fn)(x)
    """)
    assert [d.rule for d in diags] == ["FLC001"]


def test_flc003_needs_jit_reachability(tmp_path):
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        def helper(x):
            s = jnp.sum(x)
            return float(s)
    """)
    assert _scan(tmp_path, src) == []
    # same helper, now called from a jit root: host sync becomes an error
    diags = _scan(tmp_path, src + textwrap.dedent("""
        @jax.jit
        def root(x):
            return helper(x)
    """))
    assert [d.rule for d in diags] == ["FLC003"]


def test_flc003_cross_file_reachability(tmp_path):
    (tmp_path / "helpers.py").write_text(textwrap.dedent("""
        import jax.numpy as jnp

        def helper(x):
            s = jnp.sum(x)
            return float(s)
    """))
    (tmp_path / "driver.py").write_text(textwrap.dedent("""
        import jax
        from helpers import helper

        @jax.jit
        def root(x):
            return helper(x)
    """))
    diags = checker.check_paths(
        [str(tmp_path / "helpers.py"), str(tmp_path / "driver.py")],
        search_dirs=(str(tmp_path),),
    )
    assert [(os.path.basename(d.path), d.rule) for d in diags] == [
        ("helpers.py", "FLC003")
    ]


def test_pinned_fragments_are_long_literals():
    errors_path = checker.find_errors_module([os.path.join(REPO, "src")])
    fragments = checker.pinned_fragments(errors_path)
    assert all(len(f) >= 24 for f in fragments)
    # every shared constant contributes a signature
    for const in ("ERR_OTA_TOPK", "ERR_OTA_COMPRESSION", "ERR_OTA_MAPEL",
                  "ERR_OTA_ALIGN_UPLINK", "ERR_SCAN_ONLINE_POLICY"):
        assert const in fragments.values()


def test_flc008_needs_jit_reachability(tmp_path):
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from repro.utils import spans

        def helper(x):
            with spans.span("fl.step"):
                return jnp.sum(x)

        def driver(x):
            with spans.span("fl.horizon"):
                return helper(x)
    """)
    assert _scan(tmp_path, src) == []
    # the same helper reached from a jit root: the span would time tracing
    diags = _scan(tmp_path, src + textwrap.dedent("""
        @jax.jit
        def root(x):
            return helper(x)
    """))
    assert [(d.line, d.rule) for d in diags] == [(7, "FLC008")]

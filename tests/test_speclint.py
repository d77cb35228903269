"""Unit tests for the speclint call-signature pass (tools/speclint.py).

The pass is the repo's slice of the reference's strict-mypy gate
(reference Makefile:133-136, linter.ini): a fork override that changes a
helper's parameters must fail `make lint` at every stale call site.
These tests seed exactly that class of bug into a synthetic namespace
and check the pass reports it — and stays silent on the legal shapes it
must not flag (splats, shadowing, defaults, keywords).
"""
import importlib.util
import os
import textwrap

import pytest

_SPECLINT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools", "speclint.py"
)


@pytest.fixture(scope="module")
def speclint():
    spec = importlib.util.spec_from_file_location("speclint_under_test", _SPECLINT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build_ns(src, tmp_path, name="seeded_spec"):
    """Exec ``src`` the way the builder does — compiled against a real
    file so inspect.getsource works — and return the namespace dict."""
    path = tmp_path / f"{name}.py"
    path.write_text(textwrap.dedent(src))
    ns = {}
    code = compile(path.read_text(), str(path), "exec")
    exec(code, ns)
    # mimic module globals: functions defined by the exec see ns as their
    # __globals__, which is what check_call_signatures keys on
    return ns


def test_wrong_arity_is_caught(speclint, tmp_path):
    ns = _build_ns(
        """
        def helper(state, index):
            return index

        def process_thing(state):
            return helper(state)  # stale call site: missing 'index'
        """,
        tmp_path,
    )
    findings = speclint.check_call_signatures(ns, "<seeded>")
    assert len(findings) == 1
    assert "process_thing" in findings[0] and "helper()" in findings[0]


def test_unknown_keyword_is_caught(speclint, tmp_path):
    ns = _build_ns(
        """
        def helper(state, index=0):
            return index

        def process_thing(state):
            return helper(state, idx=3)  # typo'd keyword
        """,
        tmp_path,
    )
    findings = speclint.check_call_signatures(ns, "<seeded>")
    assert len(findings) == 1 and "does not bind" in findings[0]


def test_too_many_positionals_is_caught(speclint, tmp_path):
    ns = _build_ns(
        """
        def helper(state):
            return state

        def process_thing(state):
            return helper(state, 1, 2)
        """,
        tmp_path,
    )
    assert len(speclint.check_call_signatures(ns, "<seeded>")) == 1


def test_legal_shapes_stay_silent(speclint, tmp_path):
    ns = _build_ns(
        """
        def helper(state, index=0, *, flag=False):
            return index

        def uses_default(state):
            return helper(state)

        def uses_keyword(state):
            return helper(state, index=2, flag=True)

        def uses_splat(state, args):
            return helper(*args)  # unknowable statically: skipped

        def shadows(state):
            helper = len  # local shadow: the ns function is NOT the callee
            return helper(state)
        """,
        tmp_path,
    )
    assert speclint.check_call_signatures(ns, "<seeded>") == []


def test_non_function_callees_are_skipped(speclint, tmp_path):
    ns = _build_ns(
        """
        class Thing:
            def __init__(self, a, b):
                pass

        def make(state):
            return Thing(1, 2, 3)  # classes use a different convention: skipped
        """,
        tmp_path,
    )
    assert speclint.check_call_signatures(ns, "<seeded>") == []


# ---------------------------------------------------------------------------
# duplicate-definition sweep (pyflakes F811 class)
# ---------------------------------------------------------------------------


def _dup_findings(speclint, src):
    import ast

    src = textwrap.dedent(src)
    tree = ast.parse(src)
    noqa = {i + 1 for i, line in enumerate(src.splitlines())
            if "noqa" in line}
    return speclint.check_duplicate_defs(tree, "mod.py", noqa)


def test_duplicate_test_function_is_caught(speclint):
    findings = _dup_findings(
        speclint,
        """
        def test_x():
            assert True

        def test_x():  # the classic: the first test silently never runs
            assert False
        """,
    )
    assert len(findings) == 1
    assert "test_x" in findings[0] and "line 2" in findings[0]


def test_duplicate_class_and_method_are_caught(speclint):
    findings = _dup_findings(
        speclint,
        """
        class C:
            def m(self):
                return 1

            def m(self):
                return 2

        class C:
            pass
        """,
    )
    assert len(findings) == 2
    assert any("'m'" in f for f in findings)
    assert any("'C'" in f for f in findings)


def test_branch_split_definitions_are_legal(speclint):
    findings = _dup_findings(
        speclint,
        """
        try:
            from fast import impl
        except ImportError:
            def impl():
                return None

        if True:
            def helper():
                return 1
        else:
            def helper():
                return 2
        """,
    )
    assert findings == []


def test_duplicate_inside_else_branch_is_caught(speclint):
    findings = _dup_findings(
        speclint,
        """
        try:
            import fast
        except ImportError:
            pass
        else:
            def test_x():
                assert True

            def test_x():
                assert False
        """,
    )
    assert len(findings) == 1 and "test_x" in findings[0]


def test_property_setter_idiom_is_exempt(speclint):
    findings = _dup_findings(
        speclint,
        """
        class C:
            @property
            def x(self):
                return self._x

            @x.setter
            def x(self, v):
                self._x = v
        """,
    )
    assert findings == []


def test_mark_decorated_duplicates_are_still_caught(speclint):
    # the exemption is ONLY the @x.setter accumulator idiom; a foreign
    # dotted decorator must not shield a shadowing redefinition
    findings = _dup_findings(
        speclint,
        """
        import pytest

        @pytest.mark.slow
        def test_x():
            assert True

        @pytest.mark.slow
        def test_x():
            assert False
        """,
    )
    assert len(findings) == 1 and "test_x" in findings[0]


def test_noqa_suppresses_duplicate_definition(speclint):
    findings = _dup_findings(
        speclint,
        """
        def f():
            return 1

        def f():  # noqa: deliberate override
            return 2
        """,
    )
    assert findings == []


def test_repo_tooling_is_covered_by_the_walk(speclint):
    # the satellite contract: the source walk lints tools/ and chip_smoke.py,
    # not just the package — a duplicate def there must be reachable
    files = list(speclint._py_files())
    names = {os.path.basename(f) for f in files}
    assert "chip_smoke.py" in names and "speclint.py" in names
    assert any(os.sep + "tools" + os.sep in f for f in files)

"""The suite's own configuration: the per-test time limit of pytest.ini
holds with or without pytest-timeout, and ``slow`` is off by default but
still selectable. Both run the repository's real conftest.py and
pytest.ini in a scratch directory."""
import os

pytest_plugins = "pytester"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name):
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


def test_time_limit_fails_a_sleeping_test_under_xdist(pytester):
    """A test that sleeps past the limit fails with the limit's message on
    its xdist worker, and that worker goes on to the next test."""
    pytester.makeconftest(_read("conftest.py"))
    pytester.makeini("[pytest]\ntimeout = 1\n")
    pytester.makepyfile(
        test_sleep="""
            import time

            def test_sleeps_past_the_limit():
                time.sleep(60)

            def test_after_on_the_same_worker():
                pass
        """,
        test_other="def test_other():\n    pass\n")
    result = pytester.runpytest_subprocess(
        "-p", "xdist", "-n", "6", "--dist", "loadfile", timeout=120)
    result.assert_outcomes(passed=2, failed=1)
    result.stdout.fnmatch_lines(["*test_sleeps_past_the_limit*",
                                 "*Timeout*"])
    out = result.stdout.str() + result.stderr.str()
    assert "Unknown config option" not in out
    assert result.duration < 60


def test_slow_is_off_by_default_and_selectable(pytester):
    pytester.makeini(_read("pytest.ini"))
    pytester.makepyfile(test_marks="""
        import pytest

        def test_fast():
            pass

        @pytest.mark.slow
        def test_heavy():
            pass
    """)
    default = pytester.runpytest("-v")
    default.assert_outcomes(passed=1, deselected=1)
    default.stdout.fnmatch_lines(["*test_fast PASSED*"])
    slow = pytester.runpytest("-v", "-m", "slow")
    slow.assert_outcomes(passed=1, deselected=1)
    slow.stdout.fnmatch_lines(["*test_heavy PASSED*"])

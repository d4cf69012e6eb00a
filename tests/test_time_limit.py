"""The suite's own net: every test's time limit (``tests/conftest.py``
``TEST_LIMIT_S``, ``@pytest.mark.time_limit``). A child pytest loads that
conftest on a temporary file of tests that wait for ever: the one that
waits fails under its own name with every thread's stack, the tests
after it run, and the run's exit code is pytest's 1, not a clock's 124."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)


def child_pytest(tmp_path, source, *args):
    """Run ``source`` as ``test_child.py`` under the repo's conftest;
    returns the finished process and the seconds it took."""
    (tmp_path / "test_child.py").write_text(textwrap.dedent(source))
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["PYTHONPATH"] = os.pathsep.join([TESTS, REPO])
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-p",
         "no:cacheprovider", "-v", *args, "test_child.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    return proc, time.monotonic() - t0


@pytest.fixture(scope="module")
def alarm_run(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("alarm")
    proc, seconds = child_pytest(tmp_path, """
        import threading
        import time

        import pytest


        def never_answers(gate, answered):
            gate.wait()
            answered.set()


        @pytest.mark.time_limit(2)
        def test_blocks():
            answered = threading.Event()
            threading.Thread(target=never_answers, daemon=True,
                             args=(threading.Event(), answered)).start()
            answered.wait()


        def test_after_the_blocked_one():
            pass


        @pytest.mark.time_limit(2)
        def test_quick_under_a_short_limit():
            pass


        def test_three_seconds_after_a_two_second_limit():
            time.sleep(3)
        """, "-p", "no:xdist", "--junitxml=junit.xml")
    return proc, seconds, (tmp_path / "junit.xml").read_text()


def test_blocked_test_fails_under_its_own_name_with_every_stack(alarm_run):
    proc, _, junit = alarm_run
    assert "test_child.py::test_blocks FAILED" in proc.stdout, proc.stdout
    report = proc.stdout.split("_ test_blocks _", 1)[1]
    assert "time limit of 2 s reached" in report
    # Who waited (the main thread) and on whom.
    assert "Current thread" in report and "in test_blocks" in report
    assert "in never_answers" in report
    case = junit.split('name="test_blocks"', 1)[1].split("</testcase>", 1)[0]
    assert "<failure" in case and "in never_answers" in case


def test_the_file_goes_on_and_the_run_ends_with_rc_1(alarm_run):
    proc, seconds, _ = alarm_run
    assert "test_child.py::test_after_the_blocked_one PASSED" in proc.stdout
    assert "1 failed, 3 passed" in proc.stdout, proc.stdout
    assert proc.returncode == 1, proc.stderr
    assert seconds < 60.0


def test_limit_is_disarmed_between_tests(alarm_run):
    proc, _, _ = alarm_run
    assert ("test_child.py::test_three_seconds_after_a_two_second_limit "
            "PASSED") in proc.stdout, proc.stdout


def test_backstop_ends_a_main_thread_no_signal_reaches(tmp_path):
    """SIGALRM masked stands for a main thread stuck in native code: the
    backstop writes the stacks to stderr and ends the worker, xdist names
    the test the worker went down in, and (``--dist loadfile``) a new
    worker takes the file's pending tests, the crashed one among them —
    which here waits only the first time round."""
    proc, _ = child_pytest(tmp_path, """
        import os
        import signal
        import threading

        import pytest


        def test_before():
            pass


        @pytest.mark.time_limit(2)
        def test_masked():
            if not os.path.exists("waited_once"):
                open("waited_once", "w").close()
                signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
                threading.Event().wait()


        def test_after():
            pass
        """, "-p", "xdist", "-n", "1", "--dist", "loadfile")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "Timeout (0:00:07)!" in proc.stderr and "in test_masked" in proc.stderr
    assert ("worker 'gw0' crashed while running "
            "'test_child.py::test_masked'") in proc.stdout, proc.stdout
    assert "test_child.py::test_after" in proc.stdout
    assert "1 failed, 3 passed" in proc.stdout, proc.stdout

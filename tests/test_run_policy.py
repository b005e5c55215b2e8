"""The run's own policy (``tests/conftest.py``): a limit a case, shown on a
throw-away directory whose ``conftest.py`` takes this repo's hooks with
``CASE_LIMIT_S`` at 2 s, run by a pytest of its own (under xdist, and in one
process)."""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

CONFTEST = pathlib.Path(__file__).with_name("conftest.py")


@pytest.fixture
def policy(request):
    return request.config.pluginmanager.get_plugin(str(CONFTEST))


@pytest.fixture
def throw_away(tmp_path):
    """A directory with this repo's hooks at a limit of 2 s, four cases of
    which one sleeps ``SLEEPS`` seconds beside a waiting thread, and a way to
    run it."""
    (tmp_path / "pytest.ini").write_text("[pytest]\n")
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""\
        import importlib.util
        spec = importlib.util.spec_from_file_location("the_repos_conftest",
                                                      {str(CONFTEST)!r})
        policy = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(policy)
        policy.CASE_LIMIT_S = 2
        globals().update({{name: value for name, value in vars(policy).items()
                          if not name.startswith("__")}})
        """))
    (tmp_path / "test_cases.py").write_text(textwrap.dedent("""\
        import os, threading, time
        import pytest

        def an_engine_thread_waits(stop):
            stop.wait()

        def test_sleeps():
            stop = threading.Event()
            thread = threading.Thread(target=an_engine_thread_waits,
                                      args=(stop,), daemon=True)
            thread.start()
            time.sleep(float(os.environ["SLEEPS"]))
            stop.set()
            thread.join()

        @pytest.mark.parametrize("n", range(3))
        def test_ends_in_time(n):
            time.sleep(0.1)
        """))
    env = {name: value for name, value in os.environ.items()
           if not name.startswith(("PYTEST_", "JAX_COMPILATION_CACHE_DIR"))}

    def run(sleeps, *options):
        return subprocess.run(
            [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
             "-p", "no:randomly", "-rf", *options],
            cwd=tmp_path, env={**env, "SLEEPS": str(sleeps)},
            capture_output=True, text=True, timeout=240)
    return run


def test_a_case_that_never_returns_ends_the_case_not_the_run(throw_away):
    """Under two workers: the worker whose case is still running after the
    limit writes every thread's stack and exits, xdist fails THAT case by
    name and deals the rest on, and the run ends by itself with exit code
    1."""
    done = throw_away(600, "-p", "xdist", "-n", "2")
    assert done.returncode == 1, done.stdout + done.stderr
    assert "node down" in done.stdout
    assert "FAILED test_cases.py::test_sleeps" in done.stdout
    assert "crashed while running 'test_cases.py::test_sleeps'" in done.stdout
    assert "1 failed, 3 passed" in done.stdout
    assert "Timeout (0:00:02)!" in done.stderr
    assert " in test_sleeps" in done.stderr
    assert " in an_engine_thread_waits" in done.stderr


def test_in_one_process_the_stacks_are_written_and_the_case_goes_on(
        throw_away):
    done = throw_away(3, "-p", "no:xdist")
    assert done.returncode == 0, done.stdout + done.stderr
    assert "4 passed" in done.stdout
    assert " in test_sleeps" in done.stderr
    assert " in an_engine_thread_waits" in done.stderr
    assert done.stderr.count("Timeout (0:00:02)!") == 1


@pytest.mark.parametrize("ends", ["returns", "raises"])
def test_a_case_that_ends_leaves_no_timer_armed(policy, request, monkeypatch,
                                                ends):
    calls = []
    monkeypatch.setattr(policy.faulthandler, "dump_traceback_later",
                        lambda *a, **kw: calls.append(("arm", a, kw)))
    monkeypatch.setattr(policy.faulthandler, "cancel_dump_traceback_later",
                        lambda: calls.append(("cancel",)))
    case = policy.pytest_runtest_protocol(item=request.node, nextitem=None)
    next(case)
    (_, (limit,), how), = calls
    assert limit == policy.CASE_LIMIT_S
    # only a worker may exit: a developer's one process goes on
    assert how["exit"] == ("PYTEST_XDIST_WORKER" in os.environ)
    os.fstat(how["file"])           # the run's stderr, an open descriptor
    if ends == "returns":
        with pytest.raises(StopIteration) as over:
            case.send(True)
        assert over.value.value is True
    else:
        with pytest.raises(KeyboardInterrupt):
            case.throw(KeyboardInterrupt())
    assert calls[1:] == [("cancel",)]


def test_the_limit_is_a_quarter_of_the_drivers_clock_at_most(policy, request):
    assert 0 < policy.CASE_LIMIT_S <= 1470 / 4
    # pytest's own timer only dumps, and there is one timer a process
    assert not request.config.getini("faulthandler_timeout")

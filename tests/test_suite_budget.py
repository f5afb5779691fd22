"""What keeps tier-1 inside its clock (ROADMAP.md, "Tier-1 verify"), held by the tree itself: no
engine, no runtime. The driver runs the suite under ``-n 6 --dist loadfile`` and cuts it at 1,470 s;
a run that is cut counts only as far as it got."""

import importlib
import os
import re
import subprocess
import sys
import textwrap
import time

TESTS = os.path.dirname(os.path.abspath(__file__))


def _source(name):
    with open(os.path.join(TESTS, name), encoding="utf-8") as f:
        return f.read()


def test_one_file_alone_describes_a_tpu_topology():
    """``--dist loadfile`` hands a file to ONE worker, and only one process may load the TPU
    library: every test that compiles for a described chip lives in ``test_chip_compile.py``."""
    describing = [os.path.relpath(os.path.join(d, n), TESTS) for d, _, names in os.walk(TESTS) for n in names
                  if n.endswith(".py") and n != os.path.basename(__file__) and "get_topology_desc" in _source(os.path.join(d, n))]
    assert describing == ["test_chip_compile.py"]


def test_the_watchdogs_default_is_at_most_240_seconds_and_one_variable_sets_it():
    conftest = _source("conftest.py")
    assert [int(s) for s in re.findall(r'os\.environ\.get\("RT_TEST_TIMEOUT_S", "(\d+)"\)', conftest)] == [240]
    assert conftest.count("os.environ.get(") == 2, "RT_TEST_TIMEOUT_S and XLA_FLAGS: no second knob"


def test_a_wait_in_a_fixture_fails_inside_the_limit_with_every_stack_and_the_run_goes_on(tmp_path):
    """The watchdog is armed before a test's set-up and stays armed through its tear-down: a
    fixture that sleeps past a 1 s limit fails THAT test (with the stacks of the threads in its
    captured stderr), whichever side of the ``yield`` it sleeps on, and the next test still runs."""
    (tmp_path / "test_waits.py").write_text(textwrap.dedent("""
        import time, pytest

        @pytest.fixture
        def waits_in_set_up():
            time.sleep(60)
            yield

        @pytest.fixture
        def waits_in_tear_down():
            yield
            time.sleep(60)

        def test_a(waits_in_set_up): pass
        def test_b(waits_in_tear_down): pass
        def test_c(): pass
    """))
    env = {**os.environ, "RT_TEST_TIMEOUT_S": "1", "PYTHONPATH": os.pathsep.join([TESTS, os.path.dirname(TESTS)])}
    t0 = time.time()
    r = subprocess.run([sys.executable, "-m", "pytest", "test_waits.py", "-p", "conftest", "-p", "no:cacheprovider", "-p", "no:xdist", "-q"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    out = r.stdout + r.stderr
    assert r.returncode == 1 and time.time() - t0 < 60, out[-3000:]
    assert "2 passed, 2 errors" in out, out[-3000:]  # test_b's body passed; its tear-down is the second error
    for test in ("test_a", "test_b"):
        assert f"test_waits.py::{test} exceeded the 1s watchdog" in out, out[-3000:]
    assert out.count("(most recent call first)") >= 2 and "in waits_in_set_up" in out and "in waits_in_tear_down" in out, out[-3000:]


def _description_files():
    """Every test file that states a ``DESC``, found by reading the files: the tenth is held without an edit here."""
    return sorted(n[:-3] for n in os.listdir(TESTS) if n.startswith("test_") and n.endswith(".py") and re.search(r"^DESC = ", _source(n), re.M))


def test_every_description_file_runs_the_one_hybrid_battery():
    """Every file that states a description the hybrid loop serves holds the SAME test functions,
    the battery's own: none is a copy with the description changed."""
    battery = importlib.import_module("hybrid_battery")
    shared = [n for n in battery.__all__ if n.startswith("test_")]
    assert len(shared) >= 8 and len(_description_files()) >= 9
    for name in _description_files():
        module = importlib.import_module(name)
        assert isinstance(module.DESC, battery.Description)
        assert [n for n in shared if getattr(module, n, None) is not getattr(battery, n)] == [], name


# ``tests/SECONDS.md`` is one machine's record (it says which) of one whole run under the driver's command,
# written by ``scripts/suite_seconds.py``. What is held here is its shape and its rules, and no second is
# timed: a PR that adds a file, or seconds, runs the suite, renews the table, and where it must, these.
FILE_LIMIT_S = 240  # no file alone over what Tier-1 gives ONE test: under ``--dist loadfile`` a file is one worker's, and the longest bounds the run
OVER_THE_LIMIT = {  # the files that may be, each with what keeps it there: the one by rule, and at most three more
    "test_chip_compile.py": "one file by rule: only one process may load the TPU compiler (test_one_file_alone_describes_a_tpu_topology)",
    "test_kimi_linear.py": "the battery, the shares and the placement's cases, and a delta rule with a gate by channel checked op by op at four gates and four lengths",
    "test_keye_vl.py": "the plain reference sorts every query's scores, 5 s a round of four prompts, and four of its five faults need programs of their own",
    "test_qwen3_next.py": "the battery, the shares and the placement's cases beside two mixers and an expert block checked form against form, op by op",
}
SUM_LIMIT_S = 6457.5  # the table's machine (a builder's 8 cores, not the driver's): PR 62's second whole run after its change, 6,150.0 s, plus 5%


def test_the_table_of_seconds_has_a_row_for_every_test_file_and_keeps_inside_its_limits():
    sys.path.insert(0, os.path.join(os.path.dirname(TESTS), "scripts"))
    try:
        rows, said_sum = importlib.import_module("suite_seconds").read_table()
    finally:
        sys.path.pop(0)
    files = {os.path.relpath(os.path.join(d, n), TESTS) for d, _, names in os.walk(TESTS) for n in names if re.fullmatch(r"test_.*\.py", n)}
    assert files - set(rows) == set(), "run the suite whole and renew the table: python scripts/suite_seconds.py <junit xml>"
    assert set(rows) - files == set(), "rows of files that are gone"
    assert abs(sum(s for _, s in rows.values()) - said_sum) < 1.0 and said_sum < SUM_LIMIT_S
    over = {name for name, (_, seconds) in rows.items() if seconds > FILE_LIMIT_S}
    assert over <= set(OVER_THE_LIMIT) and len(OVER_THE_LIMIT) <= 4, over

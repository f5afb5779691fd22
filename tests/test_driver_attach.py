"""Driver attach: external processes join the running cluster.

Reference test strategy: python/ray/tests/test_multi_node* (drivers
connecting via ray.init(address=...)) and the job-manager tests that
assert submitted entrypoints run against the shared cluster.
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu

_DRIVER_ENV = {
    "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
    "JAX_PLATFORMS": "cpu",
    # what a head exports to everything it starts: ``address="auto"`` is then THIS worker's head, not the
    # newest session on a machine where five other pytest workers start and stop heads of their own
    "RT_SESSION_PID": str(os.getpid()),
}


def _run_driver(script: str, extra_env: dict | None = None, timeout: float = 180.0):
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**_DRIVER_ENV, **(extra_env or {})},
    )


def test_external_driver_tasks_objects_and_named_actors(rt_start):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def add(self, k):
            self.n += k
            return self.n

    c = Counter.options(name="shared_counter", namespace="default").remote()
    assert ray_tpu.get(c.add.remote(5)) == 5

    p = _run_driver(
        """
        import ray_tpu, numpy as np
        ray_tpu.init(address="auto")
        r = ray_tpu.put(np.arange(100))
        assert ray_tpu.get(r).sum() == 4950

        @ray_tpu.remote
        def f(x):
            return x * 2

        assert ray_tpu.get(f.remote(21)) == 42
        c = ray_tpu.get_actor("shared_counter", namespace="default")
        print("ATTACH_RESULT", ray_tpu.get(c.add.remote(7)))
        ray_tpu.shutdown()
        """
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "ATTACH_RESULT 12" in p.stdout
    # the mutation happened on the HEAD's actor, not a private copy
    assert ray_tpu.get(c.add.remote(1)) == 13


def test_auto_is_the_session_a_process_belongs_to_not_the_newest_on_the_machine(rt_start):
    """Another head's ``cluster_info.json``, newer than this one's and of a live process, does not
    draw a driver that carries this head's pid in ``RT_SESSION_PID`` (``state.load_latest_cluster_info``)."""
    import json
    import shutil

    from ray_tpu.util.state import session_dir

    other = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        os.makedirs(session_dir(other.pid), exist_ok=True)
        with open(os.path.join(session_dir(other.pid), "cluster_info.json"), "w") as f:
            json.dump({"pid": other.pid, "agent_address": ["127.0.0.1", 1], "authkey": "00"}, f)
        p = _run_driver("from ray_tpu.util.state import load_latest_cluster_info as info; print('HEAD', info()['pid'])")
        assert f"HEAD {os.getpid()}" in p.stdout, (p.stdout, p.stderr[-1500:])
    finally:
        other.kill()
        other.wait()
        shutil.rmtree(session_dir(other.pid), ignore_errors=True)


def test_driver_attach_requires_authkey(rt_start):
    """A dialer without the session authkey must be rejected at the mp
    auth handshake — the same gate agents pass through."""
    import json

    from ray_tpu.util.state import session_dir

    with open(os.path.join(session_dir(), "cluster_info.json")) as f:  # this head's own, not the machine's newest
        info = json.load(f)
    host, port = info["agent_address"]
    p = _run_driver(
        f"""
        from multiprocessing import connection
        try:
            conn = connection.Client(("{host}", {port}), "AF_INET", authkey=b"wrong-key-000000")
            print("CONNECTED")  # must not happen
        except Exception as e:
            print("REJECTED", type(e).__name__)
        """,
        timeout=60,
    )
    assert "REJECTED" in p.stdout and "CONNECTED" not in p.stdout


def test_submitted_job_runs_against_shared_cluster(rt_start):
    """The job manager exports RT_HEAD_ADDRESS so a plain init() inside
    the entrypoint attaches (reference: job supervisor sets RAY_ADDRESS;
    previously each job booted a private head)."""
    from ray_tpu.job.job_manager import JobSubmissionClient

    @ray_tpu.remote
    class Board:
        def __init__(self):
            self.v = None

        def set(self, v):
            self.v = v

        def get(self):
            return self.v

    b = Board.options(name="board", namespace="default").remote()
    ray_tpu.get(b.set.remote("empty"))

    client = JobSubmissionClient()
    ep = (
        f"{sys.executable} -c \""
        "import ray_tpu; ray_tpu.init(); "
        "b = ray_tpu.get_actor('board', namespace='default'); "
        "ray_tpu.get(b.set.remote('written-by-job')); ray_tpu.shutdown()\""
    )
    job_id = client.submit_job(entrypoint=ep, runtime_env={"env_vars": {"JAX_PLATFORMS": "cpu"}})
    status = None
    for _ in range(240):
        status = str(client.get_job_status(job_id))
        if "SUCCEEDED" in status or "FAILED" in status:
            break
        time.sleep(0.5)
    assert "SUCCEEDED" in status, client.get_job_logs(job_id)[-2000:]
    assert ray_tpu.get(b.get.remote()) == "written-by-job"


def test_driver_disconnect_drops_ref_holder(rt_start):
    """A driver that exits while holding the only external reference must
    not leak the holder entry: the head drops it like a dead worker's
    (runtime._driver_pump finally-path)."""
    client = ray_tpu._auto_init() if hasattr(ray_tpu, "_auto_init") else None
    from ray_tpu.core import context

    rt = context.get_client()
    before = len(rt._drivers)
    p = _run_driver(
        """
        import ray_tpu
        ray_tpu.init(address="auto")
        r = ray_tpu.put(b"x" * 1024)
        import sys
        print("PUT_OK", r.id.hex())
        sys.stdout.flush()
        # exit WITHOUT shutdown: the pump's EOF path must clean up
        import os
        os._exit(0)
        """
    )
    assert "PUT_OK" in p.stdout, p.stderr[-1500:]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and len(rt._drivers) > before:
        time.sleep(0.2)
    assert len(rt._drivers) == before  # pump reaped the connection


def test_head_shutdown_fails_driver_calls_fast(rt_start):
    """After the head goes away, a blocked/subsequent driver call raises
    ConnectionError instead of hanging (DriverClient fail-fast path)."""
    p = _run_driver(
        """
        import threading, time
        import ray_tpu
        client = ray_tpu.init(address="auto")

        @ray_tpu.remote
        class Sleeper:
            def nap(self, s):
                import time as t
                t.sleep(s)
                return "done"

        s = Sleeper.remote()
        ref = s.nap.remote(60)
        time.sleep(1)
        # sever the link (simulates head death for this driver)
        client.conn.close()
        try:
            ray_tpu.get(ref, timeout=30)
            print("NO_ERROR")
        except Exception as e:
            print("FAILED_FAST", type(e).__name__)
        """,
        timeout=120,
    )
    assert "FAILED_FAST" in p.stdout, (p.stdout, p.stderr[-1500:])


def test_attach_rejects_resource_args():
    ray_tpu.shutdown()
    with pytest.raises(ValueError, match="attaches to an existing cluster"):
        ray_tpu.init(address="auto", num_cpus=2)


def test_env_attach_yields_to_explicit_sizing(rt_start, monkeypatch):
    """A job entrypoint that explicitly asks for a self-contained runtime
    (sizing args) gets one even though RT_HEAD_ADDRESS is exported."""
    p = _run_driver(
        """
        import ray_tpu
        client = ray_tpu.init(num_cpus=1)
        from ray_tpu.core.runtime import Runtime
        assert isinstance(client, Runtime), type(client)
        ray_tpu.shutdown()
        print("OWN_RUNTIME_OK")
        """,
        extra_env={"RT_HEAD_ADDRESS": "127.0.0.1:1"},  # would fail if dialed
    )
    assert "OWN_RUNTIME_OK" in p.stdout, p.stderr[-1500:]

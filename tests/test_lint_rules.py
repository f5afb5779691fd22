"""Per-rule positive/negative fixtures for tpulint (ray_tpu/lint/).

Each rule gets at least one fixture that MUST fire and one that MUST
stay silent — the silent side is what keeps the analyzer usable (a noisy
rule gets baselined into oblivion). Engine-level behavior (fingerprints,
inline suppression, baseline counts) is covered at the bottom.
"""

import textwrap

import pytest

from ray_tpu.lint import baseline as bl
from ray_tpu.lint.engine import Finding, lint_source
from ray_tpu.lint.rules import all_rules, rule_catalog


def run(src: str, rule_id: str | None = None):
    out = lint_source(textwrap.dedent(src), path="fixture.py")
    assert not any(f.rule == "TPLERR" for f in out), out
    if rule_id is None:
        return out
    return [f for f in out if f.rule == rule_id]


def test_catalog_has_at_least_six_rules():
    cat = rule_catalog()
    assert len(cat) >= 6
    assert len({rid for rid, _, _ in cat}) == len(cat), "duplicate rule ids"
    assert len(all_rules()) == len(cat)


# ------------------------------------------------------------------ TPL001
def test_tpl001_flags_get_in_actor_method():
    out = run("""
        import ray_tpu

        @ray_tpu.remote
        class Pump:
            def step(self, ref):
                return ray_tpu.get(ref)
    """, "TPL001")
    assert len(out) == 1
    assert out[0].context == "Pump.step"


def test_tpl001_flags_blocking_get_in_async_def():
    out = run("""
        import ray_tpu

        async def handler(ref):
            return ray_tpu.get(ref)
    """, "TPL001")
    assert len(out) == 1


def test_tpl001_silent_on_plain_function_and_bounded_get():
    assert run("""
        import ray_tpu

        def driver(ref):
            return ray_tpu.get(ref)

        @ray_tpu.remote
        class Pump:
            def step(self, ref):
                return ray_tpu.get(ref, timeout=30.0)
    """, "TPL001") == []


def test_tpl001_silent_on_non_actor_class():
    assert run("""
        import ray_tpu

        class Helper:
            def step(self, ref):
                return ray_tpu.get(ref)
    """, "TPL001") == []


# ------------------------------------------------------------------ TPL002
def test_tpl002_flags_dropped_remote_result():
    out = run("""
        def kick(actor):
            actor.ping.remote()
            actor.options(num_cpus=1).remote()
    """, "TPL002")
    assert len(out) == 2


def test_tpl002_silent_when_ref_is_kept_or_awaited():
    assert run("""
        async def kick(actor, f):
            r = actor.ping.remote()
            refs = [f.remote() for _ in range(3)]
            await actor.ping.remote()
            return r, refs
    """, "TPL002") == []


# ------------------------------------------------------------------ TPL003
def test_tpl003_flags_closure_captured_lock():
    out = run("""
        import threading
        import ray_tpu

        def make_job():
            lock = threading.Lock()

            @ray_tpu.remote
            def job():
                with lock:
                    return 1

            return job
    """, "TPL003")
    assert len(out) == 1
    assert "lock" in out[0].message


def test_tpl003_flags_hazard_default_argument():
    out = run("""
        import threading
        import ray_tpu

        @ray_tpu.remote
        def job(l=threading.Lock()):
            return l
    """, "TPL003")
    assert len(out) == 1


def test_tpl003_silent_when_constructed_inside_or_shadowed():
    assert run("""
        import threading
        import ray_tpu

        def make_job():
            lock = threading.Lock()

            @ray_tpu.remote
            def job():
                lock = threading.Lock()  # local, not a capture
                with lock:
                    return 1

            @ray_tpu.remote
            def other(n):
                return n + 1  # never touches the enclosing lock

            return job, other
    """, "TPL003") == []


# ------------------------------------------- CCR006 (absorbed TPL004)
ABBA_SRC = """
    import threading

    a_lock = threading.Lock()
    b_lock = threading.Lock()

    def fwd():
        with a_lock:
            with b_lock:
                pass

    def rev():
        with b_lock:
            with a_lock:
                pass
"""


def test_ccr006_flags_abba_inversion():
    out = run(ABBA_SRC, "CCR006")
    assert len(out) == 1
    assert "a_lock" in out[0].message and "b_lock" in out[0].message


def test_ccr006_flags_self_lock_inversion_across_methods():
    out = run("""
        class Registry:
            def put(self):
                with self._lock:
                    with self._conns_lock:
                        pass

            def drop(self):
                with self._conns_lock:
                    with self._lock:
                        pass
    """, "CCR006")
    assert len(out) == 1


def test_ccr006_silent_on_consistent_order_and_multi_item_with():
    assert run("""
        import threading

        a_lock = threading.Lock()
        b_lock = threading.Lock()

        def one():
            with a_lock, b_lock:
                pass

        def two():
            with a_lock:
                with b_lock:
                    pass
    """, "CCR006") == []


def test_ccr006_nesting_does_not_cross_function_boundaries():
    # a nested def's body starts with an empty held-set: this is the
    # dynamic sanitizer's territory, not lexical nesting
    assert run("""
        def outer():
            with a_lock:
                def inner():
                    with b_lock:
                        pass
                return inner

        def other():
            with b_lock:
                with a_lock:
                    pass
    """, "CCR006") == []


# ------------------------------------- TPL004 -> CCR006 alias contract
def test_tpl004_alias_select_runs_ccr006():
    # pre-absorption --select specs keep working; the finding carries the
    # CANONICAL id (the baseline handles old-id fingerprints separately)
    rules = all_rules({"TPL004"})
    assert [r.id for r in rules] == ["CCR006"]
    out = lint_source(textwrap.dedent(ABBA_SRC), path="fixture.py", rules=rules)
    assert [f.rule for f in out] == ["CCR006"]


def test_tpl004_alias_inline_disable_suppresses_ccr006():
    src = textwrap.dedent(ABBA_SRC)
    f = [x for x in lint_source(src, path="fixture.py") if x.rule == "CCR006"][0]
    lines = src.splitlines()
    lines[f.line - 1] += "  # tpulint: disable=TPL004"
    patched = "\n".join(lines)
    assert [x for x in lint_source(patched, path="fixture.py") if x.rule == "CCR006"] == []


def test_tpl004_alias_baseline_entry_suppresses_ccr006_finding():
    # an entry accepted under the OLD id (old-id fingerprint and all)
    # still suppresses the finding now reported as CCR006
    f = run(ABBA_SRC, "CCR006")[0]
    old = Finding("TPL004", f.path, f.line, f.col, f.message, f.context)
    entries = bl.entries_from_findings([old])
    assert set(entries) == {old.fingerprint()} != {f.fingerprint()}
    d = bl.diff([f], entries)
    assert d.new == [] and d.suppressed == 1 and d.stale == []


# ------------------------------------------------------------------ TPL005
def test_tpl005_flags_print_and_time_in_decorated_jit():
    out = run("""
        import functools
        import time
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            print("tracing", n)
            return x * time.time()
    """, "TPL005")
    assert len(out) == 2


def test_tpl005_flags_call_form_jit():
    out = run("""
        import jax
        import numpy as np

        def sample(x):
            return x + np.random.rand()

        sample_fn = jax.jit(sample)
    """, "TPL005")
    assert len(out) == 1
    assert "np.random.rand" in out[0].message


def test_tpl005_flags_global_write_tracer_leak():
    out = run("""
        import jax

        @jax.jit
        def leak(x):
            global acc
            acc = x
            return x
    """, "TPL005")
    assert len(out) == 1
    assert "global" in out[0].message


def test_tpl005_nested_jitted_def_reports_once():
    out = run("""
        import jax

        @jax.jit
        def outer(x):
            @jax.jit
            def inner(y):
                print(y)
                return y
            return inner(x)
    """, "TPL005")
    assert len(out) == 1
    assert out[0].context == "outer.inner"


def test_tpl005_silent_on_debug_print_and_unjitted_code():
    assert run("""
        import jax

        @jax.jit
        def step(x):
            jax.debug.print("x={x}", x=x)
            return x + 1

        def host_side(x):
            print(x)  # not jitted: fine
            return x
    """, "TPL005") == []


# ------------------------------------------------------------------ TPL006
def test_tpl006_flags_unbounded_recv_and_bare_queue_get():
    out = run("""
        import time

        def pump(conn, q, timeout):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                msg = conn.recv()
                item = q.get()
    """, "TPL006")
    assert len(out) == 2


def test_tpl006_flags_unbounded_request_and_eventwait():
    out = run("""
        def spin(peer, ev, deadline):
            for _ in range(100):
                peer.request("poll")
                ev.wait()
    """, "TPL006")
    assert len(out) == 2


def test_tpl006_flags_long_fixed_sleep():
    out = run("""
        import time

        def spin(timeout):
            while True:
                time.sleep(5)
    """, "TPL006")
    assert len(out) == 1


def test_tpl006_silent_when_bounded_or_no_deadline():
    assert run("""
        import time

        def bounded(sock, peer, ev, q, timeout):
            sock.settimeout(timeout)
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                sock.recv(4096)
                peer.request("poll", timeout=1.0)
                ev.wait(timeout=0.5)
                q.get(timeout=0.1)
                time.sleep(0.01)

        def no_deadline(conn):
            while True:
                conn.recv()  # caller made no timeout promise
    """, "TPL006") == []


def test_tpl006_nested_helper_deadline_does_not_leak_to_outer():
    # a helper's local `timeout` is ITS deadline contract, not the outer
    # function's — the outer loop made no promise to any caller
    assert run("""
        def outer(q):
            def helper():
                timeout = 5.0
                return timeout
            while True:
                item = q.get()
    """, "TPL006") == []


def test_tpl006_nested_settimeout_does_not_vouch_for_outer():
    # only a settimeout in the OUTER body bounds the outer recv
    out = run("""
        def outer(sock, timeout):
            def configure(s):
                s.settimeout(1.0)
            deadline = 1.0
            while True:
                sock.recv(4096)
    """, "TPL006")
    assert len(out) == 1


def test_tpl006_silent_outside_loops():
    assert run("""
        def once(conn, timeout):
            return conn.recv()
    """, "TPL006") == []


# ------------------------------------- ERR001 conn arm (absorbed TPL007)
def test_err001_flags_bare_pass_conn_swallow():
    out = run("""
        def send(sock, data):
            try:
                sock.sendall(data)
            except ConnectionError:
                pass
    """, "ERR001")
    assert len(out) == 1


def test_err001_flags_tuple_catch_with_conn_member():
    out = run("""
        def send(sock, data):
            try:
                sock.sendall(data)
            except (BrokenPipeError, ValueError):
                pass
    """, "ERR001")
    assert len(out) == 1


def test_err001_silent_on_handled_or_cleanup_oserror():
    assert run("""
        def close(sock):
            try:
                sock.close()
            except OSError:
                pass

        def send(st, sock, data):
            try:
                sock.sendall(data)
            except ConnectionError:
                st.failover()
    """, "ERR001") == []


# -------------------------------------------------------------- engine bits
def test_inline_suppression_comment_accepts_retired_alias_id():
    # disable=TPL007 must keep suppressing after the TPL007 -> ERR001
    # migration: both sides of the comparison canonicalize
    src = """
        def send(sock, data):
            try:
                sock.sendall(data)
            except ConnectionError:  # tpulint: disable=TPL007
                pass
    """
    assert run(src, "ERR001") == []
    src_all = src.replace("disable=TPL007", "disable=all")
    assert run(src_all) == []


def test_fingerprint_is_line_independent():
    base = """
        def send(sock, data):
            try:
                sock.sendall(data)
            except ConnectionError:
                pass
    """
    shifted = "# a new header comment\n\n" + textwrap.dedent(base)
    f1 = lint_source(textwrap.dedent(base), path="m.py")
    f2 = lint_source(shifted, path="m.py")
    assert len(f1) == len(f2) == 1
    assert f1[0].line != f2[0].line
    assert f1[0].fingerprint() == f2[0].fingerprint()


def test_baseline_counts_cap_accepted_duplicates(tmp_path):
    def mk(n):
        return [Finding("TPL007", "m.py", 10 + i, 0, "swallowed ConnectionError", "f") for i in range(n)]

    path = str(tmp_path / "bl.json")
    bl.save(path, mk(2))
    entries = bl.load(path)
    ok = bl.diff(mk(2), entries)
    assert ok.new == [] and ok.suppressed == 2 and ok.stale == []
    worse = bl.diff(mk(3), entries)
    assert len(worse.new) == 1  # third duplicate is NEW, not grandfathered
    better = bl.diff(mk(0), entries)
    assert better.new == [] and len(better.stale) == 1
    # PARTIAL fix is also stale: unused budget must not become silent
    # headroom for a later reintroduction of the same finding
    partial = bl.diff(mk(1), entries)
    assert partial.new == [] and len(partial.stale) == 1
    assert partial.stale[0]["unused"] == 1


def test_syntax_error_reported_not_raised():
    out = lint_source("def broken(:\n", path="bad.py")
    assert len(out) == 1 and out[0].rule == "TPLERR"


# ---------------------------------------------------- TPL001 interprocedural
def test_tpl001_follows_call_into_module_helper():
    out = run("""
        import ray_tpu

        def _collect(refs):
            return ray_tpu.get(refs)

        @ray_tpu.remote
        class Pump:
            def step(self, refs):
                return _collect(refs)
    """, "TPL001")
    assert len(out) == 1
    assert out[0].context == "Pump.step" and "_collect" in out[0].message


def test_tpl001_follows_call_from_async_def():
    out = run("""
        import ray_tpu

        def _collect(refs):
            return ray_tpu.get(refs)

        async def handler(refs):
            return _collect(refs)
    """, "TPL001")
    assert len(out) == 1 and "event loop" in out[0].message


def test_tpl001_interprocedural_silent_cases():
    # bounded helper, async helper (flagged on its own body instead),
    # call from a plain function: all silent at the call site
    assert run("""
        import ray_tpu

        def _bounded(refs):
            return ray_tpu.get(refs, timeout=5.0)

        @ray_tpu.remote
        class Pump:
            def step(self, refs):
                return _bounded(refs)
    """, "TPL001") == []
    assert run("""
        import ray_tpu

        def _collect(refs):
            return ray_tpu.get(refs)

        def plain(refs):
            return _collect(refs)
    """, "TPL001") == []
    # async helper: exactly ONE finding (on the helper body), not two
    out = run("""
        import ray_tpu

        async def _acollect(refs):
            return ray_tpu.get(refs)

        @ray_tpu.remote
        class Pump:
            async def step(self, refs):
                return await _acollect(refs)
    """, "TPL001")
    assert len(out) == 1 and out[0].context == "_acollect"


def test_tpl001_helper_nested_def_does_not_leak():
    # a closure DEFINED in the helper doesn't run when the helper runs
    assert run("""
        import ray_tpu

        def _factory():
            def inner(refs):
                return ray_tpu.get(refs)
            return inner

        @ray_tpu.remote
        class Pump:
            def step(self, refs):
                return _factory()
    """, "TPL001") == []


# ---------------------------------------------------- TPL002 interprocedural
def test_tpl002_flags_dropped_helper_returned_ref():
    out = run("""
        def kick(f, x):
            return f.remote(x)

        def driver(f):
            kick(f, 1)
    """, "TPL002")
    assert len(out) == 1
    assert out[0].context == "driver" and "kick" in out[0].message


def test_tpl002_interprocedural_silent_when_bound_or_not_a_ref():
    assert run("""
        def kick(f, x):
            return f.remote(x)

        def driver(f):
            ref = kick(f, 1)
            return ref
    """, "TPL002") == []
    assert run("""
        def log(x):
            return str(x)

        def driver(f):
            log(1)
    """, "TPL002") == []


# ------------------------------------------------------ TPL005 partial forms
def test_tpl005_flags_variable_bound_partial_target():
    out = run("""
        import jax, time, functools

        def decode_step(params, cfg):
            time.time()
            return params

        step = functools.partial(decode_step, cfg=1)
        fn = jax.jit(step, donate_argnums=(1,))
    """, "TPL005")
    assert len(out) == 1 and out[0].context == "decode_step"


def test_tpl005_flags_plain_alias_and_inline_partial():
    out = run("""
        import jax, time
        from functools import partial

        def decode_step(params, cfg):
            time.time()
            return params

        fn = jax.jit(partial(decode_step, cfg=1))
    """, "TPL005")
    assert len(out) == 1
    out2 = run("""
        import jax, time

        def decode_step(params):
            time.time()
            return params

        alias = decode_step
        fn = jax.jit(alias)
    """, "TPL005")
    assert len(out2) == 1


def test_tpl005_silent_on_unjitted_partial():
    assert run("""
        import time, functools

        def decode_step(params, cfg):
            time.time()
            return params

        step = functools.partial(decode_step, cfg=1)
    """, "TPL005") == []


# =========================================================== jaxcheck (JXC)
# Synthetic entries traced through the real driver: every rule gets one
# fixture that MUST fire and one that MUST stay silent. Specs are built
# directly (not via the decorator) so the global registry stays untouched.
import os

import numpy as np

from ray_tpu.lint.jaxcheck.registry import EntrySpec
from ray_tpu.lint.jaxcheck.driver import run_jaxcheck

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(fn, shapes, **kw):
    return EntrySpec(
        name=f"fixture.{fn.__name__}", fn=fn, shapes=shapes,
        path=fn.__code__.co_filename, line=fn.__code__.co_firstlineno, **kw,
    )


def _findings(spec, rule_id):
    return [f for f in run_jaxcheck(root=_ROOT, entries=[spec]) if f.rule == rule_id]


def _f32(*shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _bf16(*shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


# ------------------------------------------------------------------ JXC001
def _jx_state_step(cache, delta):
    return cache + delta, delta.sum()


def test_jxc001_flags_undonated_state_and_silent_when_donated():
    shapes = {"b": lambda: ((_f32(512, 512), _f32(512, 512)), {})}
    out = _findings(_spec(_jx_state_step, shapes), "JXC001")
    # cache's shape reappears in the output; neither input donated -> one
    # flag (the second matching input has no unclaimed output left)
    assert len(out) == 1 and "'cache'" in out[0].message
    assert _findings(_spec(_jx_state_step, shapes, donate=("cache",)), "JXC001") == []


def test_jxc001_threshold_spares_small_buffers():
    shapes = {"b": lambda: ((_f32(8), _f32(8)), {})}
    assert _findings(_spec(_jx_state_step, shapes), "JXC001") == []  # default 1 MiB floor
    assert len(_findings(_spec(_jx_state_step, shapes, donate_bytes=0), "JXC001")) == 1


# ------------------------------------------------------------------ JXC002
def _np_identity(v):
    return np.asarray(v)


def _jx_with_callback(x):
    import jax

    return jax.pure_callback(_np_identity, jax.ShapeDtypeStruct(x.shape, x.dtype), x)


def _jx_pure(x):
    return x * 2.0


def test_jxc002_flags_host_callback_and_silent_on_pure():
    out = _findings(_spec(_jx_with_callback, {"b": lambda: ((_f32(64, 64),), {})}), "JXC002")
    assert len(out) == 1 and "pure_callback" in out[0].message
    assert _findings(_spec(_jx_pure, {"b": lambda: ((_f32(64, 64),), {})}), "JXC002") == []


def test_jxcerr_on_host_coercion_that_breaks_the_trace():
    def _jx_concretizes(x):
        return _np_identity(x).sum()

    spec = _spec(_jx_concretizes, {"b": lambda: ((_f32(8, 8),), {})})
    out = [f for f in run_jaxcheck(root=_ROOT, entries=[spec]) if f.rule == "JXCERR"]
    assert len(out) == 1 and "failed to trace" in out[0].message


# ------------------------------------------------------------------ JXC003
def _jx_upcast_dot(a, b):
    import jax.numpy as jnp

    return a.astype(jnp.float32) @ b.astype(jnp.float32)


def _jx_mxu_dot(a, b):
    import jax.numpy as jnp

    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def test_jxc003_flags_bf16_upcast_matmul_and_silent_on_preferred_accumulate():
    shapes = {"b": lambda: ((_bf16(512, 512), _bf16(512, 512)), {})}
    out = _findings(_spec(_jx_upcast_dot, shapes), "JXC003")
    assert out and "bf16" in out[0].message
    assert _findings(_spec(_jx_mxu_dot, shapes), "JXC003") == []


# ------------------------------------------------------------------ JXC004
def _jx_scaled(x, n):
    return x * n


def test_jxc004_flags_baked_python_scalar_and_silent_when_traced():
    baked = {"b": lambda: ((_f32(128, 128), 2), {})}  # n static-bound, like partial(fn, n=2)
    out = _findings(_spec(_jx_scaled, baked, varying={"n": (2, 3)}), "JXC004")
    assert len(out) == 1 and "'n'" in out[0].message and "recompile" in out[0].message
    # production passes n as a traced 0-d array -> nothing static to probe
    import jax
    import jax.numpy as jnp

    traced = {"b": lambda: ((_f32(128, 128), jax.ShapeDtypeStruct((), jnp.float32)), {})}
    assert _findings(_spec(_jx_scaled, traced, varying={"n": (2, 3)}), "JXC004") == []


def test_jxc004_silent_without_probe():
    assert _findings(_spec(_jx_scaled, {"b": lambda: ((_f32(8, 8), 2), {})}), "JXC004") == []


# ------------------------------------------------------------------ JXC005
def _mesh2():
    import jax
    import numpy as _np
    from jax.sharding import Mesh

    return Mesh(_np.asarray(jax.devices("cpu")[:2]), ("dp",))


def _jx_psum_dp(x):
    import jax

    return jax.lax.psum(x, "dp")


def _jx_collective_entry(x):
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(_jx_psum_dp, mesh=_mesh2(), in_specs=P("dp"), out_specs=P(), check_vma=False)(x)


def test_jxc005_flags_axis_outside_declared_mesh_and_silent_when_declared():
    shapes = {"b": lambda: ((_f32(8, 64),), {})}
    out = _findings(_spec(_jx_collective_entry, shapes, mesh_axes=("tp",)), "JXC005")
    assert len(out) == 1 and "'dp'" in out[0].message
    assert _findings(_spec(_jx_collective_entry, shapes, mesh_axes=("dp",)), "JXC005") == []


def _jx_branchy_psum(x):
    import jax

    def local(v):
        return jax.lax.cond(v.sum() > 0, lambda u: jax.lax.psum(u, "dp"), lambda u: u * 2.0, v)

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(local, mesh=_mesh2(), in_specs=P("dp"), out_specs=P("dp"), check_vma=False)(x)


def test_jxc005_flags_collective_diverging_across_cond_branches():
    out = _findings(_spec(_jx_branchy_psum, {"b": lambda: ((_f32(8, 64),), {})}, mesh_axes=("dp",)), "JXC005")
    assert len(out) == 1 and "branches" in out[0].message


# ------------------------------------------------------------------ JXC006
def test_jxc006_flags_tile_hostile_trailing_dims_and_silent_on_aligned():
    hostile = {"b": lambda: ((_f32(4096, 130),), {})}  # 130 -> 256 lanes: 49% waste
    out = _findings(_spec(_jx_pure, hostile), "JXC006")
    assert len(out) == 1 and "(8,128)" in out[0].message
    aligned = {"b": lambda: ((_f32(4096, 128),), {})}
    assert _findings(_spec(_jx_pure, aligned), "JXC006") == []
    small = {"b": lambda: ((_f32(8, 130),), {})}  # under the bytes floor
    assert _findings(_spec(_jx_pure, small), "JXC006") == []


# ------------------------------------------- jaxcheck on the real entries
def test_fused_step_sampling_lane_donation_regression():
    """The slots fused step donates its sampling lanes (keys/temps/top_k/
    top_p) and passes them through; reverting to the pre-fix donation set
    must resurface the JXC001 findings — while the tokens lane stays
    suppressed by its inline per-arg disable."""
    from dataclasses import replace

    from ray_tpu.lint.jaxcheck import import_entry_modules, registry

    import_entry_modules()
    spec = registry.get_entry("llm.fused_step")
    assert spec is not None
    assert _findings(spec, "JXC001") == []  # fixed state is clean
    old = replace(spec, donate=("cache", "keys"))
    msgs = [f.message for f in _findings(old, "JXC001")]
    assert len(msgs) == 3 and all(any(f"'{a}'" in m for m in msgs) for a in ("temps", "top_k", "top_p"))
    assert not any("'tokens'" in m for m in msgs)  # inline disable still scopes to its own line


def test_paged_fused_step_lane_donation_regression():
    from dataclasses import replace

    from ray_tpu.lint.jaxcheck import import_entry_modules, registry

    import_entry_modules()
    spec = registry.get_entry("llm.paged_fused_step")
    assert spec is not None
    assert _findings(spec, "JXC001") == []
    old = replace(spec, donate=("lengths", "keys"))
    assert len(_findings(old, "JXC001")) == 3


def test_int8_dequant_does_not_trip_jxc003():
    """The int8 KV dequant (int8->f32 convert feeding the attention
    einsums) must never register as JXC003's bf16->f32-before-dot trap:
    the conversion happens at the compute dtype attention already uses
    and stays off the flops-dominant dots. Traced over every quantized
    hot-path entry (fused decode, spec verify, disagg scatter-in) for
    both layouts — a refactor that routes the dequant through a bf16
    intermediate feeding the unembed/projection matmuls would fire
    here."""
    from ray_tpu.lint.jaxcheck import import_entry_modules, registry

    import_entry_modules()
    for name in (
        "llm.fused_step_int8", "llm.paged_fused_step_int8",
        "llm.spec_verify_int8", "llm.spec_verify_paged_int8",
        "llm.disagg_extract_slots_int8", "llm.disagg_extract_paged_int8",
        "llm.disagg_scatter_slots_int8", "llm.disagg_scatter_paged_int8",
    ):
        spec = registry.get_entry(name)
        assert spec is not None, name
        assert _findings(spec, "JXC003") == [], name
        assert _findings(spec, "JXCERR") == [], name  # all int8 buckets trace


def test_int8_fused_step_donation_audited():
    """The int8 cache pytree (values + scale lanes) donates wholesale:
    dropping the donation must resurface JXC001 on the quantized entry."""
    from dataclasses import replace

    from ray_tpu.lint.jaxcheck import import_entry_modules, registry

    import_entry_modules()
    spec = registry.get_entry("llm.fused_step_int8")
    assert spec is not None
    assert _findings(spec, "JXC001") == []
    old = replace(spec, donate=("keys", "temps", "top_k", "top_p"))
    msgs = [f.message for f in _findings(old, "JXC001")]
    assert any("'cache" in m for m in msgs), msgs


def test_tpl001_bounded_helper_from_async_still_flags():
    # mirrors the lexical gate exactly: a timeout bound clears the
    # actor-deadlock case but a bounded get still parks an event loop
    out = run("""
        import ray_tpu

        def _bounded(refs):
            return ray_tpu.get(refs, timeout=30.0)

        async def handler(refs):
            return _bounded(refs)
    """, "TPL001")
    assert len(out) == 1 and "event loop" in out[0].message


def test_jxcerr_on_rule_crash_instead_of_lint_crash():
    # a JXC004 probe value whose re-trace raises must degrade to a
    # finding, not take down the whole run
    def _jx_div(x, n):
        return x.reshape(x.shape[0] // n, -1)

    spec = _spec(_jx_div, {"b": lambda: ((_f32(8, 8), 2), {})}, varying={"n": (2, 0)})
    fs = run_jaxcheck(root=_ROOT, entries=[spec])
    assert any(f.rule == "JXCERR" and "JXC004" in f.message for f in fs), fs


# ------------------------------------------------------------------ CCR001
def test_ccr001_flags_sleep_under_lock():
    out = run("""
        import time

        class Pump:
            def tick(self):
                with self._lock:
                    time.sleep(0.5)
    """, "CCR001")
    assert len(out) == 1
    assert "_lock" in out[0].message and out[0].context == "Pump.tick"


def test_ccr001_flags_unbounded_queue_get_under_lock():
    out = run("""
        class Pump:
            def tick(self):
                with self._lock:
                    item = self._q.get()
    """, "CCR001")
    assert len(out) == 1


def test_ccr001_flags_index_rpc_under_lock_transitively():
    # the blocking call hides one hop away: tick -> _refresh -> index RPC
    out = run("""
        class Client:
            def _refresh(self):
                return self._index.lookup(b"k")

            def tick(self):
                with self._lock:
                    return self._refresh()
    """, "CCR001")
    assert len(out) == 1
    assert "via" in out[0].message


def test_ccr001_holds_lock_annotation_seeds_held_set():
    out = run("""
        import time

        class Pump:
            def _drain_locked(self):  # holds-lock: _lock
                time.sleep(0.1)
    """, "CCR001")
    assert len(out) == 1


def test_ccr001_silent_outside_lock_and_on_condvar_wait():
    # sleep after release, and cv.wait() ON the held lock (the one
    # blocking-while-holding shape that is the POINT of a condvar)
    assert run("""
        import time

        class Pump:
            def tick(self):
                with self._lock:
                    n = 1
                time.sleep(0.5)

            def park(self):
                with self._cv:
                    self._cv.wait()
    """, "CCR001") == []


# ------------------------------------------------------------------ CCR002
def test_ccr002_flags_device_sync_in_hot_root():
    out = run("""
        import numpy as np

        class Engine:
            def step(self):
                return np.asarray(self._logits)
    """, "CCR002")
    assert len(out) == 1
    assert "step" in out[0].message


def test_ccr002_flags_sync_reachable_from_stage_helper():
    out = run("""
        class Engine:
            def _readback(self):
                return float(self._host[0])

            def _stage_sample(self):
                return self._readback()
    """, "CCR002")
    assert len(out) == 1
    assert "_stage_sample" in out[0].message


def test_ccr002_silent_off_hot_path_and_on_host_dict_float():
    # float(d["key"]) is a host dict lookup, not a device readback; and
    # a cold-path method may sync freely
    assert run("""
        import numpy as np

        class Engine:
            def debug_dump(self):
                return np.asarray(self._logits)

            def step(self):
                return float(self._cfg["temp"])
    """, "CCR002") == []


# ------------------------------------------------------------------ CCR003
GUARDED_SRC = """
    import threading

    class Index:
        def __init__(self):
            self._lock = threading.Lock()
            self._entries = {{}}  # guarded-by: _lock

        def put(self, k, v):
            {body}
"""


def test_ccr003_flags_unguarded_write_to_declared_field():
    out = run(GUARDED_SRC.format(body="self._entries[k] = v"), "CCR003")
    assert len(out) == 1
    assert "_entries" in out[0].message and "guarded-by" in out[0].message


def test_ccr003_flags_unguarded_mutator_call():
    out = run(GUARDED_SRC.format(body="self._entries.pop(k, None)"), "CCR003")
    assert len(out) == 1


def test_ccr003_silent_under_lock_in_init_and_with_holds_lock():
    assert run("""
        import threading

        class Index:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock

            def put(self, k, v):
                with self._lock:
                    self._entries[k] = v

            def _put_locked(self, k, v):  # holds-lock: _lock
                self._entries[k] = v
    """, "CCR003") == []


# ------------------------------------------------------------------ CCR004
def test_ccr004_flags_manual_acquire_without_try_finally():
    out = run("""
        class Agent:
            def reap(self):
                self._lock.acquire()
                self._work()
                self._lock.release()
    """, "CCR004")
    assert len(out) == 1


def test_ccr004_silent_on_try_finally_and_hand_over_hand():
    # classic try/finally, plus the chained-locking shape where acquire
    # is the LAST statement of a with-body and the try/finally is the
    # with's next sibling (gcs-style hand-over-hand traversal)
    assert run("""
        class Agent:
            def reap(self):
                self._lock.acquire()
                try:
                    self._work()
                finally:
                    self._lock.release()

            def walk(self, nxt):
                with self._lock:
                    nxt.acquire()
                try:
                    self._visit(nxt)
                finally:
                    nxt.release()
    """, "CCR004") == []


# ------------------------------------------------------------------ CCR005
def test_ccr005_flags_thread_mutating_captured_state_unguarded():
    out = run("""
        import threading

        def pump(items):
            done = []

            def worker():
                done.append(len(items))

            t = threading.Thread(target=worker)
            t.start()
            return done
    """, "CCR005")
    assert len(out) == 1
    assert "done" in out[0].message


def test_ccr005_silent_when_guarded_or_bound_method_target():
    assert run("""
        import threading

        def pump(items, lock):
            done = []

            def worker():
                with lock:
                    done.append(len(items))

            threading.Thread(target=worker).start()

        class Pool:
            def spawn(self):
                threading.Thread(target=self._run).start()
    """, "CCR005") == []


# --------------------------- fix-regression fixtures (mutation-style) ---
# These replicate the PRE-fix shapes of the two serving-plane true
# positives this analyzer caught, so re-introducing either hazard makes
# CCR001 fire again even if the tree-wide self-check baseline drifts.

def test_ccr001_refires_on_stats_estimate_under_admission_lock():
    # pre-fix AdmissionController.stats(): queue-wait estimate computed
    # UNDER the admission lock; the estimate falls through to
    # engine.host_load(), which waits on the engine lock
    pre_fix = run("""
        import threading

        class AdmissionController:
            def _estimate(self):
                return self.engine.host_load()

            def stats(self):
                with self._lock:
                    return {"queue_wait_est_s": self._estimate()}
    """, "CCR001")
    assert len(pre_fix) == 1 and "via" in pre_fix[0].message

    # the shipped fix: hoist the estimate above the lock
    assert run("""
        import threading

        class AdmissionController:
            def _estimate(self):
                return self.engine.host_load()

            def stats(self):
                est = self._estimate()
                with self._lock:
                    return {"queue_wait_est_s": est}
    """, "CCR001") == []


def test_ccr001_refires_on_plane_publish_under_engine_lock():
    # pre-fix LLMEngine._plane_publish: serialization + object-plane put
    # + a 10s-timeout index register RPC, all inside the engine lock
    pre_fix = run("""
        class LLMEngine:
            def _plane_publish(self, ks):
                self._kv_plane.publish(ks)

            def _stage_admission(self):
                with self._lock:
                    self._plane_publish([1])
    """, "CCR001")
    assert len(pre_fix) == 1

    # the shipped fix: enqueue under the lock, publish at step tail
    assert run("""
        class LLMEngine:
            def _stage_admission(self):
                with self._lock:
                    self._plane_offers.append([1])

            def _flush_plane_offers(self):
                offers, self._plane_offers = self._plane_offers, []
                for ks in offers:
                    self._kv_plane.publish(ks)
    """, "CCR001") == []


# ------------------------------------------------ baseline "why" policy
def test_update_baseline_preserves_prior_why():
    f = Finding("CCR001", "ray_tpu/x.py", 3, 4, "sleep [sleep] while holding C._lock", "C.m")
    prior = bl.entries_from_findings([f])
    prior[f.fingerprint()]["why"] = "accepted debt: tracked in ROADMAP"
    fresh = bl.entries_from_findings([f], prior=prior)
    assert fresh[f.fingerprint()]["why"] == "accepted debt: tracked in ROADMAP"


def test_update_baseline_carries_why_across_rule_alias():
    # entry hand-annotated under TPL004, regenerated after the rename
    new = Finding("CCR006", "ray_tpu/x.py", 3, 4, "lock-order inversion", "")
    old = Finding("TPL004", new.path, new.line, new.col, new.message, new.context)
    prior = bl.entries_from_findings([old])
    prior[old.fingerprint()]["why"] = "two-phase shutdown, documented"
    fresh = bl.entries_from_findings([new], prior=prior)
    assert fresh[new.fingerprint()]["why"] == "two-phase shutdown, documented"


# ------------------------------------------- ERR catalog (fault discipline)
def run_serving(src: str, rule_id: str | None = None):
    """ERR002-005 and ERR001's broad arm only fire on serving paths —
    fixtures opt in via the path."""
    out = lint_source(textwrap.dedent(src), path="ray_tpu/serve/fixture.py")
    assert not any(f.rule == "TPLERR" for f in out), out
    if rule_id is None:
        return out
    return [f for f in out if f.rule == rule_id]


def test_err001_broad_arm_flags_serving_swallow():
    out = run_serving("""
        def push(state, item):
            try:
                state.deliver(item)
            except Exception:
                pass
    """, "ERR001")
    assert len(out) == 1
    assert out[0].context == "push"


def test_err001_broad_arm_needs_serving_path():
    # same code outside serve/llm/direct stays TPL007-scoped: broad
    # swallows fire only where the typed-error contract applies
    assert run("""
        def push(state, item):
            try:
                state.deliver(item)
            except Exception:
                pass
    """, "ERR001") == []


def test_err001_silent_when_handler_observes():
    assert run_serving("""
        def push(self, state, item):
            try:
                state.deliver(item)
            except Exception:
                self.counts["deliver_errors"] += 1

        def flag(rec, state, item):
            try:
                state.deliver(item)
            except Exception:
                rec["error"] = True

        def rewrap(state, item):
            try:
                state.deliver(item)
            except Exception as e:
                raise RuntimeError("x") from e
    """, "ERR001") == []


def test_err001_silent_in_teardown_scope_and_module_guard():
    assert run_serving("""
        try:
            import fastpath
        except Exception:
            fastpath = None

        class Pool:
            def shutdown(self):
                try:
                    self.conn.close()
                except Exception:
                    pass

            def __del__(self):
                try:
                    self.conn.close()
                except Exception:
                    pass
    """, "ERR001") == []


def test_err001_silent_on_specific_typed_catch_degradation():
    # catching a SPECIFIC taxonomy type and degrading is the
    # bounded-degradation idiom (poll loop break), not a swallow
    assert run_serving("""
        def drain(q):
            while q:
                try:
                    q.pop_ready()
                except GetTimeoutError:
                    break
    """, "ERR001") == []


def test_err002_flags_generic_raise_from_serving_root():
    out = run_serving("""
        def step(engine):
            raise RuntimeError("stepper wedged")
    """, "ERR002")
    assert len(out) == 1
    assert "step()" in out[0].message


def test_err002_follows_callgraph_two_levels():
    out = run_serving("""
        class Server:
            def generate(self, prompt):
                return self._admit(prompt)

            def _admit(self, prompt):
                if not prompt:
                    raise ValueError("empty prompt")
    """, "ERR002")
    assert len(out) == 1
    assert "via _admit" in out[0].message
    assert out[0].context == "Server._admit"


def test_err002_silent_on_typed_raise_and_non_root():
    assert run_serving("""
        def step(engine):
            raise MigrationError("typed is fine")

        def helper_not_a_root(engine):
            raise RuntimeError("unreachable from any root at depth 0")
    """, "ERR002") == []


def test_err003_flags_raise_in_except_without_cause():
    out = run_serving("""
        def fetch(plane, key):
            try:
                return plane.get(key)
            except KeyError:
                raise LookupFailed(f"no {key}")
    """, "ERR003")
    assert len(out) == 1
    assert "from e" in out[0].message


def test_err003_silent_when_cause_threaded():
    assert run_serving("""
        def a(plane, key):
            try:
                return plane.get(key)
            except KeyError as e:
                raise LookupFailed(f"no {key}") from e

        def b(plane, key):
            try:
                return plane.get(key)
            except KeyError as e:
                raise TaskError(cause=e)

        def c(plane, key):
            try:
                return plane.get(key)
            except KeyError:
                raise  # bare re-raise keeps the original
    """, "ERR003") == []


def test_err004_flags_unbounded_retry_loop():
    out = run_serving("""
        def pump(plane, item):
            while True:
                try:
                    return plane.publish(item)
                except Exception:
                    time.sleep(0.1)
    """, "ERR004")
    assert len(out) == 1


def test_err004_silent_when_loop_is_bounded():
    assert run_serving("""
        def pump_deadline(plane, item, deadline):
            while True:
                if time.monotonic() > deadline:
                    raise PublishFailed("out of time")
                try:
                    return plane.publish(item)
                except Exception:
                    time.sleep(0.1)

        def pump_budget(plane, item, budget):
            while True:
                try:
                    return plane.publish(item)
                except Exception:
                    if not budget.try_spend():
                        raise
                    time.sleep(0.1)
    """, "ERR004") == []


def test_err005_flags_unbounded_gets_on_serving_root():
    out = run_serving("""
        import ray_tpu

        def step(engine, ref, plane, conn):
            a = ray_tpu.get(ref)
            b = plane.get_owned_view(ref.id)
            c = conn.request("get", key="k")
            return a, b, c
    """, "ERR005")
    assert len(out) == 3


def test_err005_silent_when_bounded():
    assert run_serving("""
        import ray_tpu

        def step(engine, ref, plane, conn):
            a = ray_tpu.get(ref, timeout=5.0)
            b = plane.get_owned_view(ref.id, timeout=10.0)
            c = conn.request("get", key="k", timeout=10.0)
            return a, b, c
    """, "ERR005") == []


def test_err005_interprocedural_forwarded_none_timeout():
    # helper defaults timeout_s=None and forwards it into the transport:
    # a caller omitting the param inherits the unbounded wait
    out = run_serving("""
        def fetch_block(plane, key, timeout_s=None):
            return plane.fetch(key, timeout_s=timeout_s)

        def caller(plane, key):
            return fetch_block(plane, key)

        def bounded_caller(plane, key):
            return fetch_block(plane, key, timeout_s=30.0)
    """, "ERR005")
    assert len(out) == 1
    assert "fetch_block" in out[0].message and out[0].context == "caller"


# ------------------------------------- TPL007 -> ERR001 alias contract
def test_tpl007_alias_baseline_entry_suppresses_err001_finding():
    # an entry accepted under the OLD id (old-id fingerprint and all)
    # still suppresses the finding now reported as ERR001
    f = run("""
        def send(sock, data):
            try:
                sock.sendall(data)
            except ConnectionError:
                pass
    """, "ERR001")[0]
    old = Finding("TPL007", f.path, f.line, f.col, f.message, f.context)
    entries = bl.entries_from_findings([old])
    assert set(entries) == {old.fingerprint()} != {f.fingerprint()}
    d = bl.diff([f], entries)
    assert d.new == [] and d.suppressed == 1 and d.stale == []


def test_update_baseline_carries_why_across_tpl007_migration():
    # a hand-annotated TPL007 entry regenerated after the absorption
    # keeps its why VERBATIM under the new ERR001 fingerprint
    new = Finding("ERR001", "ray_tpu/x.py", 3, 4, "swallowed ConnectionError", "send")
    old = Finding("TPL007", new.path, new.line, new.col, new.message, new.context)
    prior = bl.entries_from_findings([old])
    why = "deliberate: peer death observed by the heartbeat plane one layer up"
    prior[old.fingerprint()]["why"] = why
    fresh = bl.entries_from_findings([new], prior=prior)
    assert fresh[new.fingerprint()]["why"] == why

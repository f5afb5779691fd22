"""Channel-compiled DAG execution: the head leaves the steady-state loop.

Reference parity: python/ray/dag/compiled_dag_node.py:1 (compile a bound
DAG once, execute many times over persistent channels) redesigned on the
shm-ring + unix-doorbell channels of ray_tpu.experimental.channels
instead of plasma mutable objects. After ``compile_channel_dag``:

    driver --chan--> actor A --chan--> actor B --chan--> driver

every ``execute`` writes the input into a pinned ring and every hop is a
~30us shm write + doorbell — no task submission, no scheduler, no head
involvement.

Topology rules (v1, same-host):
  * every compute node is a method bound on an EXISTING actor handle
    (ActorMethodNode) or on a ClassNode-created actor;
  * every node consumes at least one InputNode or upstream node (the
    channel clock: a node with no in-edge would free-run);
  * all actors live on this host (abstract unix sockets + shm).
"""

from __future__ import annotations

import threading
import uuid

from ray_tpu.core.object_store import _session_tag
from ray_tpu.dag import ActorMethodNode, ClassMethodNode, ClassNode, DAGNode, InputNode
from ray_tpu.experimental.channels import (
    STOP,
    ChannelClosedError,
    ChannelError,
    ChannelFullError,
    ChannelReader,
    ChannelWriter,
    _Stop,
    _WrappedError,
)


class CompiledDagRef:
    """Future for one execute(); results are delivered in submission
    order (the rings are FIFO), so get() drains up to this ref's seq.
    The outcome is cached on the ref: repeated get() returns (or
    re-raises) the same result; only a timeout leaves it pending."""

    def __init__(self, dag: "ChannelCompiledDAG", seq: int):
        self._dag = dag
        self._seq = seq
        self._state = "pending"
        self._value = None
        self._exc: BaseException | None = None

    def get(self, timeout: float | None = None):
        if self._state == "pending":
            try:
                self._value = self._dag._read_result(self._seq, timeout)
            except TimeoutError:
                raise  # row not consumed; retry is safe
            except BaseException as e:  # noqa: BLE001
                self._state = "err"
                self._exc = e
                raise
            self._state = "ok"
        if self._state == "err":
            raise self._exc
        return self._value


class ChannelCompiledDAG:
    def __init__(self, leaves, nslots: int = 8, buffer_size_bytes: int = 256 << 10):
        self._leaves = leaves if isinstance(leaves, list) else [leaves]
        self.nslots = nslots
        self.slot_size = buffer_size_bytes
        self._dag_id = uuid.uuid4().hex[:8]
        self._broken: BaseException | None = None
        self._torn_down = False
        self._send_seq = 0
        self._read_seq = 0
        self._done: dict[int, list] = {}
        self._pending: dict = {}  # channel name -> deque of undelivered values
        self._lock = threading.Lock()  # counters + _done; NEVER held across recv
        self._drain_lock = threading.Lock()  # serializes reader draining

        schedule = self._topo_schedule()
        self._plan_and_connect(schedule)

    # ------------------------------------------------------------------
    # compile
    # ------------------------------------------------------------------
    def _topo_schedule(self) -> list[DAGNode]:
        schedule: list[DAGNode] = []
        seen: dict[int, int] = {}

        def deps_of(node):
            deps = list(node._bound_args) + list(node._bound_kwargs.values())
            if isinstance(node, ClassMethodNode):
                deps.append(node._class_node)
            return deps

        def visit(node):
            if not isinstance(node, DAGNode):
                return
            st = seen.get(id(node))
            if st == 1:
                return
            if st == 0:
                raise ValueError("cycle detected in DAG")
            seen[id(node)] = 0
            for d in deps_of(node):
                visit(d)
            seen[id(node)] = 1
            schedule.append(node)

        for lf in self._leaves:
            visit(lf)
        return schedule

    def _node_handle(self, node, boot_memo):
        if isinstance(node, ActorMethodNode):
            return node._handle
        if isinstance(node, ClassMethodNode):
            return node._class_node._execute_memo(boot_memo)
        raise ValueError(
            f"channel-compiled DAGs support actor-method nodes only, got {type(node).__name__} "
            "(plain @remote functions have no persistent process to pin a channel to)"
        )

    def _plan_and_connect(self, schedule):
        boot_memo: dict = {"__inputs__": ()}
        compute = []
        for node in schedule:
            if isinstance(node, InputNode):
                continue
            if isinstance(node, ClassNode):
                node._execute_memo(boot_memo)  # instantiate compile-time actors
                continue
            compute.append(node)
        if not compute:
            raise ValueError("empty DAG")

        for lf in self._leaves:
            if isinstance(lf, InputNode):
                raise ValueError("an InputNode cannot be a DAG output")

        tag = _session_tag()
        chan_n = 0
        # (producer key, consumer id) -> channel name; producer key is
        # id(node) or ('input', index). A node feeding the driver through
        # several leaf positions shares ONE channel; the driver fans the
        # single delivered value out to every position.
        chans: dict[tuple, str] = {}

        def chan_for(producer_key, consumer_id) -> str:
            nonlocal chan_n
            key = (producer_key, consumer_id)
            if key not in chans:
                chans[key] = f"rt{tag}_ch{self._dag_id}_{chan_n}"
                chan_n += 1
            return chans[key]

        # per-node: ordered in-channel list + arg template
        node_in: dict[int, list[str]] = {}
        node_tmpl: dict[int, list] = {}
        node_out: dict[int, list[str]] = {id(n): [] for n in compute}
        compute_ids = {id(n) for n in compute}
        self._input_chans: dict[str, int] = {}  # name -> input index
        for node in compute:
            ins: list[str] = []
            tmpl: list = []
            if node._bound_kwargs:
                raise ValueError("channel-compiled DAGs do not support kwargs binds (v1)")
            for a in node._bound_args:
                if isinstance(a, InputNode):
                    name = chan_for(("input", a.index), id(node))
                    self._input_chans.setdefault(name, a.index)
                    if name not in ins:
                        ins.append(name)
                    tmpl.append(("edge", ins.index(name)))
                elif isinstance(a, DAGNode):
                    if id(a) not in compute_ids:
                        raise ValueError(f"unsupported upstream node {type(a).__name__}")
                    name = chan_for(id(a), id(node))
                    node_out[id(a)].append(name)
                    if name not in ins:
                        ins.append(name)
                    tmpl.append(("edge", ins.index(name)))
                else:
                    tmpl.append(("const", a))
            if not ins:
                raise ValueError(
                    f"node {node._method!r} consumes no InputNode/upstream output; "
                    "a channel-compiled node needs an in-edge to clock it"
                )
            node_in[id(node)] = ins
            node_tmpl[id(node)] = tmpl

        # leaf output channels -> driver (per-leaf names may repeat when
        # the same node is listed as several outputs)
        self._output_names: list[str] = []
        for lf in self._leaves:
            name = chan_for(id(lf), "driver")
            node_out[id(lf)].append(name)
            self._output_names.append(name)
        for nid, outs in node_out.items():
            node_out[nid] = list(dict.fromkeys(outs))

        # group steps per actor (topo order preserved within each plan)
        self._handles = []
        by_actor: dict = {}
        for node in compute:
            h = self._node_handle(node, boot_memo)
            aid = h._actor_id
            if aid not in by_actor:
                by_actor[aid] = (h, [])
                self._handles.append(h)
            by_actor[aid][1].append(
                {
                    "method": node._method,
                    "in": node_in[id(node)],
                    "out": node_out[id(node)],
                    "arg_template": node_tmpl[id(node)],
                }
            )

        # push setup to every actor (parallel: each blocks until its
        # channels connect), then bring up the driver ends: writers dial
        # root actors' listeners; readers accept the leaves' writers
        setup_refs = [
            h.__rt_chan_setup__.remote(
                {"nslots": self.nslots, "slot_size": self.slot_size, "steps": steps}
            )
            for h, steps in by_actor.values()
        ]
        self._writers: dict[str, ChannelWriter] = {}
        self._readers: dict[str, ChannelReader] = {}
        try:
            for name in self._input_chans:
                self._writers[name] = ChannelWriter(name, self.nslots, self.slot_size)
            for name in dict.fromkeys(self._output_names):
                self._readers[name] = ChannelReader(name, self.nslots, self.slot_size)
            import ray_tpu

            ray_tpu.get(setup_refs, timeout=120.0)
        except BaseException:
            self._teardown_endpoints()
            raise

    # ------------------------------------------------------------------
    # execute
    # ------------------------------------------------------------------
    def execute(self, *input_args) -> CompiledDagRef:
        if self._torn_down:
            raise ChannelError("compiled DAG was torn down")
        if self._broken is not None:
            raise ChannelError(f"compiled DAG is broken: {self._broken!r}")
        with self._lock:
            # in-flight cap = output ring capacity: past it the leaves'
            # writers would stall the whole pipeline and execute() would
            # block forever waiting for a credit only get() can free
            if self._send_seq - self._read_seq >= self.nslots:
                raise ChannelError(
                    f"{self.nslots} executions already in flight; get() results "
                    "first (or compile with a larger nslots)"
                )
            # validate EVERYTHING before any send — arity, picklability,
            # slot fit — because a partial row in the input rings would
            # desync every later execution; an unexpected mid-row failure
            # after that still marks the DAG broken
            needed = max(self._input_chans.values(), default=-1) + 1
            if len(input_args) < needed:
                raise ValueError(f"compiled DAG takes {needed} inputs, got {len(input_args)}")
            import pickle as _pickle

            from ray_tpu.experimental.channels import _HDR

            payloads = {}
            for name, idx in self._input_chans.items():
                data = _pickle.dumps(input_args[idx], protocol=5)
                w = self._writers[name]
                if len(data) > w.slot_size - _HDR.size:
                    raise ChannelFullError(
                        f"input {idx} is {len(data)} bytes, exceeds slot size {w.slot_size}; "
                        "raise experimental_compile(buffer_size_bytes=...)"
                    )
                payloads[name] = data
            try:
                for name, data in payloads.items():
                    self._writers[name].send_bytes(data)
            except BaseException as e:  # noqa: BLE001 - mid-row failure poisons the rings
                self._broken = e if isinstance(e, ChannelError) else ChannelError(f"mid-row send failed: {e!r}")
                raise
            seq = self._send_seq
            self._send_seq += 1
        return CompiledDagRef(self, seq)

    def _read_result(self, seq: int, timeout: float | None):
        from collections import deque

        with self._drain_lock:
            with self._lock:
                if seq in self._done:
                    return self._unwrap(self._done.pop(seq))
                if self._broken is not None:
                    raise ChannelError(f"compiled DAG is broken: {self._broken!r}")
                if not self._pending:
                    self._pending = {n: deque() for n in self._readers}
            while True:
                with self._lock:
                    if self._read_seq > seq:
                        return self._unwrap(self._done.pop(seq))
                    row_seq = self._read_seq
                # fill each channel's buffer for this row BEFORE popping
                # any — a timeout mid-row leaves buffered values buffered,
                # so a retried get() resumes without desyncing the rings.
                # self._lock is NOT held across the blocking recv: execute()
                # and teardown() stay responsive while a get() waits.
                for name, r in self._readers.items():
                    if not self._pending[name]:
                        if timeout is not None:
                            r.sock.settimeout(timeout)
                        try:
                            self._pending[name].append(r.recv())
                        except ChannelClosedError as e:
                            with self._lock:
                                self._broken = e
                            raise
                        finally:
                            if timeout is not None and r.sock is not None:
                                r.sock.settimeout(None)
                vals = {name: self._pending[name].popleft() for name in self._readers}
                row = [vals[n] for n in self._output_names]
                with self._lock:
                    self._done[row_seq] = row
                    self._read_seq += 1

    def _unwrap(self, vals: list):
        for v in vals:
            if isinstance(v, _WrappedError):
                raise v.exc
            if isinstance(v, _Stop):
                raise ChannelError("pipeline was stopped")
        return vals if len(vals) > 1 else vals[0]

    # ------------------------------------------------------------------
    # teardown
    # ------------------------------------------------------------------
    def teardown(self, *, kill_actors: bool = False, timeout: float = 30.0):
        """Drain gracefully: STOP flows through every stage in order, the
        actor loops exit, endpoints close. Safe after failures too."""
        if self._torn_down:
            return
        self._torn_down = True
        # graceful drain only if no get() is wedged in a blocking recv:
        # otherwise skip straight to the force path, whose endpoint close
        # wakes the stuck reader with ChannelClosedError
        drained = self._drain_lock.acquire(timeout=5.0)
        try:
            if drained and self._broken is None:
                try:
                    for name in self._input_chans:
                        self._writers[name].send(STOP)
                    for r in self._readers.values():
                        if r.sock is None:
                            continue
                        r.sock.settimeout(timeout)
                        try:
                            while not isinstance(r.recv(), _Stop):
                                pass
                        except (ChannelError, TimeoutError):
                            pass
                except ChannelError:
                    pass
        finally:
            if drained:
                self._drain_lock.release()
        # force-stop any loop that did not drain (dead peers)
        import ray_tpu

        refs = []
        for h in self._handles:
            try:
                refs.append(h.__rt_chan_teardown__.remote())
            except Exception:
                pass
        for ref in refs:
            try:
                ray_tpu.get(ref, timeout=10.0)
            except Exception:
                pass
        self._teardown_endpoints()
        if kill_actors:
            for h in self._handles:
                try:
                    ray_tpu.kill(h)
                except Exception:
                    pass

    def _teardown_endpoints(self):
        for w in self._writers.values():
            try:
                w.close()
            except Exception:
                pass
        for r in self._readers.values():
            try:
                r.close()
            except Exception:
                pass


def compile_channel_dag(leaf_or_leaves, *, nslots: int = 8, buffer_size_bytes: int = 256 << 10) -> ChannelCompiledDAG:
    return ChannelCompiledDAG(leaf_or_leaves, nslots=nslots, buffer_size_bytes=buffer_size_bytes)

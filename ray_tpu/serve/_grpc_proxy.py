"""gRPC ingress: route gRPC calls to application deployments.

Reference parity: the Serve gRPC proxy (serve/_private/proxy.py gRPCProxy
+ grpc_util.py) — the reference compiles user protos; here a
GenericRpcHandler serves one proto-less generic method so no protoc step
is needed (requests/responses are JSON bytes over standard gRPC/HTTP-2
framing):

    /ray_tpu.serve.Generic/Call
        request  b'{"application": ..., "method": ..., "args": [...],
                    "kwargs": {...}}'
        response b'{"result": ...}' | b'{"error": ...}'  (+ gRPC status)

Client side, any gRPC stack works; `grpc_call()` is the convenience
wrapper. Streaming deployments use /ray_tpu.serve.Generic/CallStreaming
(server-streaming: one JSON message per yielded item).
"""

from __future__ import annotations

import json
import threading

import ray_tpu
from ray_tpu.serve._proxy import ROUTES_TIMEOUT_S
from ray_tpu.serve.handle import DeploymentHandle

_METHOD_UNARY = "/ray_tpu.serve.Generic/Call"
_METHOD_STREAM = "/ray_tpu.serve.Generic/CallStreaming"


class GrpcProxy:
    def __init__(self, controller, host: str = "127.0.0.1", port: int = 0):
        import grpc

        self._controller = controller
        self._handles: dict[str, DeploymentHandle] = {}
        self._lock = threading.Lock()
        proxy = self

        class Handler(grpc.GenericRpcHandler):
            def service(self, handler_call_details):
                if handler_call_details.method == _METHOD_UNARY:
                    return grpc.unary_unary_rpc_method_handler(
                        proxy._call, request_deserializer=None, response_serializer=None
                    )
                if handler_call_details.method == _METHOD_STREAM:
                    return grpc.unary_stream_rpc_method_handler(
                        proxy._call_streaming, request_deserializer=None, response_serializer=None
                    )
                return None

        from concurrent.futures import ThreadPoolExecutor

        self._server = grpc.server(ThreadPoolExecutor(max_workers=32))
        self._server.add_generic_rpc_handlers((Handler(),))
        self.port = self._server.add_insecure_port(f"{host}:{port}")
        self.host = host
        self._server.start()

    def _handle(self, app: str) -> DeploymentHandle:
        with self._lock:
            h = self._handles.get(app)
        if h is not None:
            return h
        apps = ray_tpu.get(self._controller.list_applications.remote(), timeout=ROUTES_TIMEOUT_S)
        if app not in apps:
            raise KeyError(f"no application {app!r} (have {sorted(apps)})")
        h = DeploymentHandle(self._controller, app, apps[app]["ingress"])
        with self._lock:
            self._handles[app] = h
        return h

    @staticmethod
    def _parse(request: bytes):
        body = json.loads(request or b"{}")
        return body["application"], body.get("method"), body.get("args") or [], body.get("kwargs") or {}

    def _drop_handle(self, app: str):
        # redeploys can change an app's ingress: invalidate on error like
        # the HTTP proxy's route refresh, so the next call rebuilds
        with self._lock:
            self._handles.pop(app, None)

    @staticmethod
    def _timeout(context) -> float:
        remaining = context.time_remaining()  # None without a client deadline
        return min(remaining, 3600.0) if remaining else 60.0

    def _call(self, request: bytes, context) -> bytes:
        import grpc

        app = None
        try:
            app, method, args, kwargs = self._parse(request)
            h = self._handle(app)
            if method:
                h = h.options(method_name=method)
            result = h.remote(*args, **kwargs).result(timeout_s=self._timeout(context))
            return json.dumps({"result": result}, default=str).encode()
        except Exception as e:  # noqa: BLE001
            if app:
                self._drop_handle(app)
            context.set_code(grpc.StatusCode.INTERNAL)
            context.set_details(repr(e))
            return json.dumps({"error": repr(e)}).encode()

    def _call_streaming(self, request: bytes, context):
        import grpc

        app = None
        try:
            app, method, args, kwargs = self._parse(request)
            h = self._handle(app).options(stream=True)
            if method:
                h = h.options(method_name=method)
            for item in h.remote(*args, **kwargs):
                yield json.dumps({"result": item}, default=str).encode()
        except Exception as e:  # noqa: BLE001
            if app:
                self._drop_handle(app)
            context.set_code(grpc.StatusCode.INTERNAL)
            context.set_details(repr(e))

    def stop(self):
        self._server.stop(grace=1.0)


def grpc_call(address: str, application: str, *args, method: str | None = None, timeout_s: float = 60.0, **kwargs):
    """Convenience unary client for the generic ingress."""
    import grpc

    with grpc.insecure_channel(address) as channel:
        fn = channel.unary_unary(_METHOD_UNARY, request_serializer=None, response_deserializer=None)
        payload = json.dumps({"application": application, "method": method, "args": list(args), "kwargs": kwargs}).encode()
        try:
            out = json.loads(fn(payload, timeout=timeout_s))
        except grpc.RpcError as e:
            raise RuntimeError(f"serve gRPC call failed: {e.details()}") from None
    if "error" in out:
        raise RuntimeError(out["error"])
    return out["result"]


def grpc_call_streaming(address: str, application: str, *args, method: str | None = None, timeout_s: float = 60.0, **kwargs):
    """Server-streaming client: yields each item the deployment yields."""
    import grpc

    with grpc.insecure_channel(address) as channel:
        fn = channel.unary_stream(_METHOD_STREAM, request_serializer=None, response_deserializer=None)
        payload = json.dumps({"application": application, "method": method, "args": list(args), "kwargs": kwargs}).encode()
        for msg in fn(payload, timeout=timeout_s):
            yield json.loads(msg)["result"]

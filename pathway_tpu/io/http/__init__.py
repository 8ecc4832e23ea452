"""``pw.io.http`` — REST ingress/egress.

Capability parity with reference ``python/pathway/io/http/_server.py``:
``rest_connector(...) -> (Table, response_writer)`` (``:624``),
``PathwayWebserver`` (aiohttp + OpenAPI docs, ``:329``),
``RestServerSubject`` (``:490``).  Each HTTP request becomes a row; the
response is resolved when the paired response table produces the row's
result (future-per-key, exactly the reference's mechanism).
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
from typing import Any, Callable

from pathway_tpu.internals import device_counters as _devctr
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import keys as K
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.internals.table import Table
from pathway_tpu.io._connector import RowSource, coerce_row, fmt_value, input_table
from pathway_tpu.io._subscribe import subscribe

__all__ = ["rest_connector", "PathwayWebserver", "RetryLater"]

logger = logging.getLogger("pathway_tpu.http")


class RetryLater(Exception):
    """Request shed by admission control before entering the engine.

    The ingress maps it to HTTP 429 with a ``Retry-After`` header — the
    client is told WHEN capacity is expected back instead of having its
    request buffered into an unbounded queue (see
    ``pathway_tpu/serving/admission.py``)."""

    def __init__(self, retry_after: float = 1.0, reason: str = "overloaded"):
        super().__init__(reason)
        self.retry_after = max(0.0, float(retry_after))
        self.reason = reason


class PathwayWebserver:
    """One aiohttp server shared by any number of routes (reference
    ``PathwayWebserver``).  Runs on its own thread + event loop."""

    def __init__(self, host: str = "0.0.0.0", port: int = 8080, with_cors: bool = False):
        self.host = host
        self.port = port
        self.with_cors = with_cors
        self._routes: dict[tuple[str, str], Callable] = {}
        self._openapi_paths: dict[str, Any] = {}
        self._loop: asyncio.AbstractEventLoop | None = None
        self._started = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()

    def register(self, route: str, methods: tuple[str, ...], handler: Callable, doc: Any = None) -> None:
        for m in methods:
            self._routes[(m.upper(), route)] = handler
        if doc is not None:
            self._openapi_paths[route] = doc

    def openapi_description_json(self) -> dict:
        return {
            "openapi": "3.0.3",
            "info": {"title": "pathway_tpu app", "version": "1.0"},
            "paths": self._openapi_paths,
        }

    def _ensure_started(self) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(target=self._serve, daemon=True)
            self._thread.start()
        self._started.wait(timeout=10)

    def _serve(self) -> None:
        from aiohttp import web

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        app = web.Application()

        async def dispatch(request: "web.Request") -> "web.Response":
            # where the request's `rest_ingress` span starts (RestServerSubject)
            request["pathway_t0_ns"] = _tracing.now_ns()
            handler = self._routes.get((request.method, request.path))
            if handler is None:
                return web.json_response({"error": "not found"}, status=404)
            try:
                payload: dict[str, Any] = {}
                if request.can_read_body:
                    text = await request.text()
                    if text:
                        payload = json.loads(text)
                payload.update(request.query)
                result = await handler(payload, request)
                if isinstance(result, web.Response):
                    return result
                return web.json_response(result, dumps=lambda o: json.dumps(o, default=str))
            except RetryLater as e:
                # load shed: bounded queues + explicit backpressure, never
                # a silent drop or an unbounded buffer
                import math

                return web.json_response(
                    {"error": e.reason, "retry_after": e.retry_after},
                    status=429,
                    headers={"Retry-After": str(max(1, math.ceil(e.retry_after)))},
                )
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=400)
            except Exception as e:  # noqa: BLE001
                logger.exception("handler failed")
                return web.json_response({"error": repr(e)}, status=500)

        async def docs(_request: "web.Request") -> "web.Response":
            return web.json_response(self.openapi_description_json())

        app.router.add_route("*", "/_schema", docs)
        app.router.add_route("*", "/{tail:.*}", dispatch)

        async def start() -> None:
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, self.host, self.port)
            await site.start()
            self._started.set()

        loop.run_until_complete(start())
        loop.run_forever()


class RestServerSubject(RowSource):
    """Bridges HTTP requests into the engine stream (reference
    ``RestServerSubject`` ``io/http/_server.py:490``)."""

    def __init__(
        self,
        webserver: PathwayWebserver,
        route: str,
        methods: tuple[str, ...],
        schema: sch.SchemaMetaclass,
        delete_completed_queries: bool,
        request_validator: Callable | None = None,
        admission: Any = None,
        tenant_field: str = "tenant",
    ):
        self.webserver = webserver
        self.route = route
        self.methods = methods
        self.schema = schema
        self.delete_completed_queries = delete_completed_queries
        self.request_validator = request_validator
        #: admission controller (serving/admission.py contract: ``admit(
        #: tenant, route=...) -> ticket`` raising :class:`RetryLater` on
        #: shed, ticket released when the request leaves the system) —
        #: None keeps the legacy unbounded ingress
        self.admission = admission
        self.tenant_field = tenant_field
        self.futures: dict[K.Pointer, asyncio.Future] = {}
        #: per in-flight request: [when the engine resolved it (ns, 0
        #: until then), that epoch's time]; resolve() writes them on the
        #: engine thread, _handle reads them back on the loop
        self._resolved: dict[K.Pointer, list] = {}
        self._seq = 0
        self._events: Any = None
        self._closed = threading.Event()

    def run(self, events: Any) -> None:
        self._events = events
        doc = {
            "post": {
                "requestBody": {
                    "content": {
                        "application/json": {
                            "schema": {
                                "type": "object",
                                "properties": {
                                    n: {"type": "string"}
                                    for n in self.schema.column_names()
                                },
                            }
                        }
                    }
                },
                "responses": {"200": {"description": "result"}},
            }
        }
        self.webserver.register(self.route, self.methods, self._handle, doc)
        self.webserver._ensure_started()
        # REST source stays open for the lifetime of the run (or until the
        # scheduler shuts down)
        while not self._closed.is_set() and not events.stopped:
            self._closed.wait(timeout=0.25)

    async def _handle(self, payload: dict[str, Any], request: Any) -> Any:
        if self.request_validator is not None:
            maybe_error = self.request_validator(payload)
            if maybe_error is not None:
                raise ValueError(str(maybe_error))
        ticket = None
        if self.admission is not None:
            # bounded ingress: admit or shed BEFORE the row enters the
            # engine; the ticket holds one slot of the tenant's bounded
            # queue until the response resolves (raises RetryLater)
            tenant = str(payload.get(self.tenant_field) or "default")
            ticket = self.admission.admit(tenant, route=self.route)
        try:
            self._seq += 1
            key = K.ref_scalar("__rest__", id(self), self._seq)
            row = coerce_row(payload, self.schema)
            loop = asyncio.get_running_loop()
            future: asyncio.Future = loop.create_future()
            self.futures[key] = future
            ctx = _tracing.new_trace() if _tracing.enabled() else None
            resolved = self._resolved[key] = [0, None]
            self._events.add(key, row)
            self._events.commit()
            _devctr.bump(rest_requests=1)
            if ctx is not None:
                # the request's clock starts where the webserver took it up
                ctx.t0_ns = request["pathway_t0_ns"]
                _tracing.record_span(
                    "rest_ingress", ctx.t0_ns, _tracing.now_ns(), ctx=ctx
                )
            try:
                result = await asyncio.wait_for(future, timeout=120)
            finally:
                self.futures.pop(key, None)
                self._resolved.pop(key, None)
                if self.delete_completed_queries:
                    self._events.remove(key, row)
                    self._events.commit()
                t1_ns = _tracing.now_ns()
                if resolved[0]:
                    _devctr.bump(rest_responses=1)
                    _tracing.record_span(
                        "rest_respond", resolved[0], t1_ns, ctx=ctx,
                        args={"epoch": resolved[1]},
                    )
                _tracing.finish_request(ctx, t1_ns)  # slow: tail-kept
        finally:
            if ticket is not None:
                ticket.release()
        return result

    def resolve(self, key: K.Pointer, value: Any, time: int | None = None) -> None:
        """Hand ``value`` to the request waiting under ``key`` (engine
        thread); ``time`` is the epoch that produced it, the causal link
        from the request's ``rest_respond`` span to the epoch's spans."""
        future = self.futures.get(key)
        if future is not None and not future.done():
            resolved = self._resolved.get(key)
            if resolved is not None:
                resolved[:] = _tracing.now_ns(), time
            loop = future.get_loop()
            loop.call_soon_threadsafe(
                lambda: None if future.done() else future.set_result(value)
            )

    def stop(self) -> None:
        self._closed.set()


def rest_connector(
    host: str | None = None,
    port: int | None = None,
    *,
    webserver: PathwayWebserver | None = None,
    route: str = "/",
    methods: tuple[str, ...] = ("POST",),
    schema: sch.SchemaMetaclass | None = None,
    autocommit_duration_ms: int | None = 50,
    keep_queries: bool | None = None,
    delete_completed_queries: bool = False,
    request_validator: Callable | None = None,
    documentation: Any = None,
    admission: Any = None,
    tenant_field: str = "tenant",
) -> tuple[Table, Callable[[Table], None]]:
    """Expose an HTTP endpoint as an input table; returns the table and a
    ``response_writer(responses)`` that resolves each request's HTTP response
    from the row in ``responses`` with the same key (column ``result``).

    ``admission`` (optional) is an admission controller (see
    ``pathway_tpu/serving/admission.py``): each request is admitted
    against the tenant named by ``payload[tenant_field]`` before its row
    enters the engine, and a shed request gets HTTP 429 + ``Retry-After``
    instead of unbounded buffering."""
    if schema is None:
        schema = sch.schema_from_types(query=str)
    if webserver is None:
        webserver = PathwayWebserver(host or "0.0.0.0", port or 8080)
    subject = RestServerSubject(
        webserver,
        route,
        methods,
        schema,
        delete_completed_queries,
        request_validator,
        admission=admission,
        tenant_field=tenant_field,
    )
    table = input_table(subject, schema, name=f"rest:{route}")

    def response_writer(responses: Table) -> None:
        result_col = "result" if "result" in responses._column_names else responses._column_names[-1]

        def on_change(key: K.Pointer, row: dict, time: int, is_addition: bool) -> None:
            if not is_addition:
                return
            subject.resolve(key, fmt_value(row[result_col]), time)

        subscribe(responses, on_change=on_change, name="rest_response")

    return table, response_writer

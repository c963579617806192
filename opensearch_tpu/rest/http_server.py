"""Threaded HTTP front-end for the REST controller.

Analog of the netty4 HTTP transport (modules/transport-netty4/...
Netty4HttpServerTransport.java) at the fidelity this slice needs: a
thread-per-connection stdlib server handing parsed (method, path, params,
body) to ``RestController.dispatch``.  _cat endpoints render text tables
unless ``format=json`` (rest/action/cat/ behavior).
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qsl, urlsplit

from opensearch_tpu.common.telemetry import (TRACEPARENT, SpanContext,
                                             metrics, tracer)


def _cat_table(rows: list[dict], want_header: bool,
               columns: str | None = None) -> bytes:
    if not rows:
        return b""
    cols = list(rows[0])
    if columns:                       # ?h=a,b column selection
        cols = [c.strip() for c in columns.split(",") if c.strip()]
    widths = {c: max(len(c) if want_header else 0,
                     *(len(str(r.get(c, ""))) for r in rows)) for c in cols}
    out = []
    if want_header:
        out.append(" ".join(c.ljust(widths[c]) for c in cols).rstrip())
    for r in rows:
        out.append(" ".join(str(r.get(c, "")).ljust(widths[c])
                            for c in cols).rstrip())
    return ("\n".join(out) + "\n").encode()


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "opensearch-tpu"

    def log_message(self, fmt, *args):  # quiet
        pass

    def setup(self):
        # the thread hand-off: from accept's return on the serving thread
        # to this handler thread running
        accepted = self.server.accepted_ns.pop(self.client_address, None)
        # from here this thread waits for the request line and parses
        # the headers (http.server), before any span is open
        self._waiting_ns = now = time.monotonic_ns()
        self._accept_wait_ns = None if accepted is None else now - accepted
        super().setup()

    def _handle(self):
        attrs = {"http.method": self.command}
        wait_ns, self._accept_wait_ns = self._accept_wait_ns, None
        if wait_ns is not None:
            # the connection's first request only: the time between the
            # requests of a kept-alive connection is the client's
            head_ns = time.monotonic_ns() - self._waiting_ns
            attrs["accept_wait_ns"] = wait_ns
            attrs["head_read_ns"] = head_ns
            metrics().histogram("rest.accept_wait_ms").observe(
                wait_ns / 1e6)
            metrics().histogram("rest.head_read_ms").observe(head_ns / 1e6)
        # cpu: duration less CPU is all the time this request's thread
        # did not run, from the body read to the socket write
        with tracer().start_span(
                "http.request", attrs,
                parent=SpanContext.from_traceparent(
                    self.headers.get(TRACEPARENT)), cpu=True) as span:
            span.set_attribute("http.status", self._respond(span))

    def _respond(self, span) -> int:
        """Body read, dispatch, serialisation and socket write; returns
        the status sent.  ``span`` (``http.request``) gets the parts
        ``read`` and ``respond`` here, ``route`` and ``after`` in
        ``RestController.dispatch``."""
        from opensearch_tpu.common.breakers import (CircuitBreakingError,
                                                    breaker_service)

        t_read = time.monotonic_ns()
        split = urlsplit(self.path)
        params = dict(parse_qsl(split.query, keep_blank_values=True))
        length = int(self.headers.get("Content-Length") or 0)
        # in-flight byte accounting BEFORE the body is read into memory
        # (the reference's in_flight_requests breaker / IndexingPressure
        # admission check)
        breaker = breaker_service().in_flight
        extra_headers: dict = {}
        try:
            breaker.add_estimate(length, label=f"<http_request> "
                                               f"{split.path}")
        except CircuitBreakingError as e:
            # the body stays UNREAD (that's the point) — the connection
            # cannot be reused, or the next parse reads body bytes as a
            # request line
            self.close_connection = True
            status, payload = 429, e.to_xcontent()
            # Retry-After from the measured admission drain rate
            # (permit-release EWMA, floor/ceiling clamped) instead of a
            # hardcoded second — a wedged node tells clients to
            # actually back off
            hint = 1
            bp = getattr(self.server.controller.node,
                         "search_backpressure", None)
            if bp is not None:
                hint = bp.admission.retry_after_hint()
            extra_headers["Retry-After"] = str(hint)
        else:
            try:
                body = self.rfile.read(length) if length else b""
                span.add_part("read", time.monotonic_ns() - t_read)
                status, payload = self.server.controller.dispatch(
                    self.command, split.path, params, body,
                    self.headers.get("Content-Type") or "",
                    self.headers.get("Authorization") or "",
                    headers=dict(self.headers.items()),
                    response_headers=extra_headers)
            finally:
                breaker.release(length)
        t_respond = time.monotonic_ns()
        from opensearch_tpu.rest.controller import PlainText
        is_cat = split.path.startswith("/_cat") and params.get("format") != "json"
        if isinstance(payload, PlainText):
            # verbatim text surface (Prometheus /_metrics exposition):
            # no x-content negotiation, the payload IS the wire format
            data = payload.text.encode()
            ctype = payload.content_type
        elif is_cat and isinstance(payload, list):
            data = _cat_table(payload, want_header="v" in params,
                              columns=params.get("h"))
            ctype = "text/plain; charset=UTF-8"
        else:
            # response format negotiation (x-content: json/yaml/cbor via
            # ?format= or Accept); _cat keeps its table/json handling
            from opensearch_tpu.common.errors import OpenSearchTpuError
            from opensearch_tpu.common.xcontent import to_bytes
            fmt = params.get("format") or ""
            if split.path.startswith("/_cat"):
                # only format=json reaches here (tables short-circuit
                # above); pin it so Accept can't override an explicit
                # format=json request
                fmt = "json"
            try:
                data, ctype = to_bytes(payload,
                                       self.headers.get("Accept") or "",
                                       fmt)
            except OpenSearchTpuError as e:
                status = e.status
                data = (json.dumps(e.to_xcontent()) + "\n").encode()
                ctype = "application/json; charset=UTF-8"
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        for k, v in extra_headers.items():
            # error-mapping headers (Retry-After on 429 rejections)
            self.send_header(k, str(v))
        opaque = self.headers.get("X-Opaque-Id")
        if opaque:
            # the reference echoes X-Opaque-Id on every response so
            # clients can correlate (Task.X_OPAQUE_ID response header)
            self.send_header("X-Opaque-Id", opaque)
        self.end_headers()
        if self.command != "HEAD":
            self.wfile.write(data)
        span.add_part("respond", time.monotonic_ns() - t_respond)
        return status

    do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _handle


class _Server(ThreadingHTTPServer):
    # accept backlog sized like the reference's netty transport, not the
    # stdlib default (5): the open-loop load harness showed bursts of
    # concurrent connects overflowing the backlog — the kernel then
    # refuses/resets, which clients see as transport errors rather than
    # an honest 429 with Retry-After.  The OS clamps to somaxconn.
    request_queue_size = 1024

    def __init__(self, *args, **kwargs):
        # client address -> time.monotonic_ns() when accept returned
        self.accepted_ns: dict = {}
        super().__init__(*args, **kwargs)

    def process_request(self, request, client_address):
        # on the serving thread, before the handler thread is started
        self.accepted_ns[client_address] = time.monotonic_ns()
        try:
            super().process_request(request, client_address)
        except BaseException:        # no handler thread will take it
            self.accepted_ns.pop(client_address, None)
            raise


class HttpServer:
    def __init__(self, controller, host: str = "127.0.0.1", port: int = 9200):
        self.httpd = _Server((host, port), _Handler)
        self.httpd.controller = controller
        self.port = self.httpd.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self):
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        name="http-server", daemon=True)
        self._thread.start()

    def stop(self):
        """Idempotent, and safe WITHOUT a prior start():
        ``ThreadingHTTPServer.shutdown()`` blocks forever unless
        ``serve_forever`` is actually running, so it is only called when
        the serving thread exists."""
        thread, self._thread = self._thread, None
        if thread is not None:
            self.httpd.shutdown()
        self.httpd.server_close()
        if thread is not None:
            thread.join(timeout=5)

"""Standalone HTTP/1.1 server for the ASGI app, built on h11 + asyncio.

The reference ran under uvicorn (/root/reference/Makefile:3-7); uvicorn is not
available in this environment, so quorum_tpu bundles a small ASGI server. It
supports exactly what the API needs: request bodies, JSON responses, and
incrementally-flushed streaming (SSE) responses with chunked transfer encoding.

Run:  python -m quorum_tpu.server.serve --port 8000 [--config config.yaml]
"""

from __future__ import annotations

import argparse
import asyncio
import logging
from typing import Any

import h11

from quorum_tpu.config import load_config
from quorum_tpu.devices import NoAcceleratorError
from quorum_tpu.observability import setup_aggregation_log
from quorum_tpu.server.app import create_app

logger = logging.getLogger(__name__)


class _ConnectionHandler:
    def __init__(self, app, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        self.app = app
        self.reader = reader
        self.writer = writer
        self.conn = h11.Connection(h11.SERVER)

    async def run(self) -> None:
        try:
            while True:
                request = await self._next_request()
                if request is None:
                    return
                await self._handle(request)
                if self.conn.our_state is h11.MUST_CLOSE or self.conn.their_state is h11.MUST_CLOSE:
                    return
                try:
                    self.conn.start_next_cycle()
                except h11.ProtocolError:
                    return
        except (ConnectionResetError, BrokenPipeError, asyncio.IncompleteReadError):
            pass
        except Exception:
            logger.exception("Connection handler error")
        finally:
            self.writer.close()

    async def _next_event(self):
        while True:
            event = self.conn.next_event()
            if event is h11.NEED_DATA:
                data = await self.reader.read(65536)
                self.conn.receive_data(data)
                if data == b"" and self.conn.their_state is h11.IDLE:
                    return None
                continue
            return event

    async def _next_request(self) -> h11.Request | None:
        while True:
            event = await self._next_event()
            if event is None or isinstance(event, h11.ConnectionClosed):
                return None
            if isinstance(event, h11.Request):
                return event

    async def _read_body(self) -> bytes:
        chunks = []
        while True:
            event = await self._next_event()
            if isinstance(event, h11.Data):
                chunks.append(bytes(event.data))
            elif isinstance(event, h11.EndOfMessage) or event is None:
                return b"".join(chunks)

    async def _handle(self, request: h11.Request) -> None:
        body = await self._read_body()
        path, _, query = request.target.partition(b"?")
        scope: dict[str, Any] = {
            "type": "http",
            "asgi": {"version": "3.0"},
            "http_version": "1.1",
            "method": request.method.decode(),
            "path": path.decode(),
            "raw_path": bytes(request.target),
            "query_string": query,
            "headers": [(k.lower(), v) for k, v in request.headers],
            "client": self.writer.get_extra_info("peername"),
            "server": self.writer.get_extra_info("sockname"),
            "scheme": "http",
        }

        body_sent = False

        async def receive():
            nonlocal body_sent
            if body_sent:
                return {"type": "http.disconnect"}
            body_sent = True
            return {"type": "http.request", "body": body, "more_body": False}

        started = False

        async def send(message: dict[str, Any]) -> None:
            nonlocal started
            if message["type"] == "http.response.start":
                started = True
                headers = [(k, v) for k, v in message.get("headers", [])]
                self._send(
                    h11.Response(status_code=message["status"], headers=headers)
                )
            elif message["type"] == "http.response.body":
                data = message.get("body", b"")
                if data:
                    self._send(h11.Data(data=data))
                if not message.get("more_body", False):
                    self._send(h11.EndOfMessage())
                await self.writer.drain()

        try:
            await self.app(scope, receive, send)
        except Exception:
            logger.exception("ASGI app error")
            if not started:
                self._send(
                    h11.Response(
                        status_code=500,
                        headers=[(b"content-type", b"application/json")],
                    )
                )
                self._send(h11.Data(data=b'{"error":{"message":"internal error"}}'))
                self._send(h11.EndOfMessage())
                await self.writer.drain()

    def _send(self, event) -> None:
        data = self.conn.send(event)
        if data:
            self.writer.write(data)


async def start_server(app, host: str = "127.0.0.1", port: int = 0) -> asyncio.Server:
    """Bind and return the asyncio server (port 0 → ephemeral; read the bound
    port from ``server.sockets[0].getsockname()[1]``). Used by bench.py and the
    socket-level tests, which need a real TCP socket — httpx.ASGITransport
    buffers entire responses and cannot observe streaming incrementality."""

    async def on_connect(reader, writer):
        await _ConnectionHandler(app, reader, writer).run()

    return await asyncio.start_server(on_connect, host, port)


async def serve(app, host: str = "0.0.0.0", port: int = 8000) -> None:
    server = await start_server(app, host, port)
    addrs = ", ".join(str(s.getsockname()) for s in server.sockets)
    logger.info("quorum_tpu serving on %s", addrs)
    async with server:
        await server.serve_forever()


def main() -> None:
    parser = argparse.ArgumentParser(description="quorum_tpu OpenAI-compatible server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--config", default=None, help="path to config.yaml")
    parser.add_argument("--log-level", default="INFO")
    parser.add_argument(
        "--log-dir", default="logs",
        help="directory for the aggregation log channel (logs/aggregation.log)",
    )
    parser.add_argument(
        "--watch", action="store_true",
        help="dev mode: hot-reload config.yaml edits in-process without "
             "dropping live tpu:// engines (reference parity with its "
             "uvicorn --reload-include '*.yaml' dev server)",
    )
    args = parser.parse_args()

    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(levelname)s:%(asctime)s:%(name)s: %(message)s",
    )
    setup_aggregation_log(args.log_dir)
    # Multi-host deployments: join the jax process group before any backend
    # initializes a device client (no-op for single-process runs — laptop,
    # one chip, CPU). Env-driven: JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES
    # / JAX_PROCESS_ID, or TPU-pod metadata inference.
    from quorum_tpu.parallel.distributed import initialize

    initialize()
    cfg = load_config(args.config)
    try:
        app = create_app(cfg, watch_config=True if args.watch else None)
    except NoAcceleratorError as e:
        # No fallback: a tpu:// config without a TPU does not start.
        raise SystemExit(f"quorum_tpu.server.serve: {e}") from e
    try:
        asyncio.run(serve(app, args.host, args.port))
    except KeyboardInterrupt:
        pass
    finally:
        # Graceful teardown: cancel in-flight generations, join scheduler
        # threads, release HBM — not strictly needed on process exit, but it
        # makes embedding (and Ctrl-C during local runs) clean.
        from quorum_tpu.engine.engine import shutdown_all_engines

        shutdown_all_engines()


if __name__ == "__main__":
    main()

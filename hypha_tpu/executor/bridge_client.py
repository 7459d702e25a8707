"""Executor-side Job-Bridge client: HTTP over the job's unix socket.

Parity with the reference executor's ``api.py::Session``
(executors/accelerate/src/hypha/accelerate_executor/api.py:11-63):
``fetch``, ``send_resource``, ``send_status``, and ``receive`` — an SSE
context manager yielding JSON file pointers as tensors land.
"""

from __future__ import annotations

import json
from contextlib import contextmanager
from typing import Any, Iterator

import httpx

from .. import messages
from ..messages import Fetch, Progress, ProgressResponse, Receive, Send

__all__ = ["Session"]


class Session:
    def __init__(self, socket_path: str, timeout: float = 300.0) -> None:
        self._client = httpx.Client(
            transport=httpx.HTTPTransport(uds=socket_path),
            base_url="http://bridge",
            timeout=timeout,
        )

    def close(self) -> None:
        self._client.close()

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------

    def fetch(self, fetch: Fetch) -> list[str]:
        """Materialize a reference under work_dir/artifacts; returns the
        work-dir-relative paths."""
        r = self._client.post(
            "/resources/fetch", json={"fetch": messages.to_json_dict(fetch)}
        )
        r.raise_for_status()
        return r.json()["paths"]

    def send_resource(
        self,
        send: Send,
        path: str,
        resource: str = "updates",
        meta: dict[str, Any] | None = None,
    ) -> bool:
        """Ship a work-dir file to peers (runs in the worker's background).
        ``meta`` rides the stream header (e.g. num_samples for the parameter
        server's sample-weighted mean). True where the node holds a second
        name for the file until the send has ended (the 202's ``held``):
        the file's link count then says whether a send of it is open."""
        r = self._client.post(
            "/resources/send",
            json={
                "send": messages.to_json_dict(send),
                "path": path,
                "resource": resource,
                "meta": meta or {},
            },
        )
        r.raise_for_status()
        return bool(r.json().get("held"))

    def send_status(self, progress: Progress) -> ProgressResponse:
        """Report progress; returns the scheduler's control decision."""
        r = self._client.post(
            "/status/send", json={"progress": messages.to_json_dict(progress)}
        )
        r.raise_for_status()
        resp = messages.from_json_dict(r.json()["response"])
        if not isinstance(resp, ProgressResponse):
            raise ValueError(f"unexpected status response {resp!r}")
        return resp

    @contextmanager
    def receive(self, receive: Receive) -> Iterator[Iterator[dict[str, Any]]]:
        """SSE stream of ``{path,size,from_peer,resource}`` pointers."""
        with self._client.stream(
            "POST",
            "/resources/receive",
            json={"receive": messages.to_json_dict(receive)},
            timeout=None,
        ) as response:
            response.raise_for_status()

            def events() -> Iterator[dict[str, Any]]:
                for line in response.iter_lines():
                    if line.startswith("data: "):
                        yield json.loads(line[len("data: ") :])

            yield events()

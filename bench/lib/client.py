"""Stdlib client of the serving front end's newline-JSON protocol.

Copied, cut to what the load generator needs, from the program's
``examples/stream_client.Client`` so the yardstick cannot change under a
later change to the program. Submits never wait for their reply: one
reader thread takes every event, and the sender keeps its schedule.
"""
from __future__ import annotations

import json
import socket
import threading


class Connection:
    def __init__(self, host: str, port: int, timeout: float = 30.0):
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._sock.settimeout(None)
        self._reader = self._sock.makefile("r", encoding="utf-8")
        self._wlock = threading.Lock()

    def send(self, obj: dict) -> None:
        data = (json.dumps(obj) + "\n").encode()
        with self._wlock:
            self._sock.sendall(data)

    def submit(self, prompt, max_new: int, tag) -> None:
        self.send({"op": "submit", "prompt": [int(t) for t in prompt],
                   "max_new": int(max_new), "stream": True, "tag": tag})

    def events(self):
        """Yield events in arrival order until the server closes."""
        for line in self._reader:
            if line.strip():
                yield json.loads(line)

    def close(self) -> None:
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._reader.close()
            self._sock.close()
        except OSError:
            pass

"""Pure-Python shims matching runtime.native_bindings when the native
toolchain is unavailable (same public API, reduced fidelity). The native
path is the supported one; this keeps CI/minimal environments working."""

from __future__ import annotations

import queue as _pyqueue
import threading
import time
from typing import Any, Optional


def now_ns() -> int:
    return time.monotonic_ns()


class QueueClosed(Exception):
    pass


class BlockingQueue:
    def __init__(self, capacity: int):
        self._q = _pyqueue.Queue(maxsize=max(capacity, 1))
        self._closed = threading.Event()

    def push(self, obj: Any, timeout: Optional[float] = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._closed.is_set():
                raise QueueClosed("queue closed")
            try:
                self._q.put(obj, timeout=0.05)
                return True
            except _pyqueue.Full:
                if deadline is not None and time.monotonic() > deadline:
                    return False

    def pop(self, timeout: Optional[float] = None) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            try:
                return self._q.get(timeout=0.05)
            except _pyqueue.Empty:
                if self._closed.is_set() and self._q.empty():
                    raise QueueClosed("queue closed and drained")
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("BlockingQueue.pop timed out")

    def size(self) -> int:
        return self._q.qsize()

    def capacity(self) -> int:
        return self._q.maxsize

    def close(self):
        self._closed.set()

    @property
    def closed(self) -> bool:
        return self._closed.is_set()


class _TracerState(threading.local):
    def __init__(self):
        self.stack = []


_trace_enabled = False
_trace_events: list = []
# reentrant: a gc callback may record a span while this thread holds it
_trace_mu = threading.RLock()
_trace_tls = _TracerState()


class HostTracer:
    enabled = False  # fast-path mirror, same as the native bindings

    @staticmethod
    def enable():
        global _trace_enabled
        _trace_enabled = True
        HostTracer.enabled = True

    @staticmethod
    def disable():
        global _trace_enabled
        _trace_enabled = False
        HostTracer.enabled = False

    @staticmethod
    def is_enabled() -> bool:
        return _trace_enabled

    @staticmethod
    def begin(name: str):
        if _trace_enabled:
            _trace_tls.stack.append((name, now_ns()))

    @staticmethod
    def end():
        if _trace_enabled and _trace_tls.stack:
            name, t0 = _trace_tls.stack.pop()
            with _trace_mu:
                _trace_events.append(
                    (0, t0, now_ns(), threading.get_ident(), 0, name))

    @staticmethod
    def instant(name: str):
        if _trace_enabled:
            t = now_ns()
            with _trace_mu:
                _trace_events.append(
                    (1, t, t, threading.get_ident(), 0, name))

    @staticmethod
    def counter(name: str, value: int):
        if _trace_enabled:
            t = now_ns()
            with _trace_mu:
                _trace_events.append(
                    (2, t, t, threading.get_ident(), value, name))

    @staticmethod
    def count() -> int:
        with _trace_mu:
            return len(_trace_events)

    @staticmethod
    def clear():
        with _trace_mu:
            _trace_events.clear()

    @staticmethod
    def events() -> list:
        with _trace_mu:
            return list(_trace_events)

    @staticmethod
    def export_chrome_trace(path: str):
        import json
        out = []
        for kind, t0, t1, tid, value, name in HostTracer.events():
            if kind == 0:
                out.append({"name": name, "ph": "X", "pid": 0, "tid": tid,
                            "ts": t0 / 1e3, "dur": (t1 - t0) / 1e3})
            elif kind == 1:
                out.append({"name": name, "ph": "i", "pid": 0, "tid": tid,
                            "ts": t0 / 1e3, "s": "t"})
            else:
                out.append({"name": name, "ph": "C", "pid": 0, "tid": tid,
                            "ts": t0 / 1e3, "args": {"value": value}})
        with open(path, "w") as f:
            json.dump({"traceEvents": out}, f)


_stats: dict = {}
_stats_mu = threading.Lock()


def stat_update(name: str, delta: int):
    with _stats_mu:
        cur, peak = _stats.get(name, (0, 0))
        cur += delta
        _stats[name] = (cur, max(peak, cur))


def stat_current(name: str) -> int:
    with _stats_mu:
        return _stats.get(name, (0, 0))[0]


def stat_peak(name: str) -> int:
    with _stats_mu:
        return _stats.get(name, (0, 0))[1]


def stat_reset(name: str):
    with _stats_mu:
        _stats.pop(name, None)


def stat_names() -> list:
    with _stats_mu:
        return sorted(_stats)


class WorkQueue:
    def __init__(self, num_threads: int):
        self._q: "_pyqueue.Queue" = _pyqueue.Queue()
        self._errors: list = []
        self._mu = threading.Lock()
        self._stop = False
        self._threads = [threading.Thread(target=self._loop, daemon=True)
                         for _ in range(max(num_threads, 1))]
        for t in self._threads:
            t.start()

    def _loop(self):
        while True:
            fn = self._q.get()
            if fn is None:
                self._q.task_done()
                return
            try:
                fn()
            except Exception as e:
                with self._mu:
                    self._errors.append(e)
            finally:
                self._q.task_done()

    def submit(self, fn):
        if self._stop:
            raise RuntimeError("WorkQueue.submit on stopped queue")
        self._q.put(fn)

    def wait_idle(self):
        self._q.join()
        with self._mu:
            if self._errors:
                raise self._errors.pop(0)

    def pending(self) -> int:
        return self._q.unfinished_tasks

    def shutdown(self):
        self._stop = True
        for _ in self._threads:
            self._q.put(None)


# TCPStore fallback: thin wrappers over the native wire protocol are not
# possible without the native lib; provide a socket-based Python server
# compatible enough for single-host tests.
import socket
import socketserver
import struct as _struct


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        srv = self.server
        f = self.request
        try:
            while True:
                hdr = self._recv(f, 5)
                if hdr is None:
                    return
                op, keylen = hdr[0], _struct.unpack("<I", hdr[1:5])[0]
                key = self._recv(f, keylen) or b""
                (arg,) = _struct.unpack("<Q", self._recv(f, 8))
                if op == 1:
                    val = self._recv(f, arg) if arg else b""
                    with srv.cv:
                        srv.kv[key] = val
                        srv.cv.notify_all()
                    f.sendall(_struct.pack("<q", 0))
                elif op in (2, 4):
                    deadline = None if arg == 0 else time.monotonic() + arg / 1e3
                    with srv.cv:
                        while key not in srv.kv:
                            left = None if deadline is None else deadline - time.monotonic()
                            if left is not None and left <= 0:
                                break
                            srv.cv.wait(timeout=0.05 if left is None else min(left, 0.05))
                        if key not in srv.kv:
                            f.sendall(_struct.pack("<q", -1))
                        elif op == 2:
                            v = srv.kv[key]
                            f.sendall(_struct.pack("<q", len(v)) + v)
                        else:
                            f.sendall(_struct.pack("<q", 0))
                elif op == 3:
                    with srv.cv:
                        cur = 0
                        v = srv.kv.get(key)
                        if v is not None and len(v) == 8:
                            (cur,) = _struct.unpack("<q", v)
                        cur += _struct.unpack("<q", _struct.pack("<Q", arg))[0]
                        srv.kv[key] = _struct.pack("<q", cur)
                        srv.cv.notify_all()
                    f.sendall(_struct.pack("<q", cur))
        except Exception:
            return

    @staticmethod
    def _recv(sock, n):
        data = b""
        while len(data) < n:
            chunk = sock.recv(n - len(data))
            if not chunk:
                return None
            data += chunk
        return data


class TCPStoreServer:
    def __init__(self, port: int = 0):
        self._srv = socketserver.ThreadingTCPServer(("0.0.0.0", port), _Handler)
        self._srv.daemon_threads = True
        self._srv.allow_reuse_address = True
        self._srv.kv = {}
        self._srv.cv = threading.Condition()
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        daemon=True)
        self._thread.start()

    @property
    def port(self) -> int:
        return self._srv.server_address[1]

    def stop(self):
        self._srv.shutdown()


class TCPStore:
    def __init__(self, host: str, port: int, timeout: float = 60.0):
        deadline = time.monotonic() + timeout
        while True:
            try:
                self._sock = socket.create_connection((host, port), timeout=5)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise ConnectionError(f"TCPStore: cannot reach {host}:{port}")
                time.sleep(0.05)
        self._sock.settimeout(None)  # blocking gets may legitimately wait >5s
        self._mu = threading.Lock()

    def _req(self, op, key: bytes, arg: int, payload: bytes = b""):
        with self._mu:
            msg = bytes([op]) + _struct.pack("<I", len(key)) + key + \
                _struct.pack("<Q", arg & (2**64 - 1)) + payload
            self._sock.sendall(msg)
            status = _Handler._recv(self._sock, 8)
            (st,) = _struct.unpack("<q", status)
            val = b""
            if op == 2 and st >= 0:
                val = _Handler._recv(self._sock, st) or b""
            return st, val

    def set(self, key: str, value: bytes):
        self._req(1, key.encode(), len(value), value)

    def get(self, key: str, timeout: float = 60.0) -> bytes:
        st, val = self._req(2, key.encode(), int(timeout * 1000))
        if st < 0:
            raise TimeoutError(f"TCPStore.get({key!r}) timed out")
        return val

    def add(self, key: str, delta: int = 1) -> int:
        st, _ = self._req(3, key.encode(), delta)
        return st

    def wait(self, key: str, timeout: float = 60.0):
        st, _ = self._req(4, key.encode(), int(timeout * 1000))
        if st != 0:
            raise TimeoutError(f"TCPStore.wait({key!r}) timed out")

    def close(self):
        try:
            self._sock.close()
        except Exception:
            pass

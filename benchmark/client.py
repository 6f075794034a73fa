"""The server child and the load generator: stdlib only, never jax.

`Server`, `in_threads` and the ladder check are chip_smoke.py's, copied:
the yardstick may not import a file that a later PR can edit. Added: one
keep-alive connection per client thread, and the closed-loop window.
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HTTP_TIMEOUT = 600.0
START_TIMEOUT = 300.0
STOP_TIMEOUT = 120.0
LATE_ANSWER_S = 60.0

# /debug/vars counters that say a rung below the device served something,
# or that the device path failed and was papered over. All must stay 0.
LADDER_ZERO = (
    "device_dispatch_errors", "host_counts", "host_topn", "host_cold_counts",
    "oom_backpressure", "oom_batch_splits", "watchdog_timeouts",
    "tier_promote_errors",
)


def ladder_nonzero(engine_cache):
    return {k: engine_cache.get(k) for k in LADDER_ZERO
            if engine_cache.get(k) != 0}


class Connection:
    """One keep-alive HTTP connection; reopened once if the peer closed it."""

    def __init__(self, port, timeout=HTTP_TIMEOUT):
        self.port, self.timeout = port, timeout
        self.conn = None

    def call(self, method, path, body=None):
        """(status, headers, bytes), read to the last byte."""
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "localhost", self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body)
                resp = self.conn.getresponse()
                return resp.status, resp.headers, resp.read()
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Server:
    """The server child: started, asked over HTTP, stopped."""

    def __init__(self, repo, data_dir, log_path, flags=(), env=None):
        self.repo = repo
        self.data_dir = data_dir
        self.log_path = log_path
        self.flags = list(flags)
        self.env = env
        self.proc = None
        self.port = None

    def start(self):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            self.port = s.getsockname()[1]
        t0 = time.monotonic()
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu.cli", "server",
                 "--data-dir", self.data_dir,
                 "--bind", f"localhost:{self.port}"] + self.flags,
                cwd=self.repo, env=self.env,
                stdout=subprocess.PIPE, stderr=log)
        ready = threading.Event()

        def pump():
            for line in self.proc.stdout:
                if b"listening on" in line:
                    ready.set()

        threading.Thread(target=pump, daemon=True).start()
        deadline = t0 + START_TIMEOUT
        while not ready.wait(0.05):
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited {self.proc.returncode} before listening")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"server not listening after {START_TIMEOUT:.0f}s")
        return time.monotonic() - t0

    def stop(self):
        """SIGTERM and wait; True when the server exited by itself."""
        if self.proc is None or self.proc.poll() is not None:
            return True
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(STOP_TIMEOUT)
            return True
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            return False

    def kill(self):
        """SIGKILL and wait: a crash, with nothing flushed on the way out."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def request(self, method, path, body=None):
        """One bounded HTTP call on a connection of its own; the parsed
        JSON. 429 (admission shed) is retried after the advertised delay;
        anything else but 200 raises."""
        if isinstance(body, str):
            body = body.encode()
        for _ in range(60):
            conn = Connection(self.port)
            try:
                status, headers, data = conn.call(method, path, body)
            finally:
                conn.close()
            if status == 429:
                time.sleep(float(headers.get("Retry-After") or 1.0))
                continue
            if status != 200:
                raise RuntimeError(
                    f"{method} {path}: HTTP {status}: {data[:300]!r}")
            return json.loads(data) if data else None
        raise RuntimeError(f"{method} {path}: still shed after 60 tries")

    def vars(self):
        return self.request("GET", "/debug/vars")

    def log_tail(self, n=3000):
        if not os.path.exists(self.log_path):
            return ""
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")


def in_threads(n, fn, items):
    """fn(item) for every item from n threads; results in order. The first
    exception is re-raised once all threads have ended."""
    out = [None] * len(items)
    errors = []
    gate = threading.Barrier(n)

    def work(k):
        try:
            gate.wait(60)
            for i in range(k, len(items), n):
                out[i] = fn(items[i])
        except Exception as e:  # re-raised below, in the caller's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


class Sent:
    """One request of the window: what was asked, when, and what came."""

    __slots__ = ("client", "template", "pql", "start", "end", "status",
                 "result")

    def __init__(self, client, template, pql):
        self.client, self.template, self.pql = client, template, pql
        self.start = self.end = 0.0
        self.status = None      # HTTP status; None where nothing came back
        self.result = None


def closed_loop(port, index, streams, seconds, tamper=None):
    """Every client sends its stream's next request the moment the last
    one was answered, until `seconds` have passed or its stream ends
    (`next()` gives None). A request is timed from send to last byte and
    kept with its answer. Returns (list of Sent per client, t0, t1):
    requests begun before t1 are waited for, so the last ones end after it.

    `tamper(sent)` may alter an answer where it is produced: the tests'
    way to break the timed path underneath."""
    path = f"/index/{index}/query"
    gate = threading.Barrier(len(streams) + 1)
    sent = [[] for _ in streams]
    clock = {}

    def work(k):
        conn = Connection(port, timeout=seconds + LATE_ANSWER_S)
        stream = streams[k]
        try:
            gate.wait(60)
            t1 = clock["t0"] + seconds
            while time.monotonic() < t1:
                drawn = stream.next()
                if drawn is None:
                    break
                template, group = drawn
                for pql in group:
                    s = Sent(k, template, pql)
                    sent[k].append(s)
                    s.start = time.monotonic()
                    try:
                        s.status, _, body = conn.call("POST", path,
                                                      pql.encode())
                        s.end = time.monotonic()
                        if s.status == 200:
                            s.result = json.loads(body)["results"][0]
                    except (OSError, http.client.HTTPException, ValueError,
                            KeyError, IndexError):
                        s.end = time.monotonic()
                        conn.close()
                    if tamper:
                        tamper(s)
        finally:
            conn.close()

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(len(streams))]
    for t in threads:
        t.start()
    clock["t0"] = time.monotonic()
    gate.wait(60)
    for t in threads:
        t.join()
    return sent, clock["t0"], clock["t0"] + seconds


def floor_ms(port, n=200):
    """Median of n `GET /version` through the same connection code: what
    the load generator and the HTTP server cost with no query at all."""
    conn = Connection(port)
    times = []
    try:
        for _ in range(n):
            t = time.monotonic()
            conn.call("GET", "/version")
            times.append(time.monotonic() - t)
    finally:
        conn.close()
    times.sort()
    return 1000.0 * times[len(times) // 2]

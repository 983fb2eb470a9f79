"""The load generator: HTTP calls to the gateway and the loops that send them.

Everything here is host code without JAX.  Requests are plain (unstreamed)
``POST /v1/completions`` with ``temperature`` 0 and ``logprobs`` true: the
gateway writes a streamed event only when the new tokens decode to text, and
with random weights at a real vocabulary they do not, so a stream carries
nothing a blocking answer lacks (PERF.md, Open questions).

Every request carries a ``timeout_s`` that ends at the phase's deadline.  The
server then answers a request still running at the deadline with the tokens
it had produced by then (``finish_reason`` "timeout"), and one that had
produced none with 503: both are *cut*, not failed, and the tokens of the
first count.  That is what makes the token count of a window exact at both
ends: the pre-roll's requests are cut at the window's start, where every
caller sends its next request at once, and the window's own at its end.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time
import urllib.error
import urllib.request
from dataclasses import asdict, dataclass, field

from . import traffic

MIN_SEND_S = 0.05         # no request is sent this close to a deadline
HTTP_SLACK_S = 120.0      # a socket waits this much longer than timeout_s
STAGGER_S = 0.02          # between the scripts' first requests of a window


@dataclass
class Record:
    """One request as the client saw it."""

    phase: str                # "preroll" | "window" | "setup"
    client: int
    session: int
    turn: int
    prompt_tokens: int        # bytes sent; the server adds one BOS
    shared: int
    asked: int
    t_send: float
    t_due: float | None = None   # open loop: when it should have been sent
    t_done: float = math.nan
    status: int = 0
    finish: str | None = None
    n_tokens: int = 0
    cached_tokens: int = 0
    logprobs: list = field(default_factory=list)
    error: str | None = None

    @property
    def cut(self) -> bool:
        """Ended by the phase's deadline, as designed."""
        return self.finish == "timeout" or (
            self.status == 503 and "deadline expired" in (self.error or "")
        )

    @property
    def complete(self) -> bool:
        return self.status == 200 and self.finish in ("length", "stop")

    @property
    def failed(self) -> bool:
        """Not cut, and not a whole, finite answer of the length asked (or
        a shorter one that ended on the end-of-sequence token)."""
        if self.cut:
            return not all(map(math.isfinite, self.logprobs))
        if not self.complete or len(self.logprobs) != self.n_tokens:
            return True
        if not all(map(math.isfinite, self.logprobs)):
            return True
        if self.finish == "length":
            return self.n_tokens != self.asked
        return not 1 <= self.n_tokens <= self.asked

    def to_json(self) -> dict:
        d = asdict(self)
        d["logprobs"] = len(self.logprobs)
        return d


class Gateway:
    """The HTTP calls made to one server."""

    def __init__(self, base: str):
        self.base = base

    def get(self, path: str, timeout: float = 10.0) -> tuple[int, bytes]:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.status, r.read()

    def ready(self) -> bool:
        try:
            return self.get("/healthz", timeout=5.0)[0] == 200
        except (urllib.error.URLError, OSError):
            return False

    def health(self) -> dict:
        return json.loads(self.get("/healthz")[1])

    def metrics(self) -> dict[str, float]:
        """/metrics as name -> value (counters, gauges, _sum and _count)."""
        out = {}
        for line in self.get("/metrics")[1].decode().splitlines():
            m = re.fullmatch(r"([A-Za-z_:][\w:]*) (\S+)", line)
            if m:
                out[m.group(1)] = float(m.group(2))
        return out

    def complete(self, rec: Record, prompt: str, *, timeout_s: float | None,
                 prefix_cache: bool = True) -> Record:
        """Send one request and fill ``rec`` with what came back."""
        body = {"prompt": prompt, "max_tokens": rec.asked, "temperature": 0,
                "logprobs": True}
        if timeout_s is not None:
            body["timeout_s"] = timeout_s
        if not prefix_cache:
            body["prefix_cache"] = False
        req = urllib.request.Request(
            self.base + "/v1/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(
                req, timeout=(timeout_s or 0.0) + HTTP_SLACK_S
            ) as r:
                rec.status, raw = r.status, r.read()
        except urllib.error.HTTPError as e:
            rec.status, rec.error = e.code, e.read()[:300].decode(errors="replace")
        except (urllib.error.URLError, OSError) as e:
            rec.status, rec.error = -1, repr(e)
        else:
            out = json.loads(raw)
            choice = out["choices"][0]
            rec.finish = choice["finish_reason"]
            rec.logprobs = (choice.get("logprobs") or {}).get(
                "token_logprobs") or []
            usage = out.get("usage", {})
            rec.n_tokens = usage.get("completion_tokens", 0)
            rec.cached_tokens = usage.get(
                "prompt_tokens_details", {}).get("cached_tokens", 0)
        rec.t_done = time.monotonic()
        return rec


def send_alone(gw: Gateway, prompt: str, asked: int, *, shared: int = 0,
               prefix_cache: bool = True,
               timeout_s: float | None = None) -> Record:
    """One set-up request (a probe or a warm-up), outside every phase."""
    rec = Record("setup", -1, -1, -1, len(prompt), shared, asked,
                 time.monotonic())
    return gw.complete(rec, prompt, timeout_s=timeout_s,
                       prefix_cache=prefix_cache)


class Load:
    """The callers of one run: a pre-roll, then the measured window.

    ``at_open`` runs once, between the two, while no request is in flight;
    it returns nothing and may take its time (scraping counters, starting a
    trace): the window's clock starts after it."""

    def __init__(self, gw: Gateway, spec: dict, seed: int, seconds: float,
                 at_open=lambda: None):
        self.gw, self.spec, self.seed = gw, spec, seed
        self.seconds = seconds
        self.at_open = at_open
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self.t_open = math.nan
        self.t_end = math.nan

    # -- one request ----------------------------------------------------
    def _send(self, phase: str, client: int, turn: traffic.Turn,
              deadline: float, asked: int | None = None,
              t_due: float | None = None) -> Record | None:
        now = time.monotonic()
        left = deadline - now
        if left < MIN_SEND_S:
            return None
        rec = Record(phase, client, turn.session, turn.turn,
                     len(turn.prompt), turn.shared,
                     turn.max_tokens if asked is None else asked, now,
                     t_due=t_due)
        self.gw.complete(rec, turn.prompt, timeout_s=left)
        with self._lock:
            self.records.append(rec)
        if rec.status == -1:
            time.sleep(0.05)  # a dead server must not spin the caller
        return rec

    def _open(self) -> None:
        self.at_open()
        self.t_open = time.monotonic()
        self.t_end = self.t_open + self.seconds

    # -- closed loop ----------------------------------------------------
    def _caller(self, i: int, pre_end: float, barrier: threading.Barrier):
        try:
            self._call(i, pre_end, barrier)
        except threading.BrokenBarrierError:
            pass
        except BaseException:
            barrier.abort()  # the other callers must not wait for this one
            raise

    def _call(self, i: int, pre_end: float, barrier: threading.Barrier):
        j = traffic.script_of_caller(self.spec, self.seed)[i]
        script = traffic.client_script(self.spec, self.seed, j)
        while self._send("preroll", i, next(script), pre_end):
            pass
        barrier.wait()
        # The first answer of script j is cut to (j+1)/clients of its
        # length, so the callers' phases are spread from the first second;
        # and the scripts start STAGGER_S apart, so that the server admits
        # them in the same order in every run.
        n = self.spec["clients"]
        first = next(script)
        time.sleep(max(0.0, self.t_open + j * STAGGER_S - time.monotonic()))
        self._send("window", i, first, self.t_end,
                   asked=max(1, math.ceil(first.max_tokens * (j + 1) / n)))
        while self._send("window", i, next(script), self.t_end):
            pass

    def _run_closed(self) -> None:
        n = self.spec["clients"]
        barrier = threading.Barrier(n, action=self._open)
        pre_end = time.monotonic() + self.spec.get("preroll_s", 0)
        threads = [
            threading.Thread(target=self._caller, args=(i, pre_end, barrier),
                             name=f"caller-{i}")
            for i in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # -- open loop ------------------------------------------------------
    def _arrivals(self, phase: str, script, t0: float, horizon: float):
        """Send ``script``'s turns at their due times from ``t0`` until
        ``t0 + horizon``, each from a thread of its own; wait for all."""
        threads = []
        for k, due in enumerate(traffic.arrival_times(self.spec, horizon)):
            delay = t0 + due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(
                target=self._send,
                args=(phase, k, next(script), t0 + horizon),
                kwargs={"t_due": t0 + due},
            )
            t.start()
            threads.append(t)
        for t in threads:
            t.join()

    def _run_open(self) -> None:
        script = traffic.arrival_script(self.spec, self.seed)
        pre = self.spec.get("preroll_s", 0)
        if pre:
            self._arrivals("preroll", script, time.monotonic(), pre)
        self._open()
        self._arrivals("window", script, self.t_open, self.seconds)

    def run(self) -> list[Record]:
        if self.spec.get("rate_rps"):
            self._run_open()
        else:
            self._run_closed()
        return self.records

"""Traffic from a data file and a seed.

A traffic file (``benchmark/traffic/<mix>.json``) fixes the *work*: a cycle
of sessions, each ``{"shared": tokens, "turns": [[prompt_tokens,
answer_tokens], ...]}``, written out as numbers.  A turn's prompt is the
session's shared bytes followed by its own ``prompt_tokens`` bytes (one byte
is one token under the server's byte tokenizer, plus the BOS it adds), so a
session with ``shared > 0`` asks one document several questions and one with
``shared == 0`` shares nothing.  ``--seed`` draws the bytes and deals the
scripts to the callers; it never changes a length nor the order of the work
inside a script, so every run of a cell serves the same requests in the same
order.  (A seed that permuted the cycle moved ``out_tok_s`` by 7% between
seeds on the chip: which long prompts fall inside the window is the work.)

Script *j* is sessions *j*, *j + clients*, ... of each cycle, entered
``j % len(turns)`` turns into its first session, so that callers do not
change documents in step.  With ``rate_rps`` set the same sessions arrive on a schedule instead
(an open loop; see :func:`arrival_times`).
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
# Printable ASCII without the space run: every byte is one token and no
# prompt is valid UTF-8 by accident only.
_ALPHABET = bytes(range(0x21, 0x7F)).decode()


@dataclass(frozen=True)
class Turn:
    """One request: the prompt and the number of tokens asked."""

    prompt: str
    shared: int       # leading bytes of the prompt other turns also send
    max_tokens: int
    session: int      # index of the session in its (permuted) cycle
    turn: int         # index of the turn in its session


def load(name: str) -> dict:
    """The traffic file ``traffic/<name>.json``, checked."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    check(spec, name)
    return spec


def check(spec: dict, name: str = "traffic") -> None:
    clients = spec.get("clients")
    if not isinstance(clients, int) or clients < 1:
        raise ValueError(f"{name}: 'clients' must be a positive integer")
    sessions = spec.get("sessions")
    if not sessions or len(sessions) % clients:
        raise ValueError(
            f"{name}: 'sessions' must hold a multiple of {clients} sessions"
        )
    for s in sessions:
        if s["shared"] < 0 or not s["turns"]:
            raise ValueError(f"{name}: bad session {s}")
        for p, a in s["turns"]:
            if p < 1 or a < 1:
                raise ValueError(f"{name}: bad turn {[p, a]} in {s}")
    if spec.get("preroll_s", 0) < 0:
        raise ValueError(f"{name}: 'preroll_s' must be >= 0")
    rate = spec.get("rate_rps")
    if rate is not None and rate <= 0:
        raise ValueError(f"{name}: 'rate_rps' must be positive or null")


def lengths(spec: dict) -> list[tuple[int, int, int]]:
    """(shared, prompt, answer) of every request of one cycle, sorted: the
    multiset a seed may not change.  ``prompt`` counts the shared bytes."""
    return sorted(
        (s["shared"], s["shared"] + p, a)
        for s in spec["sessions"] for p, a in s["turns"]
    )


def worst_case_pages(spec: dict, page_size: int) -> int:
    """Pages the pool must hold when every caller has its longest request
    resident and no page is shared (the BOS token counted)."""
    per_request = sorted(
        (math.ceil((1 + shared_prompt + a) / page_size)
         for _, shared_prompt, a in lengths(spec)),
        reverse=True,
    )
    return sum(per_request[: spec["clients"]])


def pool_fits(spec: dict, serve: dict, must_dispatch) -> list[str]:
    """What of the mix does not fit the server its configuration's ``serve``
    asks for (nothing: []).  Every caller has a batch slot and the longest
    request (the BOS token counted) a row.  A pool (``paged_pages`` other
    than 0) holds :func:`worst_case_pages` beside its scratch page, page 0,
    and a run must have taken ``paged_decode``, the family of kernels that
    serve a decode step from pages.  ``paged_pages`` 0 is the server's word
    for no pool: a row's state is the row itself, so there is no page to
    count, no kernel that reads one and no run of pages to cache.  The run
    and ``tests/benchmark`` both ask here, so they cannot drift apart."""
    faults = []
    if spec["clients"] > serve["slots"]:
        faults.append(f"{spec['clients']} callers for {serve['slots']} slots")
    longest = max(p + a for _, p, a in lengths(spec))
    if longest + 1 > serve["max_len"]:
        faults.append(f"the longest request holds {longest + 1} tokens, a "
                      f"row {serve['max_len']}")
    paged = "paged_decode" in must_dispatch
    if serve["paged_pages"] == 0:
        if paged:
            faults.append("no pool (paged_pages 0), and must_dispatch names "
                          "paged_decode")
        if "--prefix-cache" in serve.get("extra_argv", []):
            faults.append("no pool (paged_pages 0), and --prefix-cache")
        return faults
    worst = worst_case_pages(spec, serve["page_size"])
    if worst > serve["paged_pages"] - 1:
        faults.append(f"the mix's worst case of {worst} pages does not fit a "
                      f"pool of {serve['paged_pages']} less its scratch page")
    if not paged:
        faults.append("a pool, and must_dispatch does not name paged_decode")
    return faults


def text(rng: random.Random, n: int) -> str:
    """n bytes, one token each."""
    return "".join(rng.choices(_ALPHABET, k=n))


def cycle(spec: dict, seed: int, index: int) -> list[list[Turn]]:
    """Cycle ``index`` under ``seed``: the sessions in the file's order,
    their bytes drawn.  Deterministic in (file, seed, index)."""
    rng = random.Random(f"{seed}/{index}")
    out = []
    for pos, s in enumerate(spec["sessions"]):
        doc = text(rng, s["shared"])
        out.append([
            Turn(doc + text(rng, p), s["shared"], a, pos, t)
            for t, (p, a) in enumerate(s["turns"])
        ])
    return out


def script_of_caller(spec: dict, seed: int) -> list[int]:
    """Which script each caller walks: a permutation of the callers drawn
    from the seed.  It is all of the order that a seed changes: the work,
    and the order of the work inside each script, are the file's."""
    order = list(range(spec["clients"]))
    random.Random(f"{seed}/callers").shuffle(order)
    return order


def client_script(spec: dict, seed: int, script: int):
    """The endless sequence of turns of script ``script``: sessions
    ``script``, ``script + clients``, ... of cycle after cycle, the first
    session entered ``script % len(turns)`` turns in."""
    clients = spec["clients"]
    index = 0
    while True:
        sessions = cycle(spec, seed, index)[script::clients]
        for n, turns in enumerate(sessions):
            skip = script % len(turns) if index == 0 and n == 0 else 0
            yield from turns[skip:]
        index += 1


def arrival_script(spec: dict, seed: int):
    """Open loop: the turns of cycle after cycle in the file's order."""
    index = 0
    while True:
        for turns in cycle(spec, seed, index):
            yield from turns
        index += 1


def arrival_times(spec: dict, horizon_s: float) -> list[float]:
    """Open loop: the due times, from 0, of requests over ``horizon_s``.
    Evenly spaced at ``rate_rps``; inside a burst (``burst``: ``factor``,
    ``every_s``, ``for_s``) the spacing shrinks by ``factor``.  No draw: the
    schedule is the same under every seed."""
    rate = spec["rate_rps"]
    burst = spec.get("burst")
    out, t = [], 0.0
    while t < horizon_s:
        out.append(t)
        r = rate
        if burst and (t % burst["every_s"]) < burst["for_s"]:
            r = rate * burst["factor"]
        t += 1.0 / r
    return out


def bucket(n: int, floor: int = 8) -> int:
    """The admission width a length pads up to: the batcher's ladder
    (``runtime/shapes.bucket_length``), copied so that the parent imports
    nothing of the program."""
    b = floor
    while b < n:
        b *= 2
    return b


def warmup_turns(spec: dict, page_size: int) -> list[tuple[int, int, int]]:
    """One (shared, prompt, answer) per distinct admission shape of the
    mix: the :func:`bucket` of the prompt's width, of the suffix behind a cached run and of that
    run's pages.  The set-up sends each once, alone."""
    seen: dict[tuple, tuple[int, int, int]] = {}
    for shared, prompt, answer in lengths(spec):
        cached = (shared // page_size) * page_size
        key = (
            bucket(prompt + 1),
            bucket(prompt + 1 - cached) if cached else 0,
            bucket(cached // page_size) if cached else 0,
        )
        # The longest request of a shape stands for it.
        if key not in seen or prompt > seen[key][1]:
            seen[key] = (shared, prompt, answer)
    return sorted(seen.values())

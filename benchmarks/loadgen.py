"""The one general traffic generator, and the HTTP client that plays it.

A traffic mix is a data file of parameters (``traffic/<name>.json``); this
module turns it and ``--seed`` into a schedule, and a later PR adds a mix by
adding a file. Nothing here imports JAX: the client runs in the Serve
driver, which must leave the chip to the replica.

Parameters of a request mix:

``loop``            ``open`` (arrivals on a schedule, whatever the server
                    does) or ``closed`` (``clients`` callers that each send
                    their next request when the last answer ends)
``arrival``         open loop: ``{"process": "poisson", "rate_per_s": r}``.
                    The number of arrivals in a window is fixed at
                    round(rate x seconds), so every seed offers the same
                    amount of work; the arrival times are then sorted
                    uniforms (a Poisson process conditioned on its count).
``prompt_tokens``   a distribution: ``uniform`` (min, max) or ``lognormal``
                    (median, sigma, clipped to min, max). Lengths are the
                    distribution's quantiles, one per stratum and jittered
                    inside it, in an order drawn from the seed: every seed
                    sees the same spread of lengths, in another order.
``repeat_every``    every n-th request of the window, from its first, sends
                    one and the same seeded prompt of ``repeat_prompt_tokens``
                    tokens: equal prompts must give equal answers. Its
                    length is the mix's, not drawn, so that every seed keeps
                    the same lengths (and prefill buckets) in all.
``ramp_s``          seconds of the same traffic before the window opens, so
                    that the window starts in steady state (not measured).
"""

from __future__ import annotations

import asyncio
import json
import math
import time

import numpy as np


# ---------------------------------------------------------------------------
# Schedule
# ---------------------------------------------------------------------------

def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """The distribution's inverse CDF at u in (0, 1)."""
    kind = dist["dist"]
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "lognormal":
        z = np.sqrt(2.0) * _erfinv(2.0 * u - 1.0)  # inverse normal CDF
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(x, dist.get("min", 1), dist.get("max", np.inf))
    raise ValueError(f"unknown distribution {kind!r}")


def _erfinv(y: np.ndarray) -> np.ndarray:
    """Inverse error function (Giles 2012, single-precision polynomial;
    good to ~1e-6, ample for drawing prompt lengths)."""
    y = np.clip(y, -1 + 1e-12, 1 - 1e-12)
    w = -np.log((1.0 - y) * (1.0 + y))
    small = w < 5.0
    ws = np.where(small, w - 2.5, np.sqrt(np.maximum(w, 5.0)) - 3.0)
    p_small = np.polyval([2.81022636e-08, 3.43273939e-07, -3.5233877e-06,
                          -4.39150654e-06, 0.00021858087, -0.00125372503,
                          -0.00417768164, 0.246640727, 1.50140941], ws)
    p_large = np.polyval([-0.000200214257, 0.000100950558, 0.00134934322,
                          -0.00367342844, 0.00573950773, -0.0076224613,
                          0.00943887047, 1.00167406, 2.83297682], ws)
    return np.where(small, p_small, p_large) * y


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths: one quantile per stratum, jittered, in seeded order."""
    u = (np.arange(n) + rng.uniform(0.0, 1.0, n)) / n
    lengths = np.rint(_quantile(dist, np.clip(u, 1e-9, 1 - 1e-9))).astype(int)
    return rng.permutation(np.maximum(lengths, 1))


def arrival_times(arrival: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """n arrival offsets in [0, seconds), sorted."""
    process = arrival.get("process", "poisson")
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    return np.sort(rng.uniform(0.0, seconds, n))


def make_schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """The requests of one run: ``prompts`` (lists of token ids), and for an
    open loop ``due`` (seconds from the window's start; negative inside the
    ramp). A closed loop draws from the list in order for as long as the
    window lasts: it gets ``max_requests_per_s`` x the run's length, which
    the mix sets well above what the system can complete."""
    rng = np.random.default_rng(seed)
    ramp = float(traffic.get("ramp_s", 0.0))
    if traffic["loop"] == "open":
        rate = float(traffic["arrival"]["rate_per_s"])
        n_ramp, n_win = int(round(rate * ramp)), int(round(rate * seconds))
        due = np.concatenate([
            arrival_times(traffic["arrival"], n_ramp, ramp, rng) - ramp,
            arrival_times(traffic["arrival"], n_win, seconds, rng)])
    else:
        n_ramp, n_win = 0, max(int(traffic["clients"]), math.ceil(
            (seconds + ramp) * float(traffic["max_requests_per_s"])))
        due = None
    n = n_ramp + n_win
    every = int(traffic.get("repeat_every", 0))
    repeats = list(range(n_ramp, n, every)) if every else []
    # lengths are drawn for the distinct prompts alone, the ramp's and the
    # window's apart: the window of every seed holds the same lengths
    lengths = iter(np.concatenate([
        draw_lengths(traffic["prompt_tokens"], n_ramp, rng)
        if n_ramp else np.zeros(0, int),
        draw_lengths(traffic["prompt_tokens"], n_win - len(repeats), rng)]))
    same = rng.integers(0, vocab, int(traffic["repeat_prompt_tokens"])
                        ).tolist() if repeats else None
    prompts = [list(same) if i in set(repeats) else
               rng.integers(0, vocab, int(next(lengths))).tolist()
               for i in range(n)]
    return {"prompts": prompts, "due": None if due is None else due.tolist(),
            "n_ramp": n_ramp, "repeats": repeats}


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------

class Record:
    """What the client saw of one request. Times are perf_counter seconds."""

    __slots__ = ("index", "due", "sent", "status", "chunk_times", "text",
                 "error", "done")

    def __init__(self, index: int, due: float):
        self.index, self.due = index, due
        self.sent = self.done = None
        self.status, self.chunk_times, self.text, self.error = 0, [], "", None


async def _request(port: int, path: str, prompt_text: str, rec: Record,
                   timeout_s=None) -> None:
    """One streamed POST over a fresh connection; a JSON line per chunk."""
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps(prompt_text).encode()
        extra = f"x-request-timeout-s: {timeout_s}\r\n" if timeout_s else ""
        writer.write((f"POST {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      f"Content-Type: application/json\r\n{extra}"
                      f"Connection: close\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        rec.sent = time.perf_counter()
        await writer.drain()
        status_line = await reader.readline()
        rec.status = int(status_line.split()[1])
        chunked, length = False, 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.decode().partition(":")
            if key.strip().lower() == "transfer-encoding":
                chunked = "chunked" in value.lower()
            elif key.strip().lower() == "content-length":
                length = int(value)
        if not chunked:
            rec.text = (await reader.readexactly(length)).decode()
            return
        pieces = []
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size)
            await reader.readexactly(2)
            now = time.perf_counter()
            for line in data.decode().splitlines():
                if not line.strip():
                    continue
                frame = json.loads(line)
                if isinstance(frame, dict):  # the terminal error frame
                    rec.status, rec.error = 500, json.dumps(frame)[:500]
                else:
                    pieces.append(frame)
                    rec.chunk_times.append(now)
        rec.text = "".join(pieces)
    except Exception as e:  # noqa: BLE001 — recorded, counted as failed
        rec.error = f"{type(e).__name__}: {e}"
        rec.status = rec.status if rec.status not in (0, 200) else 599
    finally:
        rec.done = time.perf_counter()
        if writer is not None:
            writer.close()


def encode_prompt(ids) -> str:
    return " ".join(str(int(i)) for i in ids)


async def _play(port: int, traffic: dict, schedule: dict, seconds: float,
                timeout_s) -> dict:
    path = traffic["path"]
    prompts = [encode_prompt(p) for p in schedule["prompts"]]
    records, tasks = [], []
    ramp = float(traffic.get("ramp_s", 0.0))
    t_open = time.perf_counter() + ramp  # the window's start
    if traffic["loop"] == "open":
        late = []
        for i, due in enumerate(schedule["due"]):
            wait = t_open + due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            rec = Record(i, t_open + due)
            late.append(time.perf_counter() - rec.due)
            records.append(rec)
            tasks.append(asyncio.ensure_future(
                _request(port, path, prompts[i], rec, timeout_s)))
        await asyncio.gather(*tasks)
        t_close = t_open + seconds
    else:
        late = [0.0]
        feed = iter(range(len(prompts)))
        t_close = t_open + seconds

        async def client():
            for i in feed:
                if time.perf_counter() >= t_close:
                    return
                rec = Record(i, time.perf_counter())
                records.append(rec)
                await _request(port, path, prompts[i], rec, timeout_s)

        await asyncio.gather(*[client() for _ in range(int(traffic["clients"]))])
    return {"records": records, "t_open": t_open, "t_close": t_close,
            "late_s": late}


def play(port: int, traffic: dict, schedule: dict, seconds: float,
         on_open=None, timeout_s=None) -> dict:
    """Play the schedule against the front door from one event loop in this
    thread. ``on_open(t_open)`` runs in a helper thread when the window
    opens (counter snapshots, the profiler)."""
    import threading

    ramp = float(traffic.get("ramp_s", 0.0))
    if on_open is not None:
        timer = threading.Timer(ramp, on_open)
        timer.daemon = True
        timer.start()
    return asyncio.run(_play(port, traffic, schedule, seconds, timeout_s))


def one_request(port: int, path: str, prompt_ids, timeout_s=None) -> Record:
    """A single request outside any schedule (warm-up)."""
    rec = Record(-1, time.perf_counter())
    asyncio.run(_request(port, path, encode_prompt(prompt_ids), rec, timeout_s))
    return rec

"""The load generator: one general program that reads a traffic mix (a data
file of parameters) and drives `POST <path>` with it. Standard library
only — it never imports jax, so it can run as a child of the process that
holds the chip without touching it or sharing its interpreter lock.

    python3 benchmark/loadgen.py < job.json > records.json

`job`: {"port", "path", "traffic": {...}, "seed", "t0", "seconds",
"vocab_size", "max_ctx"}; `t0` is on `time.monotonic()`'s clock, which
parent and child share. Sending starts `lead_in_s` before `t0` so that the
window [t0, t0 + seconds) opens on a system already in its steady state,
and nothing new is sent once it has closed; requests in flight are then
waited for (`drain_s` at most).

A mix is `{"loop": "open", "rate_per_s": r}` — Poisson arrivals, each sent
on its schedule whether or not earlier ones finished, and timed from when
it was DUE — or `{"loop": "closed", "clients": n}` — n callers that each
send their next request when the last completes. `prompt_tokens` and
`output_tokens` are `{"dist": "lognormal", "median", "sigma", "min",
"max"}` or `{"dist": "uniform", "min", "max"}`.

Every seed gets the SAME multiset of lengths and of inter-arrival gaps —
the distribution's evenly spaced quantiles — in another order, and other
token ids: runs differ in order and content, not in the amount of work.
Token ids are uniform over the vocabulary, so no two prompts share a
prefix.
"""
from __future__ import annotations

import http.client
import json
import math
import random
import statistics
import sys
import threading
import time
from typing import Dict, List, Optional

_NORMAL = statistics.NormalDist()


def quantiles(dist: dict, n: int) -> List[int]:
    """`n` evenly spaced quantiles of a length distribution, as whole
    numbers clipped to [min, max]."""
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        elif dist["dist"] == "uniform":
            x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        else:
            raise ValueError(f"unknown distribution {dist['dist']!r}")
        out.append(int(min(max(math.floor(x), dist["min"]), dist["max"])))
    return out


def _rng(seed: int, stream: int) -> random.Random:
    return random.Random(int(seed) * 1000003 + stream)


def plan(traffic: dict, seed: int, seconds: float, max_ctx: int) -> List[dict]:
    """The requests of one run, a function of the mix, the seed and the
    window's length alone: `{"id", "client", "due", "prompt_tokens",
    "max_tokens"}`. Open loop: `due` is seconds from t0 (negative in the
    lead-in), `client` None. Closed loop: `due` None; each client works
    down its own list in order."""
    lead = float(traffic.get("lead_in_s", 0.0))
    if traffic["loop"] == "open":
        n = int(round(traffic["rate_per_s"] * (lead + seconds)))
        clients = [None] * n
    else:
        per = int(traffic["requests_per_client"])
        n = traffic["clients"] * per
        clients = [i % traffic["clients"] for i in range(n)]
    prompts = quantiles(traffic["prompt_tokens"], n)
    outputs = quantiles(traffic["output_tokens"], n)
    _rng(seed, 1).shuffle(prompts)
    _rng(seed, 2).shuffle(outputs)
    due: List[Optional[float]] = [None] * n
    if traffic["loop"] == "open":
        gaps = [-math.log(1.0 - (i + 0.5) / n) / traffic["rate_per_s"]
                for i in range(n)]
        _rng(seed, 3).shuffle(gaps)
        t = -lead
        for i, g in enumerate(gaps):
            t += g
            due[i] = t
    return [{"id": i, "client": clients[i], "due": due[i],
             "prompt_tokens": prompts[i],
             "max_tokens": max(1, min(outputs[i], max_ctx - prompts[i]))}
            for i in range(n)]


def prompt_ids(seed: int, request_id: int, length: int,
               vocab_size: int) -> List[int]:
    """The prompt of one request: `length` ids uniform over the
    vocabulary, from the seed and the request's id alone."""
    return _rng(seed, 1000 + request_id).choices(range(vocab_size), k=length)


class Sender:
    def __init__(self, job: dict):
        self.job = job
        self.t0 = float(job["t0"])
        self.records: List[dict] = []
        self.lock = threading.Lock()

    def now(self) -> float:
        return time.monotonic() - self.t0

    def one(self, req: dict) -> dict:
        """Send one request and read its stream; every time is seconds
        from t0. The record says what came back, never what should have."""
        job = self.job
        rec = {"id": req["id"], "client": req["client"], "due": req["due"],
               "prompt_tokens": req["prompt_tokens"],
               "max_tokens": req["max_tokens"], "status": None,
               "tokens": [], "stamps": [], "closing": None, "error": None}
        body = json.dumps({
            "prompt": prompt_ids(job["seed"], req["id"],
                                 req["prompt_tokens"], job["vocab_size"]),
            "max_tokens": req["max_tokens"], "temperature": 0.0,
            "stream": True}).encode()
        rec["sent"] = self.now()
        with self.lock:     # on record from the send: one that never ends
            self.records.append(rec)    # is reported, not lost
        conn = http.client.HTTPConnection("127.0.0.1", job["port"],
                                          timeout=job["request_timeout_s"])
        try:
            conn.request("POST", job["path"], body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = resp.read(300).decode("utf-8", "replace")
            else:
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    at = self.now()
                    doc = json.loads(line)
                    if "token" in doc:
                        rec["tokens"].append(doc["token"])
                        rec["stamps"].append(at)
                    elif doc.get("done"):
                        rec["closing"] = {k: doc.get(k) for k in (
                            "ttft_s", "finish_reason", "completion_tokens",
                            "phases", "error")}
                        rec["done"] = at
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            conn.close()
        return rec

    def sleep_until(self, t: float):
        while True:
            left = t - self.now()
            if left <= 0:
                return
            time.sleep(min(left, 0.5))

    def run_open(self, requests: List[dict], seconds: float):
        threads = []
        for req in requests:
            if req["due"] >= seconds:
                break
            self.sleep_until(req["due"])
            th = threading.Thread(target=self.one, args=(req,), daemon=True)
            th.start()
            threads.append(th)
        return threads

    def run_closed(self, requests: List[dict], seconds: float, lead: float):
        by_client: Dict[int, List[dict]] = {}
        for r in requests:
            by_client.setdefault(r["client"], []).append(r)

        def client(todo: List[dict]):
            self.sleep_until(-lead)
            for req in todo:
                if self.now() >= seconds:
                    return
                self.one(req)

        threads = [threading.Thread(target=client, args=(todo,), daemon=True)
                   for todo in by_client.values()]
        for th in threads:
            th.start()
        return threads


def main() -> int:
    job = json.load(sys.stdin)
    traffic = job["traffic"]
    job.setdefault("request_timeout_s", 120.0)
    requests = plan(traffic, job["seed"], job["seconds"], job["max_ctx"])
    sender = Sender(job)
    lead = float(traffic.get("lead_in_s", 0.0))
    if traffic["loop"] == "open":
        threads = sender.run_open(requests, job["seconds"])
    else:
        threads = sender.run_closed(requests, job["seconds"], lead)
    sender.sleep_until(job["seconds"])
    deadline = time.monotonic() + float(traffic.get("drain_s", 60.0))
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    unfinished = sum(th.is_alive() for th in threads)
    with sender.lock:
        records = sorted((dict(r) for r in sender.records),
                         key=lambda r: r["id"])
    for r in records:
        if r["status"] is None and r["error"] is None:
            r["error"] = "unfinished when the drain ended"
    json.dump({"records": records, "unfinished": unfinished,
               "planned": len(requests)}, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The load generator: a child process that never imports JAX.

It shares no interpreter lock with the scheduler, so its lateness is its
own.  The parent writes a plan file and starts
``python loadgen.py <plan.json> <result.json>``; the child sends the
plan's requests to ``POST /generate`` with ``"stream": true``, stamps
every streamed token on its own monotonic clock (the same clock as the
parent's: CLOCK_MONOTONIC is system-wide) and writes one record per
request.  The streaming client and the token clock are copied from
``tools/serving_loadgen.py`` (``_http_generate_stream``, ``_TokenClock``).

Plan: ``{"url", "loop": "open"|"closed", "t0", "timeout_s", "requests":
[{"due"?, "prompt": [...], "max_new_tokens"}]}`` and, for a closed loop,
``"workers", "block", "warm_blocks", "seconds", "tail_s", "t_stop"``.
``t0`` is the monotonic time of the schedule's zero; an open loop sends
request i at ``t0 + due``.  A closed loop starts ``workers`` threads from
``t0``, 20 ms apart, that take the requests in order, round and round.

A closed loop's window is cut by the work, not by the clock, as a trainer's
is cut at a step: the stream comes in blocks of ``block`` requests that
are the same work in every run; the window opens when the last request of
block ``warm_blocks`` gets its first token (its prefill is done, and so
are those before it) and closes at the first such block end
``seconds`` or more later.  The child prints both times as they happen,
one JSON line each (``{"mark": "t_open", "t": ...}``), keeps the traffic
up for ``tail_s`` more seconds and stops; ``t_stop`` is when it gives up.
"""
from __future__ import annotations

import http.client
import itertools
import json
import sys
import threading
import time
from urllib.parse import urlparse


STAGGER_S = 0.02


def stream_generate(host: str, port: int, body: bytes,
                    timeout_s: float, on_first=None) -> dict:
    """One streamed POST /generate.  Returns ``{"outcome", "sent",
    "arrivals": [...], "tokens": [...], "final": {...}|None}``; outcome
    is ``ok``, ``shed`` (an explicit 503 / overloaded) or ``failed``."""
    rec = {"outcome": "failed", "sent": time.monotonic(), "arrivals": [],
           "tokens": [], "final": None, "detail": None}
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            payload = resp.read()
            rec["detail"] = f"http {resp.status}: {payload[:200]!r}"
            if resp.status == 503:
                rec["outcome"] = "shed"
            return rec
        for raw in resp:
            now = time.monotonic()
            line = raw.strip()
            if not line:
                continue
            doc = json.loads(line)
            if doc.get("done"):
                rec["final"] = doc
                rec["done"] = now
                break
            if "token" in doc:
                if on_first is not None and not rec["arrivals"]:
                    on_first(now)
                rec["arrivals"].append(now)
                rec["tokens"].append(doc["token"])
        final = rec["final"]
        if final is None:
            rec["detail"] = "stream ended without a summary line"
        elif "error" in final:
            rec["detail"] = str(final.get("detail"))
            if final.get("error") == "overloaded":
                rec["outcome"] = "shed"
        else:
            rec["outcome"] = "ok"
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["detail"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def _slim(rec: dict, req: dict, index: int, due: float) -> dict:
    """What the parent needs of one request; the summary line is cut to
    the fields the metrics read."""
    final = rec.pop("final") or {}
    want = req["max_new_tokens"]
    rec.update(
        index=index, due=due, prompt_len=len(req["prompt"]), want=want,
        stream_matches_summary=final.get("tokens") == rec["tokens"],
        complete=len(rec["tokens"]) == want,
        engine_ttft_ms=final.get("ttft_ms"),
        engine_queue_wait_ms=final.get("queue_wait_ms"),
        engine_prefill_ms=final.get("prefill_ms"),
        finish=final.get("finish"))
    del rec["tokens"]
    return rec


def run(plan: dict) -> list:
    u = urlparse(plan["url"])
    host, port = u.hostname, u.port
    reqs = plan["requests"]
    bodies = [json.dumps({"prompt": r["prompt"],
                          "max_new_tokens": r["max_new_tokens"],
                          "stream": True}).encode() for r in reqs]
    t0, timeout_s = plan["t0"], plan["timeout_s"]
    out, lock = [], threading.Lock()

    def one(i, due, on_first=None):
        # a closed loop takes its stream round and round
        k = i % len(reqs)
        rec = stream_generate(host, port, bodies[k], timeout_s, on_first)
        rec = _slim(rec, reqs[k], i, due)
        with lock:
            out.append(rec)

    threads = []
    if plan["loop"] == "open":
        for i, r in enumerate(reqs):
            due = t0 + r["due"]
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=one, args=(i, due), daemon=True)
            t.start()
            threads.append(t)
    else:
        cursor = itertools.count()
        block, warm = plan["block"], plan["warm_blocks"] * plan["block"]
        marks = {"t_stop": plan["t_stop"]}

        def mark(name, t):
            marks[name] = t
            print(json.dumps({"mark": name, "t": t}), flush=True)

        def first_token(i, now):
            """The window's edges: first tokens of requests that end a
            block."""
            if (i + 1) % block:
                return
            with lock:
                if "t_open" not in marks:
                    if i + 1 >= warm:
                        mark("t_open", now)
                elif "t_close" not in marks \
                        and now - marks["t_open"] >= plan["seconds"]:
                    mark("t_close", now)
                    marks["t_stop"] = min(marks["t_stop"],
                                          now + plan["tail_s"])

        def worker():
            while True:
                with lock:
                    i = next(cursor)
                    t_stop = marks["t_stop"]
                now = time.monotonic()
                if now >= t_stop:
                    return
                # due when the worker came free
                one(i, now, lambda t, i=i: first_token(i, t))

        for k in range(plan["workers"]):
            # staggered, so that the first requests arrive in their order
            # and every run serves the same sequence
            delay = t0 + k * STAGGER_S - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=worker, daemon=True)
            t.start()
            threads.append(t)
    for t in threads:
        t.join(timeout_s + 30.0)
    alive = sum(t.is_alive() for t in threads)
    if alive:
        raise RuntimeError(f"{alive} request threads still alive")
    return sorted(out, key=lambda r: r["index"])


def main(argv) -> int:
    plan_path, result_path = argv[1], argv[2]
    with open(plan_path) as f:
        plan = json.load(f)
    records = run(plan)
    with open(result_path, "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    if "jax" in sys.modules:
        raise SystemExit("loadgen must not import jax")
    sys.exit(main(sys.argv))

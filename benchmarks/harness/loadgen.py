"""The load generator: a child process that never imports JAX.

It is started before the parent touches JAX, so it neither holds the chip
nor shares the GIL with the engine's loop thread and the HTTP handlers.
Protocol, over the child's stdin/stdout, one JSON object a line:

    parent -> child   {"base": "http://127.0.0.1:PORT", "t0": <monotonic>,
                       "seconds": S, "drain_s": D, "schedule": {...}}
    child  -> parent  {"records": [...], "observed_until": <monotonic>}

``t0`` is on the machine's monotonic clock, which parent and child share.
Open loop: request i is sent at ``t0 + due_i`` whatever came before; the
record keeps both the due time and when it really went, so a starved
generator shows as lateness and not as a fast server. Closed loop:
``clients`` threads each send the next request of the list as soon as
their last one ended, until the window closes. After the window nothing
new is sent; requests in flight are read to their end, or until
``drain_s`` past the close, when the child hangs up and marks them
``cut``. Below capacity (an open-loop cell) every answer comes inside a
generous drain and a cut one is a failure; a closed loop keeps the
system full by design, so the requests in flight at the close are cut
and are neither served nor failed.

Every request is ``POST /generate/stream`` (SSE), greedy, with its own
``max_tokens``. The client stamps the head frame, every token frame and
the terminal as the line arrives.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from typing import Any
from urllib.parse import urlparse


def stream_one(host: str, port: int, req: dict[str, Any], due: float,
               deadline: float) -> dict[str, Any]:
    """Send one request and stamp its frames. Never raises: a failure is a
    record with ``error`` set."""
    rec: dict[str, Any] = {
        "index": req["index"], "due": due, "sent": None, "head": None,
        "token_ts": [], "tokens": [], "end": None, "finish_reason": None,
        "status": None, "error": None, "request_id": None,
        "prompt_tokens": req["prompt_tokens"], "max_tokens": req["max_tokens"],
        "completion_tokens": None, "cut": False,
    }
    body = json.dumps({"prompt": req["prompt"], "max_tokens": req["max_tokens"],
                       "temperature": 0.0}).encode()
    conn = http.client.HTTPConnection(host, port, timeout=max(deadline - time.monotonic(), 0.0) + 0.5)
    try:
        rec["sent"] = time.monotonic()
        conn.request("POST", "/generate/stream", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = f"status {resp.status}: {resp.read(200)!r}"
            return rec
        raw_tokens: list[bytes] = []
        for raw in resp:
            if not raw.startswith(b"data: "):
                continue
            now = time.monotonic()
            if now > deadline:
                # observation is over: hang up (the server cancels the
                # request) and say that this one was cut, not that it failed
                rec["cut"] = True
                break
            # no read waits past the end of observation
            conn.sock.settimeout(max(deadline - now, 0.0) + 0.5)
            payload = raw[6:].strip()
            if payload.startswith(b'{"token"'):
                rec["token_ts"].append(now)
                raw_tokens.append(payload)
            elif payload == b"[DONE]":
                break
            else:
                frame = json.loads(payload)
                if "id" in frame and rec["head"] is None:
                    rec["head"], rec["request_id"] = now, frame["id"]
                elif "finish_reason" in frame:
                    rec["finish_reason"] = frame["finish_reason"]
                    rec["completion_tokens"] = frame.get("usage", {}).get("completion_tokens")
                elif "error" in frame:
                    rec["error"] = f"{frame.get('status')}: {frame['error']}"
        rec["end"] = time.monotonic()
        rec["tokens"] = [json.loads(p)["token"] for p in raw_tokens]
        if rec["finish_reason"] is None and rec["error"] is None and not rec["cut"]:
            rec["error"] = "stream ended without a terminal frame"
    except TimeoutError:
        rec["end"] = time.monotonic()
        if rec["end"] >= deadline and rec["status"] == 200:
            rec["cut"] = True  # still queued or decoding when observation ended
        else:
            rec["error"] = "TimeoutError: no answer"
    except Exception as exc:  # the boundary: a failed request is a record
        rec["error"] = f"{type(exc).__name__}: {exc}"
        rec["end"] = time.monotonic()
    finally:
        conn.close()
    return rec


def run(cmd: dict[str, Any]) -> dict[str, Any]:
    url = urlparse(cmd["base"])
    host, port = url.hostname, url.port
    t0, seconds = float(cmd["t0"]), float(cmd["seconds"])
    t1 = t0 + seconds
    deadline = t1 + float(cmd.get("drain_s", 60.0))
    schedule = cmd["schedule"]
    requests = schedule["requests"]
    records: list[dict[str, Any]] = []
    mu = threading.Lock()

    def keep(rec: dict[str, Any]) -> None:
        with mu:
            records.append(rec)

    threads: list[threading.Thread] = []
    if schedule["loop"] == "open":
        def one(req: dict[str, Any]) -> None:
            keep(stream_one(host, port, req, t0 + req["due"], deadline))

        for req in requests:
            wait = t0 + req["due"] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            th = threading.Thread(target=one, args=(req,), daemon=True)
            th.start()
            threads.append(th)
    else:
        cursor = iter(requests)

        def client() -> None:
            while True:
                with mu:
                    req = next(cursor, None)
                now = time.monotonic()
                if req is None or now >= t1:
                    return
                # a closed-loop request is due when its client came free
                keep(stream_one(host, port, req, max(now, t0), deadline))

        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        for _ in range(int(schedule["clients"])):
            th = threading.Thread(target=client, daemon=True)
            th.start()
            threads.append(th)
    for th in threads:
        th.join(timeout=max(deadline - time.monotonic(), 0.0) + 5.0)
    with mu:
        done = sorted(records, key=lambda r: r["index"])
    return {"records": done, "observed_until": time.monotonic()}


def main() -> int:
    line = sys.stdin.readline()
    if not line.strip():
        return 0  # the parent gave up before the window: nothing to do
    out = run(json.loads(line))
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Closed-loop load: ``clients`` threads issue a fixed list of requests to
``POST /deploy`` and time each request from this side.

Standard library only: this process never imports jax, so it neither holds
the chip nor shares the server's interpreter lock. It reads one JSON line on
stdin (``url``, ``clients``, ``seconds``, ``bodies``), prints
``ready``, waits for a ``go`` line, then runs the window: it starts the
clock, issues the requests in order until ``seconds`` have passed, lets
the requests in flight finish, and stops the clock at the last completion.
It prints one JSON line per request issued, then a ``window`` line.
"""
import json
import sys
import threading
import time
import urllib.error
import urllib.request


def post(url: str, body: bytes, timeout: float = 300.0):
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()
    except (urllib.error.URLError, OSError) as e:
        return 0, f"{type(e).__name__}: {e}"


def main() -> int:
    job = json.loads(sys.stdin.readline())
    url = job["url"].rstrip("/") + "/deploy"
    bodies = [b.encode() for b in job["bodies"]]
    seconds = float(job["seconds"])
    lock = threading.Lock()
    cursor = [0]
    records = []

    def worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(bodies) or time.perf_counter() >= t_stop:
                    return
                cursor[0] = i + 1
            t0 = time.perf_counter()
            code, text = post(url, bodies[i])
            t1 = time.perf_counter()
            with lock:
                records.append((i, t0, t1, code, text))

    threads = [threading.Thread(target=worker)
               for _ in range(int(job["clients"]))]
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 2
    t_start = time.perf_counter()
    t_stop = t_start + seconds
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = max((r[2] for r in records), default=t_start)
    for i, t0, t1, code, text in sorted(records):
        print(json.dumps({"i": i, "t0": t0 - t_start, "t1": t1 - t_start,
                          "code": code, "body": text}))
    print(json.dumps({"window": t_end - t_start,
                      "exhausted": cursor[0] >= len(bodies)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

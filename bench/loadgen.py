"""Closed-loop load generator for ``POST /v1/query``.

  python bench/loadgen.py SPEC.json OUT.json

Runs in a process of its own that imports neither JAX nor the program,
so its threads never share the service's interpreter lock. Each session
is one thread on one keep-alive connection: it sends its next query only
when the previous answer has come back, as an analyst or a CI job does.
Latency is taken here, at the client, from send to the full response.

``SPEC.json`` holds ``port``, ``mix`` (a traffic name), ``seed``,
``stream``, ``t_start``/``t_end``/``n_ranks`` of the store, and either
``seconds`` (send for that long, then wait for every answer) or
``queries`` (an explicit list of spec objects, spread over the sessions
and each sent once) or ``per_session`` (that many queries each).
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import sys
import threading
import time
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import workload  # noqa: E402

ANSWER_TIMEOUT_S = 240.0
# how an answer was produced, not what it says: left out of its digest
PROVENANCE = ("cache_hit", "recomputed_shards", "partial_hits",
              "shards_pruned", "rows_scanned", "rows_filtered",
              "provenance", "inflight_hit")


def post(conn: http.client.HTTPConnection, spec: Dict):
    body = json.dumps(spec).encode()
    conn.request("POST", "/v1/query", body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    return resp.status, json.loads(data) if data else {}


def run(spec: Dict) -> Dict:
    mix = workload.load_traffic(spec["mix"])
    n = int(mix["sessions"])
    records: List[List[Dict]] = [[] for _ in range(n)]
    answers: Dict[str, Dict] = {}       # digest -> answer, kept once
    answers_lock = threading.Lock()
    seen, seen_lock = set(), threading.Lock()
    t_begin = time.monotonic()
    deadline = (t_begin + float(spec["seconds"])
                if spec.get("seconds") is not None else None)
    explicit = spec.get("queries")

    def queries_of(s: int):
        if explicit is not None:
            yield from explicit[s::n]
            return
        gen = workload.session_queries(
            mix, spec["seed"], spec["stream"], s, spec["t_start"],
            spec["t_end"], spec["n_ranks"])
        count = spec.get("per_session")
        for i, q in enumerate(gen):
            if count is not None and i >= count:
                return
            if mix.get("cold"):
                key = workload.spec_key(q)
                with seen_lock:
                    if key in seen:
                        continue
                    seen.add(key)
            yield q

    def session(s: int) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", spec["port"],
                                          timeout=ANSWER_TIMEOUT_S)
        for q in queries_of(s):
            t_send = time.monotonic()
            if deadline is not None and t_send >= deadline:
                break
            rec = {"session": s, "spec": q, "t_send": t_send}
            try:
                status, body = post(conn, q)
            except (OSError, http.client.HTTPException, ValueError) as e:
                status, body = -1, {"error": f"{type(e).__name__}: {e}"}
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", spec["port"], timeout=ANSWER_TIMEOUT_S)
            rec["t_done"] = time.monotonic()
            rec["status"] = status
            if status == 200:
                res = body["results"][0]
                what = {k: v for k, v in res.items() if k not in PROVENANCE}
                digest = hashlib.sha1(json.dumps(
                    what, sort_keys=True).encode()).hexdigest()
                with answers_lock:
                    answers.setdefault(digest, what)
                rec["digest"] = digest
                rec["cache_hit"] = bool(res.get("cache_hit"))
                rec["inflight_hit"] = bool(res.get("inflight_hit"))
                rec["rows_scanned"] = int(res.get("rows_scanned", 0))
                tick = body.get("tick") or {}
                rec["fused_width"] = int(tick.get("fused_width", 0))
                rec["evicted"] = int(tick.get("evicted", 0))
            else:
                rec["error"] = body.get("error")
            records[s].append(rec)
        conn.close()

    threads = [threading.Thread(target=session, args=(s,), daemon=True)
               for s in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    flat = sorted((r for per in records for r in per),
                  key=lambda r: r["t_send"])
    return {"t_begin": t_begin,
            "t_close": deadline if deadline is not None else
            time.monotonic(),
            "t_end": time.monotonic(), "records": flat,
            "answers": answers}


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    out = run(spec)
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

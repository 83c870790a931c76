"""Closed loop: clients that each wait for a reply before sending again.

One RPCClient, in this thread (`clients` is 1 in every mix so far).
The loop sends whole cycles of the mix: a cycle started before the
deadline is finished, and none starts after it, so every run measures
the same work per count whatever the cycle's classes cost.
"""
from __future__ import annotations

import time

from loops import Record, Window
from traffic import closed_requests

TIMEOUT_S = 300.0


def drive(host: str, port: int, mix: dict, seed: int,
          seconds: float) -> Window:
    from repro.serve.rpc import RPCClient, RPCError

    if int(mix.get("clients", 1)) != 1:
        raise ValueError("the closed loop drives one client")
    client = RPCClient(host, port, timeout=TIMEOUT_S)
    try:
        t0 = time.perf_counter()
        win = Window(t0=t0, deadline=t0 + seconds)
        for pos, req in closed_requests(mix, seed):
            now = time.perf_counter()
            if pos == 0 and now >= win.deadline:
                break
            rec = Record(req.cls, req.tenant, req.spec, due=now, sent=now)
            win.records.append(rec)
            try:
                rec.result = client.result(client.submit(req.spec))
            except RPCError as e:
                rec.error = str(e)
            rec.done = time.perf_counter()
        win.end = time.perf_counter()
    finally:
        client.close()
    return win

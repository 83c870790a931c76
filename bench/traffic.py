"""The one traffic generator: a mix's JSON file in, requests out.

Every request is a pattern class of the mix, relabelled by a random
permutation of its vertices (and its edge list shuffled), so the server
has to canonicalize each one; `tenant` names the tenant that sends it.

Closed mixes (`kind: "closed"`) send the `cycle` of classes over and
over; the loop sends the next request when the last one is answered.
The seed changes how each request is labelled, not how much work there
is.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from graphgen import seed_sequence


@dataclass
class Request:
    cls: str              # the mix's class name
    tenant: str
    spec: dict            # the RPC submit spec (serve/rpc.py wire format)


def classes(mix: dict) -> dict:
    return {c["name"]: c for c in mix["classes"]}


def relabel(cls: dict, tenant: str, rng: np.random.Generator) -> dict:
    k = int(cls["n"])
    perm = rng.permutation(k)
    edges = [[int(perm[u]), int(perm[v])] for u, v in cls["edges"]]
    edges = [e[::-1] if rng.random() < 0.5 else e for e in edges]
    edges = [edges[i] for i in rng.permutation(len(edges))]
    return {"pattern": {"n": k, "edges": edges, "name": cls["name"]},
            "use_iep": bool(cls.get("use_iep", False)), "tenant": tenant}


def closed_requests(mix: dict, seed: int):
    """Endless iterator of (cycle position, Request) for a closed mix."""
    cls = classes(mix)
    rng = np.random.default_rng(seed_sequence(seed, "traffic"))
    while True:
        for i, name in enumerate(mix["cycle"]):
            yield i, Request(name, "default",
                             relabel(cls[name], "default", rng))

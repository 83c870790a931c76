"""Load drivers, one module per mix `kind`.  Each exposes
`drive(host, port, mix, seed, seconds) -> Window`."""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field


@dataclass
class Record:
    """One request as the client saw it (host clock, perf_counter s)."""

    cls: str
    tenant: str
    spec: dict
    due: float                    # when it was due to be sent
    sent: float | None = None     # when its submit frame went out
    done: float | None = None     # when its result frame came back
    result: dict | None = None    # the wire result, None if none came
    error: str | None = None


@dataclass
class Window:
    t0: float                     # the window opened
    deadline: float               # t0 + seconds
    records: list = field(default_factory=list)
    end: float | None = None      # when the last answer came back


def driver(kind: str):
    if not kind.isidentifier():
        raise ValueError(f"bad loop kind {kind!r}")
    return importlib.import_module(f"{__name__}.{kind}")

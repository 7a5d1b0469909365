"""The one traffic generator: a traffic file of parameters, a deployment's
configuration and a seed give the requests of a run.

A traffic file (``bench/traffic/<mix>.json``) names a request kind and
gives its parameters; the configuration (``bench/configs/<config>.json``)
gives the tables' sizes, the input-size profile and the executor.  A
request kind is a module of its own, ``bench/kinds/<kind>.py``, found by
that name.  It exports

``setup(mix, svc)``       make the data from ``mix.rng``, hand the service
                          what it keeps across requests, and return the
                          requests the window cycles over;
``warm(mix)``             the requests that cover every program the window
                          uses, served once in set-up;
``serve(mix, svc, req)``  one request through the service: its answer.

A new mix of a kind that is there is a data file alone; a new kind adds
its module and edits no file.  The seed makes the table rows and the order
in which the window cycles over the requests.  Sizes and layouts come from
fixed seeds in the files, so every seed asks for the same work and finds
every program that set-up compiled.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Mix", "Request", "rows", "sizes"]


def sizes(profile: dict, m: int, q: float) -> np.ndarray:
    """The input sizes a configuration states, from its fixed seed."""
    kind = profile["kind"]
    if kind == "zipf":
        rng = np.random.default_rng(profile["seed"])
        w = rng.zipf(profile["a"], m).astype(np.float64) / profile["scale"]
        return np.clip(w, profile["lo"], profile["hi_q"] * q)
    if kind == "powerlaw":
        w = 1.0 / np.arange(1, m + 1) ** profile["exponent"]
        w = np.clip(w / w.max(), None, profile["hi_q"] * q)
        np.random.default_rng(profile["seed"]).shuffle(w)
        return w
    raise ValueError(f"unknown size profile {kind!r}")


def rows(rng: np.random.Generator, m: int, d: int, dtype: str) -> np.ndarray:
    return rng.standard_normal((m, d), dtype=np.float32).astype(dtype, copy=False)


@dataclasses.dataclass(frozen=True)
class Request:
    key: int                  # pool entry or tile number
    a: np.ndarray             # row operand (host rows, as sent)
    b: np.ndarray             # column operand
    origin: tuple             # global (row, column) of the answer's [0, 0]
    same_table: bool          # rows and columns index one table: its
                              # diagonal (no self-pairs) answers 0


class Mix:
    """Set-up state and request stream of one run; ``kind`` is the module
    of the traffic file's request kind."""

    def __init__(self, config: dict, spec: dict, seed: int, kind):
        self.config, self.spec, self.kind = config, spec, kind
        self.rng = np.random.default_rng(seed)
        self.state: dict = {}                  # what the kind keeps
        self.requests: list[Request] = []
        self._order: list[int] = []
        self._pos = 0

    def setup(self, svc) -> None:
        """Make the data and hand the service its state.  Counted as
        set-up."""
        self.requests = self.kind.setup(self, svc)
        self._order = [int(k) for k in self.rng.permutation(len(self.requests))]

    def warm_requests(self) -> list[Request]:
        return self.kind.warm(self)

    def next_request(self) -> Request:
        """The window's requests: those of set-up, cycled in seed order."""
        req = self.requests[self._order[self._pos % len(self._order)]]
        self._pos += 1
        return req

    def serve(self, svc, req: Request) -> np.ndarray:
        """One request through the service, its answer fetched to the
        client."""
        return np.asarray(self.kind.serve(self, svc, req))

    def entries(self, req: Request) -> int:
        """Result entries a request returns."""
        return int(req.a.shape[0]) * int(req.b.shape[0])

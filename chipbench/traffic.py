"""The one traffic generator: turns a mix file (``traffic/<name>.json``) into
the requests of a window and hands them to the mix's arrival process
(``arrivals/<process>.py``), which says when each is due.

A mix holds an arrival process with its parameters and classes of requests:
LIMIT (exemplar) classes and online-aggregate (AVG) classes; nothing in it is
code.  The set of requests a window holds depends only on
the mix, the configuration and the number the arrival process asks for; the
seed permutes their order.  Every seed therefore offers the same work, and
runs on different seeds differ by the table the seed generates and by arrival
order, not by a lucky draw of cheap requests.

Mix keys::

    {"arrivals": {"process": "poisson", "rate_per_s": 12.0},
     "warmup_s": 6.0,
     "limits": {"agg_outside_4se": 6},
     "classes": [
       {"share": 0.8, "k": [100, 1000],
        "templates": [{"op": "and", "terms": [{"attr": "rotate", "value": 1}]}]},
       {"share": 0.2, "kind": "aggregate", "k": [2000], "measure": [0, 1],
        "error_slo": [0.3, 0.5],
        "templates": [{"op": "or", "terms": [{"attr": "rotate", "value": 1},
                                             {"attr": "rotate", "value": 1}]}]}]}

A class without ``kind`` is an exemplar class: ``k`` lists the rows wanted.
A class with ``"kind": "aggregate"`` asks for the mean of a measure over the
predicate's rows: ``measure`` lists measure indices, ``error_slo`` target 95%
CI half-widths on the mean (in the measure's units; ``null`` sets none, and
the request reads its whole design or its round budget), ``k`` the size of the
any-k chosen arm that seeds the design (``AggregateRequest.k``, not a row
target).  A class's requests cross its templates with its ``k`` (and, for
an aggregate class, its ``measure`` and ``error_slo``) lists, split evenly by
largest remainder.

``limits`` overrides the limits of the checks (``harness.limits``).  A mix
that holds an aggregate class has to state ``agg_outside_4se``, the number
of answers whose mean may lie more than 4 standard errors from the full
scan's.  A sound estimator does so now and then, and the limit is set from
that sound rate, never from readings of the program (whose own standard
error may read low): an SE taken from about 8 random-arm blocks is Student's
t with about 7 degrees of freedom, beyond 4 in p = 0.0052 of designs.
Answers on one predicate, measure and ``k`` share one design (the program
seeds every design alike) and miss together.  With D such designs in the
window and m answers on each, the limit is m·L, where L is the smallest
count with P(Binomial(D, p) > L) < 1/1000: a sound run exceeds it in fewer
than 1 run in 1,000.  (D = 16, m = 3: L = 2, the limit 6.)  The mix's
``about`` gives its D, m and L.  A mix without the limit is refused when it
is loaded.

A term's ``attr`` is an index of the configuration's ``num_dims``
attributes, or ``"rotate"``: the i-th term of the j-th request of a template
takes attribute ``(j + 3·i) mod r``.  Its ``value`` is the value it asks for.

An arrival process is a module with ``make(params, seconds, rng, requests)``
returning an object with ``due(rel_s)`` (pop the arrivals due by then, each
with its ``due_s``), ``next_due()`` (the next known due time, or ``None``)
and ``done(arrival, rel_s)`` (a request was answered: a closed loop releases
the next one); ``requests(n)`` gives the window's first ``n`` requests in the
seed's order.  :class:`Schedule` is the open-loop case.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np

from chipbench import parallel

ROOT = Path(__file__).resolve().parent
EXEMPLAR = "exemplar"
AGGREGATE = "aggregate"


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float  # offset from the window's start
    predicates: tuple  # ((attr, value), ...)
    op: str
    k: int = 0  # rows wanted; an aggregate's chosen-arm size
    kind: str = EXEMPLAR
    measure: int = 0  # an aggregate's measure index
    error_slo: float | None = None  # an aggregate's target 95% CI half-width


def class_kind(cls: dict) -> str:
    return cls.get("kind", EXEMPLAR)


def has_aggregates(mix: dict) -> bool:
    return any(class_kind(c) == AGGREGATE for c in mix["classes"])


def load_mix(name: str, root: Path = ROOT) -> dict:
    path = root / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    for c in mix["classes"]:
        if class_kind(c) not in (EXEMPLAR, AGGREGATE):
            raise ValueError(f"mix {name!r}: class kind {class_kind(c)!r} is neither "
                             f"{EXEMPLAR!r} nor {AGGREGATE!r}")
    if has_aggregates(mix) and "agg_outside_4se" not in mix.get("limits", {}):
        raise ValueError(f"mix {name!r} holds an aggregate class but states no "
                         "limits.agg_outside_4se")
    return mix


def arrival_process(name: str, root: Path = ROOT):
    path = root / "arrivals" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no arrival process {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_arrivals_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Schedule:
    """Open-loop arrivals: a fixed list, sorted by due time."""

    def __init__(self, arrivals: list[Arrival]):
        self.arrivals = arrivals
        self._next = 0

    def due(self, rel_s: float) -> list[Arrival]:
        i = self._next
        while self._next < len(self.arrivals) and self.arrivals[self._next].due_s <= rel_s:
            self._next += 1
        return self.arrivals[i:self._next]

    def next_due(self) -> float | None:
        return self.arrivals[self._next].due_s if self._next < len(self.arrivals) else None

    def done(self, arrival: Arrival, rel_s: float) -> None:
        pass


def _split(n: int, shares: list[float]) -> list[int]:
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = np.asarray(shares, np.float64) / float(sum(shares)) * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: n - int(out.sum())]:
        out[i] += 1
    return [int(x) for x in out]


def class_requests(cls: dict, n: int, cfg: dict) -> list[Arrival]:
    """The class's ``n`` requests, in a fixed order."""
    r = int(cfg["num_dims"])
    if class_kind(cls) == AGGREGATE:
        asks = [dict(k=int(k), kind=AGGREGATE, measure=int(m),
                     error_slo=None if e is None else float(e))
                for k in cls["k"] for m in cls["measure"] for e in cls["error_slo"]]
    else:
        asks = [dict(k=int(k)) for k in cls["k"]]
    combos = [(t, ask) for t in cls["templates"] for ask in asks]
    out = []
    for (tmpl, ask), cnt in zip(combos, _split(n, [1.0] * len(combos))):
        terms = tmpl["terms"]
        for j in range(cnt):
            preds = tuple(((j + 3 * i) % r if t["attr"] == "rotate" else int(t["attr"]),
                           int(t["value"])) for i, t in enumerate(terms))
            out.append(Arrival(0.0, preds, tmpl["op"], **ask))
    return out


def requests(mix: dict, cfg: dict, n: int, rng: np.random.Generator) -> list[Arrival]:
    """``n`` requests split over the mix's classes by share, in the order
    ``rng`` permutes them to."""
    reqs: list[Arrival] = []
    classes = mix["classes"]
    for cls, cnt in zip(classes, _split(n, [float(c["share"]) for c in classes])):
        reqs.extend(class_requests(cls, cnt, cfg))
    return [reqs[i] for i in rng.permutation(len(reqs))]


def arrivals(mix: dict, cfg: dict, seconds: float, seed: int, stream: int = 0,
             root: Path = ROOT, **override):
    """The mix's arrival process over a window of ``seconds``.

    ``stream`` separates the warm-up's draws from the window's; ``override``
    replaces arrival parameters (the knee sweep's ``rate_per_s``).
    """
    params = {**mix["arrivals"], **override}
    rng = np.random.default_rng(parallel.seed_seq(seed, 5381, stream))
    return arrival_process(params["process"], root).make(
        params, seconds, rng, lambda n: requests(mix, cfg, n, rng))
